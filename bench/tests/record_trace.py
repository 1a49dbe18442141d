#!/usr/bin/env python3
"""Record a small trace on the chip, the fixture for a test of the trace
reduction (``harness.devtrace``, ``harness.breakdown``) on real planes:

    python bench/tests/record_trace.py [--out <directory>]

Aligns one batch of 8 single-end reads against a 2 Mbp reference with
the ``pallas`` engine, the program's telemetry on, under the profiler,
the way a traced run of ``bench/run.py`` does.  Writes
``small.xplane.pb`` and, beside it, ``small.spans.json`` (the program's
spans on the profiler's clock and the window's length) to ``--out``,
``bench/tests/data`` unless given.
"""

from __future__ import annotations

import argparse
import glob
import json
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import session, simulate, traffic, world  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(HERE / "data"))
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    from repro.api import Aligner
    from repro.options import AlignOptions
    params = {"name": "small", "length_bp": 2_000_000, "seed": 7,
              "repeat_frac": 0.3, "repeat_len": 200}
    fa, ref, _ = world.ensure_reference(params)
    idx = session.load_index(fa)
    reads, _ = simulate.simulate_reads(ref, 16, 100, seed=3, n_rate=0.0)
    seqs = traffic.seq_strings(reads)
    tracing = session.Tracing(True, "record_trace")
    al = Aligner(idx, AlignOptions(engine="pallas"),
                 telemetry=tracing.telemetry)
    al.align(seqs[8:])                          # warm-up: compile
    tracing.start()
    tracing.mark_window()
    t = time.perf_counter()
    al.align(seqs[:8])
    seconds = time.perf_counter() - t
    tracing.stop()
    dt, spans = tracing.reduce(seconds)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    src = glob.glob(str(tracing.dir / "**" / "*.xplane.pb"), recursive=True)
    shutil.copy(src[0], out / "small.xplane.pb")
    (out / "small.spans.json").write_text(json.dumps(
        {"seconds": seconds, "spans": spans}))
    print(json.dumps({"busy_s": dt.busy_s(), "window_s": dt.window_s,
                      "n_ops": len(dt.ops[0]), "top": dt.top_ops(5)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
