#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the
cell's own size, in one process (the index is loaded once):

    python bench/control.py --workload <name> --seconds <s> \
        --seeds 11,12,13 --control-seeds 21,22,23 \
        [--traffic <mix> --traffic-seeds 31,32]

* ``--seeds``: sound runs of the program, as ``bench/run.py`` makes them;
* ``--control-seeds``: the program with the cell's control switched on
  (``control.options`` in ``cells/<name>.json``: aligner options that
  break a guarantee the configuration states), which has to read as not
  correct;
* ``--traffic``/``--traffic-seeds``: sound runs of the cell's
  configuration under another traffic mix (a mix that a program fault
  keeps out of the benchmark), with, for every record whose NM differs
  from the reference's, the NM the program gives the same read when it
  is sent reverse-complemented (a second witness).

Each run prints one JSON line (``kind``, ``seed``, the numbers); the
last line lists them all.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402
from harness import reference, session, spec, traffic, world  # noqa: E402


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def witness(cell, idx, seed: int) -> list:
    """(NM tag, recomputed NM, NM of the read sent reverse-complemented)
    of each record whose NM tag the reference disagrees with."""
    from repro.api import Aligner
    work = spec.BENCH / ".cache" / "runs" / cell.name
    _, ref, _ = world.ensure_reference(cell.config["reference"])
    rd = cell.traffic["reads"]
    window = traffic.make_reads(rd, ref, int(rd.get("count", 4096)), seed,
                                "r")
    recs = reference.primaries(open(work / "window.sam"))
    sc = reference.Scoring.from_config(cell.config["scoring"])
    aligner = Aligner(idx, session.align_options(cell.config, cell.traffic,
                                                 None))
    out = []
    for d in window.due:
        fs = recs.get((d.qname, d.end), [])
        if len(fs) != 1 or int(fs[0][1]) & 0x4:
            continue
        f = fs[0]
        flag = int(f[1])
        read = reference.revcomp_read(d.read) if flag & 0x10 else d.read
        sr = reference.score_record(read, ref, int(f[3]) - 1, f[5], sc)
        tag = dict(t.split(":", 1) for t in f[11:]).get("NM")
        if sr is None or tag == f"i:{sr[1]}":
            continue
        flipped = reference.revcomp_read(d.read)
        res = aligner.align([traffic.seq_strings(flipped[None])[0]])
        rec = res.records()[0]
        out.append({"qname": d.qname, "end": d.end, "strand": "-" if
                    flag & 0x10 else "+", "cigar": f[5], "nm_tag": tag,
                    "nm_recomputed": sr[1],
                    "n_bases": int((d.read == 4).sum()),
                    "flipped_strand": "-" if rec.is_rev else "+",
                    "flipped_cigar": rec.cigar, "flipped_nm": rec.nm})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--traffic-seeds", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = bench_run._device(cell)
    bench_run._program()
    fa, _, _ = world.ensure_reference(cell.config["reference"])
    idx = session.load_index(fa)
    control = json.loads((spec.BENCH / "cells" / f"{cell.name}.json")
                         .read_text())["control"]["options"]
    plan = ([("sound", cell, s, None) for s in _seeds(args.seeds)]
            + [("control", cell, s, control)
               for s in _seeds(args.control_seeds)])
    if args.traffic:
        other = dataclasses.replace(cell, traffic=json.loads(
            (spec.BENCH / "traffic" / f"{args.traffic}.json").read_text()))
        plan += [("traffic:" + args.traffic, other, s, None)
                 for s in _seeds(args.traffic_seeds)]
    rows = []
    for kind, c, seed, overrides in plan:
        t = time.perf_counter()
        ctx, numbers, peak = bench_run.measure(c, seed, args.seconds, False,
                                               overrides=overrides, t_start=t,
                                               devices=devices, index=idx)
        row = {"kind": kind, "seed": seed, **numbers,
               "attempted": ctx.attempted, "failed": ctx.failed,
               "setup_s": ctx.setup_s}
        if (kind.startswith("traffic:") and numbers["nm_mismatch"]
                and c.config["driver"] == "mem"):
            row["witness"] = witness(c, idx, seed)
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({"workload": cell.name, "runs": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
