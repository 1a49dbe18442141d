#!/usr/bin/env python3
"""Offered-load sweep of a served cell, to find its knee: the highest
arrival rate the service sustains without refusing requests or letting
its queue grow through the window.  One process, the index loaded once:

    python bench/sweep.py --workload <served cell> --seconds <s> \
        --rates 2,3,4,5,6 --seed <n>

For each rate it runs the cell's window at that rate (the traffic file's
other settings unchanged) and prints one JSON line: requests sent and
failed, p50 and p95 latency, and the median latency of the last third of
the requests over that of the middle third (well above 1 when the queue
grows; the first third meets an empty queue and is left out).  The
cell's traffic file then fixes a rate at about four fifths of the knee.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402
from harness import session, spec, world  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = bench_run._device(cell)
    bench_run._program()
    fa, _, _ = world.ensure_reference(cell.config["reference"])
    idx = session.load_index(fa)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        arrivals = dict(cell.traffic["arrivals"], rate_per_s=rate)
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   arrivals=arrivals))
        ctx, numbers, _ = bench_run.measure(
            c, args.seed, args.seconds, False, t_start=time.perf_counter(),
            devices=devices, index=idx)
        lat = [v for v in ctx.latencies_ms if v is not None]
        third = max(1, len(ctx.latencies_ms) // 3)
        middle = [v for v in ctx.latencies_ms[third:-third] if v is not None]
        last = [v for v in ctx.latencies_ms[-third:] if v is not None]
        row = {"rate_per_s": rate, "sent": ctx.attempted,
               "failed": ctx.failed,
               "p50_ms": statistics.median(lat) if lat else None,
               "p95_ms": (sorted(lat)[max(0, -(-95 * len(lat) // 100) - 1)]
                          if lat else None),
               "growth": (statistics.median(last) / statistics.median(middle)
                          if middle and last else None),
               "batches": ctx.coalesce[0], "widths": ctx.widths,
               "compiles_in_window": ctx.compiles_in_window,
               **{k: numbers[k] for k in ("missing", "nm_mismatch",
                                          "score_gap_max", "offline_diff")}}
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({"workload": cell.name, "sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
