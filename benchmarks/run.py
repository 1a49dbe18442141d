"""Benchmark harness — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows.  Run:
  PYTHONPATH=src python -m benchmarks.run [--only smem,sal,bsw,e2e,scaling]

``--ci`` shrinks every suite to CI-smoke sizes; ``--json PATH`` writes
all rows (plus per-suite wall time and a telemetry-on per-stage
``kernel_breakdown``) as JSON — the CI bench-smoke job uploads that file
as the ``BENCH_ci.json`` artifact so the repo's perf trajectory is
recorded per-PR.  ``--profile PATH`` additionally writes the same
telemetry pass as a standalone ``repro.cli report``-compatible profile.

Every invocation that writes JSON also gets a run id and a structured
JSONL run log (``--runlog``, default ``<json>.runlog.jsonl``): a
manifest event, ``suite_start``/``suite_end`` brackets with wall time
and row counts, captured warnings, the regression-gate verdict, and a
crash bundle on failure — so a dead CI job leaves a parseable trail.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import platform
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only",
                    default="smem,sal,bsw,e2e,scaling,pe,io,dist,serve")
    ap.add_argument("--ci", action="store_true",
                    help="CI-smoke sizes for every suite")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all rows as JSON to PATH")
    ap.add_argument("--profile", default=None, metavar="PATH",
                    help="also write a repro.cli-report-compatible profile "
                         "of one telemetry-on batched-engine pass to PATH")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="compare the fresh payload against a committed "
                         "baseline JSON (benchmarks/baseline_ci.json) and "
                         "exit non-zero on structural or tolerance-band "
                         "regressions (requires --json)")
    ap.add_argument("--runlog", default=None, metavar="JSONL",
                    help="structured run-log path (manifest, per-suite "
                         "progress, crash bundle). Defaults to "
                         "<json>.runlog.jsonl when --json is set; 'off' "
                         "disables")
    args = ap.parse_args()
    if args.baseline and not args.json:
        ap.error("--baseline requires --json")
    if args.ci:
        # must precede the bench imports: common.py reads it at import
        os.environ["REPRO_BENCH_CI"] = "1"
    picks = set(args.only.split(","))
    from repro import obs
    from repro.kernels.config import enable_compile_cache
    enable_compile_cache()
    runlog_path = args.runlog
    if runlog_path is None and args.json:
        runlog_path = os.path.splitext(args.json)[0] + ".runlog.jsonl"
    runlog = None
    if runlog_path and runlog_path not in ("off", "-"):
        runlog = obs.RunLog(runlog_path)
        runlog.manifest("benchmarks.run", argv=sys.argv[1:],
                        ci_mode=args.ci, suites=sorted(picks))
        print(f"# run {runlog.run_id}: logging events to {runlog_path}",
              flush=True)
    try:
        _run_suites(args, picks, runlog)
    except SystemExit:
        raise
    except BaseException as e:
        if runlog is not None:
            runlog.crash(e)
            runlog.end(status="error")
            runlog.close()
        raise
    if runlog is not None:
        runlog.close()


def _run_suites(args, picks, runlog) -> None:
    from . import common, bench_smem, bench_sal, bench_bsw, bench_e2e, \
        bench_scaling, bench_pe, bench_io, bench_dist, bench_serve
    suites = {
        "smem": ("Table 4 (SMEM kernel)", bench_smem.run),
        "sal": ("Table 5 (SAL kernel)", bench_sal.run),
        "bsw": ("Tables 6-8 (BSW kernel)", bench_bsw.run),
        "e2e": ("Figure 5 (end-to-end)", bench_e2e.run),
        "scaling": ("Figure 4 (scaling)", bench_scaling.run),
        "pe": ("PE mate rescue (scalar vs batched)", bench_pe.run),
        "io": ("I/O subsystem (ingestion + index bundle)", bench_io.run),
        "dist": ("Resilient memdist (merge + recovery overhead)",
                 bench_dist.run),
        "serve": ("Always-on service (continuous batching)",
                  bench_serve.run),
    }
    warn_ctx = (runlog.capture_warnings() if runlog is not None
                else contextlib.nullcontext())
    print("name,value,derived")
    suite_s = {}
    with warn_ctx:
        for key, (title, fn) in suites.items():
            if key not in picks:
                continue
            print(f"# --- {title} ---", flush=True)
            if runlog is not None:
                runlog.emit("suite_start", suite=key, title=title)
            t0 = time.time()
            n0 = len(common.ROWS)
            fn()
            suite_s[key] = round(time.time() - t0, 1)
            if runlog is not None:
                runlog.emit("suite_end", suite=key, wall_s=suite_s[key],
                            rows=len(common.ROWS) - n0)
            print(f"# {key} done in {suite_s[key]:.1f}s", flush=True)
        breakdown = snap = wall = None
        breakdown_pallas = None
        if args.json or args.profile:
            breakdown, snap, wall = common.profiled_world_run()
            print(f"# profiled one batched pass in {wall:.2f}s", flush=True)
    if args.json:
        # smaller read set: the pallas pass runs the kernel bodies in
        # interpret mode on CPU runners
        bp, _, wp = common.profiled_world_run(
            "pallas", n_reads=common.scaled(128, 24))
        breakdown_pallas = bp
        print(f"# profiled one pallas pass in {wp:.2f}s", flush=True)
        payload = {
            "ci_mode": args.ci,
            "python": sys.version.split()[0],
            "platform": platform.platform(),
            "suites_s": suite_s,
            "rows": common.ROWS,
            "kernel_breakdown": breakdown,
            "kernel_breakdown_pallas": breakdown_pallas,
        }
        if runlog is not None:
            payload["run"] = runlog.run_id
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(common.ROWS)} rows to {args.json}", flush=True)
        if args.baseline:
            from .regression import compare, render
            failures, notes = compare(payload, json.load(open(args.baseline)))
            print(render(failures, notes), flush=True)
            if runlog is not None:
                runlog.emit("regression_gate", failures=len(failures),
                            notes=len(notes),
                            detail=failures if failures else None)
            if failures:
                if runlog is not None:
                    runlog.end(status="regression", rows=len(common.ROWS))
                sys.exit(1)
    if args.profile:
        from repro import obs
        meta = {"source": "benchmarks.run", "ci_mode": args.ci}
        if runlog is not None:
            meta["run"] = runlog.run_id
        obs.write_profile(args.profile, snap, wall_s=wall, meta=meta)
        print(f"# wrote profile to {args.profile} "
              f"(render: python -m repro.cli report {args.profile})",
              flush=True)
    if runlog is not None:
        runlog.end(status="ok", rows=len(common.ROWS),
                   suites_s=suite_s)


if __name__ == "__main__":
    main()
