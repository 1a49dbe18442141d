#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process holds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell's configuration, traffic, limits
and metrics are found by name from ``BENCHMARK.json`` (see
``bench/README.md``).  The run:

1. set-up: builds the configuration's index on a checkout's first run
   (kept under ``bench/.cache/``), loads it onto the device, generates
   the reads and arrivals from ``--seed``, and warms up the cell's own
   batch shapes;
2. window: drives the program for ``--seconds`` seconds, with the
   program's telemetry and the profiler on only under ``--trace 1``;
3. checks what the window produced against the plain reference
   (``harness.reference``);
4. prints one JSON line as the last line of standard output: ``correct``,
   ``attempted``, ``failed``, the cell's end-to-end metrics (``--trace
   0``) or per-layer metrics (``--trace 1``), the device, the traced
   ``breakdown``, and last the numbers compared with their limits, which
   are also the last lines of standard error.

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not beside it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import peaks as peak_table  # noqa: E402
from harness import spec, world  # noqa: E402
from harness.breakdown import breakdown  # noqa: E402

#: JAX records the first is around every program it builds, from the
#: persistent cache or compiled, and the second for each cache hit
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Failure(Exception):
    """A run that cannot give a result (exit 2, nothing printed)."""


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _device(cell):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Failure(f"no TPU: JAX's first device is "
                      f"{devices[0].platform!r}; there is no CPU fallback")
    if len(devices) < cell.chips:
        raise Failure(f"the cell needs {cell.chips} chips, JAX sees "
                      f"{len(devices)}")
    return devices


def _program():
    """Put the program on the path, with JAX's persistent compile cache
    at the checkout's fixed ``.jax_cache`` (the program's own default),
    whatever cache directory the environment names: the cache stays
    inside the checkout and its path, part of the cache key, never
    moves."""
    import jax
    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        raise Failure(f"no program at {src / 'repro'}: run from the root "
                      f"of a checkout of the repository")
    sys.path.insert(0, str(src))
    cache = str(spec.ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    from repro.kernels.config import enable_compile_cache
    return enable_compile_cache()


def measure(cell, seed: int, seconds: float, trace: bool, *,
            overrides: dict | None = None, t_start: float = T_START,
            devices=None, index=None):
    """Set up, run the window and check it; returns (RunContext, numbers
    compared, memory peak).  ``devices`` is JAX's device list, for the
    peak table; ``overrides`` replace aligner options (the control);
    ``index`` is an index already loaded by this process."""
    import jax
    compiles, hits = [], []      # (end, seconds, name); start times
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(
            (time.perf_counter(), secs, kw.get("fun_name", "?")))
        if event == COMPILE_EVENT else None)
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(time.perf_counter())
        if event == CACHE_HIT_EVENT else None)
    fa, ref, built = world.ensure_reference(cell.config["reference"])
    if built:
        print(f"built the index in {time.perf_counter() - t_start:.1f} s "
              f"(first run in this checkout)", file=sys.stderr, flush=True)
    driver = importlib.import_module(f"harness.drivers.{cell.config['driver']}")
    ctx, numbers, peak = driver.run(cell, fa, ref, seed, seconds, trace,
                                    t_start, overrides, index)
    if devices is not None:
        ctx.peaks = peak_table.lookup(devices[0].device_kind)
    built = [(t, secs, name) for t, secs, name in compiles
             if not any(t - secs <= h <= t for h in hits)]
    n_in = sum(1 for t, _, _ in compiles if ctx.t0 <= t <= ctx.t0 + seconds)
    late = [(secs, name) for t, secs, name in built
            if ctx.t0 <= t <= ctx.t0 + seconds]
    ctx.compiles_in_window = len(late)
    print(f"programs first used inside the window: {n_in}, compiled "
          f"{len(late)} in {sum(s for s, _ in late):.3f} s "
          f"({', '.join(sorted({n for _, n in late})) or '-'}); whole run: "
          f"{len(compiles)}, compiled {len(built)}",
          file=sys.stderr, flush=True)
    return ctx, numbers, peak


def result(cell, ctx, numbers, peak, devices, trace: bool) -> dict:
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(ctx)
        if value is None:
            if not trace:
                raise Failure(f"the run gave no {m['name']}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in cell.limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": ctx.attempted,
           "failed": ctx.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx.device.busy_s()
        device["window_s"] = ctx.device.window_s
        out["breakdown"] = breakdown(ctx.device, ctx.host_spans)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = _args(argv)
    try:
        cell = spec.load_cell(args.workload)
        devices = _device(cell)
        _program()
        ctx, numbers, peak = measure(cell, args.seed, args.seconds,
                                     bool(args.trace), devices=devices)
        out = result(cell, ctx, numbers, peak, devices, bool(args.trace))
    except Failure as e:
        print(f"bench/run.py: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(f"compared: {numbers['compared']} reads; "
          f"gap over 10: {numbers['gap_over_10']}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
