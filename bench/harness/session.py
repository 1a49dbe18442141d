"""Set-up shared by the drivers: the index on the device, the aligner's
options, and, in a traced run, the profiler and the program's spans.

The traced run keeps the program's telemetry on (``obs.Telemetry`` with
its span collector) and runs ``jax.profiler`` over the window.  The
window's start is marked in the profiler's trace by an annotation
(``bench.window``) taken at a known ``time.perf_counter()``; the
collector's clock starts at a ``perf_counter()`` bracketed by two
readings.  That pair of anchors puts the program's spans on the
profiler's clock without reading anything private of either.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

import jax

from .spec import BENCH
from . import devtrace

WINDOW_MARK = "bench.window"


def load_index(fasta):
    """The program's index, loaded, uploaded and with its occ kernel
    configuration attached (``Aligner`` would do the last two lazily on
    the first batch)."""
    from repro.io.store import load_index as _load
    from repro.kernels.engine import attach_occ_config
    idx = _load(fasta)
    jax.block_until_ready(idx.device())
    cfg = attach_occ_config(idx)
    timings = " ".join(f"{lay}/qb{qb}={best * 1e3:.3f}ms"
                       for lay, qb, best in cfg.timings)
    print(f"occ layout picked: {cfg.layout} qb={cfg.qb} ({timings})",
          file=sys.stderr, flush=True)
    return idx


def align_options(config: dict, traffic: dict, overrides: dict | None):
    from repro.options import AlignOptions
    opts = AlignOptions.from_flags(dict(traffic.get("flags", {})),
                                   engine=config["engine"])
    return opts.replace(**overrides) if overrides else opts


class Tracing:
    """Telemetry and profiler of one run; inert when ``enabled`` is
    False (the end-to-end runs keep the program's telemetry off)."""

    def __init__(self, enabled: bool, name: str):
        self.enabled = enabled
        self.telemetry = None
        self.dir = BENCH / ".cache" / "trace" / name
        self.t0 = None
        if enabled:
            from repro import obs
            a = time.perf_counter()
            self.telemetry = obs.Telemetry(trace=True)
            b = time.perf_counter()
            self._epoch = (a + b) / 2

    def start(self) -> None:
        if self.enabled:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def mark_window(self) -> float:
        """Start of the measured window (perf_counter seconds)."""
        if not self.enabled:
            self.t0 = time.perf_counter()
            return self.t0
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            self.t0 = time.perf_counter()
        return self.t0

    def stop(self) -> None:
        if self.enabled:
            jax.profiler.stop_trace()

    def reduce(self, seconds: float):
        """(DeviceTrace of the window, the program's spans that started
        in the window, on the profiler clock)."""
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one profiler trace under "
                               f"{self.dir}, found {len(paths)}")
        dt = devtrace.load(paths[0], WINDOW_MARK, seconds)
        # the collector's ts is microseconds after its epoch; the mark's
        # start on the profiler clock is self.t0 on the perf_counter clock
        off_ns = dt.window[0] - (self.t0 - self._epoch) * 1e9
        spans = []
        for ev in self.telemetry.tracer.to_dict()["traceEvents"]:
            s = ev["ts"] * 1e3 + off_ns
            if ev.get("ph") == "X" and dt.window[0] <= s < dt.window[1]:
                spans.append((ev["name"], s, s + ev["dur"] * 1e3,
                              ev.get("args") or {}))
        return dt, spans
