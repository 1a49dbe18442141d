"""What one run measured, handed to every metric reader.

A driver (``harness.drivers.<driver>``) fills a ``RunContext``; each
metric of the cell is then ``metrics/<name>.py``'s ``read(ctx)``, which
returns a number or None when the run holds nothing for it to read.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class RunContext:
    seconds: float                  # length of the measured window
    setup_s: float = 0.0
    t0: float = 0.0                 # window start, perf_counter seconds
    #: offline cells: (seconds from the window's start, reads) of every
    #: batch whose SAM was written, in order, the one in flight at the
    #: window's close included
    completions: list = dataclasses.field(default_factory=list)
    #: served cells: latency (ms, from when each request was due) of
    #: every request sent in the window; None for a request that failed
    latencies_ms: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    #: traced runs: the program's obs counters and stage times over the
    #: reads it aligned while traced (``reads_traced`` of them)
    snapshot: dict | None = None
    reads_traced: int = 0
    #: served runs: engine batches in the window and requests in them,
    #: and [(upper edge, batches)] of their widths
    coalesce: tuple | None = None
    widths: list = dataclasses.field(default_factory=list)
    #: programs compiled inside the window (none, in a sound set-up)
    compiles_in_window: int = 0
    #: traced runs: harness.devtrace.DeviceTrace of the window, and the
    #: program's host spans on the profiler's clock
    device: object = None
    host_spans: list = dataclasses.field(default_factory=list)
    peaks: dict | None = None
