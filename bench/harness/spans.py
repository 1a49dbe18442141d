"""The program's spans in a traced window, as the per-layer metrics read
them: ``ctx.host_spans`` holds (name, start_ns, end_ns, args) for every
``repro.obs`` span that started inside the window, on the profiler's
clock (``harness.session.Tracing.reduce``)."""

from __future__ import annotations

import math


def durations_ms(host_spans, name: str) -> list:
    """Durations (ms) of the spans called ``name``, in trace order."""
    return [(e - s) / 1e6 for n, s, e, _ in host_spans if n == name]


def mean(values):
    return sum(values) / len(values) if values else None


def nearest_rank(values, q: float):
    """The ``q`` quantile (0 < q <= 1) of ``values`` by nearest rank."""
    if not values:
        return None
    return sorted(values)[math.ceil(q * len(values)) - 1]


def compile_ms(host_spans, marker: str):
    """Summed duration (ms) of the program's ``compile`` spans.  None
    where the window holds no ``marker`` span: a program that writes none
    of the spans this one does records no compiles either, and a missing
    ``compile`` span then says nothing."""
    if not any(n == marker for n, _, _, _ in host_spans):
        return None
    return sum(durations_ms(host_spans, "compile"))
