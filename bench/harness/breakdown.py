"""Where the traced window's time went: the device's busiest operations,
and its idle time by what the host was doing meanwhile.

Each idle stretch of the device is labelled with the innermost program
span (``repro.obs``: ``io``, ``smem``, ``kernel.fmocc``, ``finalize`` ...)
open on the host at its midpoint, or ``no program span`` where none was
(waiting for requests, the harness itself).  The idle time is summed per
label.
"""

from __future__ import annotations

import heapq

NO_SPAN = "no program span"


def idle_by_host_span(gaps, host_spans) -> dict:
    """{label: idle seconds} over ``gaps`` [(start_ns, end_ns)]."""
    spans = sorted(host_spans, key=lambda s: s[1])
    order = sorted(range(len(gaps)),
                   key=lambda i: (gaps[i][0] + gaps[i][1]) / 2)
    active: list = []                 # heap of (-start, end, name)
    at = 0
    out: dict = {}
    for i in order:
        s, e = gaps[i]
        mid = (s + e) / 2
        while at < len(spans) and spans[at][1] <= mid:
            name, st, en, _ = spans[at]
            heapq.heappush(active, (-st, en, name))
            at += 1
        while active and active[0][1] < mid:
            heapq.heappop(active)
        label = active[0][2] if active else NO_SPAN
        out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return out


def breakdown(device, host_spans, k: int = 10) -> dict:
    idle = idle_by_host_span(device.gaps(), host_spans)
    top = sorted(idle.items(), key=lambda kv: -kv[1])[:k]
    return {"device_ops": device.top_ops(k),
            "idle_gaps": [[name, sec] for name, sec in top]}
