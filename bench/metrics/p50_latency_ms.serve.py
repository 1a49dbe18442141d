"""Median latency of the requests answered in the traced window, from
when each was due, as the client saw it."""

import statistics


def read(ctx):
    lat = [v for v in ctx.latencies_ms if v is not None]
    return statistics.median(lat) if lat else None
