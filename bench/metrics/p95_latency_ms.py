"""95th percentile (nearest rank) of the latency of every request sent
in the window, from when it was due; a failed request counts as longer
than any answered one, so more than 5% failed gives no number."""

import math


def read(ctx):
    lat = ctx.latencies_ms
    if not lat:
        return None
    k = math.ceil(0.95 * len(lat)) - 1
    answered = sorted(v for v in lat if v is not None)
    return answered[k] if k < len(answered) else None
