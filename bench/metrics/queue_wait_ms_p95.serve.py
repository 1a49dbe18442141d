"""95th percentile (nearest rank) of the requests' queue wait, from when
each was put on the server's queue to when the scheduler formed the
engine batch it joined: the program's ``serve.queue_wait`` spans."""

from harness import spans


def read(ctx):
    return spans.nearest_rank(
        spans.durations_ms(ctx.host_spans, "serve.queue_wait"), 0.95)
