"""Milliseconds spent building XLA programs inside the window: the
program's ``compile`` spans, one per program JAX built (compiled or read
from its persistent cache) on a thread with telemetry on.  None from a
program that does not split finalize."""

from harness import spans


def read(ctx):
    return spans.compile_ms(ctx.host_spans, "finalize.replay")
