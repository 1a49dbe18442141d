"""Milliseconds the service spent building XLA programs inside the
window: the program's ``compile`` spans, one per program JAX built
(compiled or read from its persistent cache) on a thread with telemetry
on.  None from a program that records no request lifecycle."""

from harness import spans


def read(ctx):
    return spans.compile_ms(ctx.host_spans, "serve.batch")
