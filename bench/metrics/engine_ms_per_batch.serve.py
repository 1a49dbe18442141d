"""Mean time of an engine batch in the aligner (the pipeline's stages
from ``smem`` to ``finalize`` and the SAM records): the program's
``serve.engine`` spans."""

from harness import spans


def read(ctx):
    return spans.mean(spans.durations_ms(ctx.host_spans, "serve.engine"))
