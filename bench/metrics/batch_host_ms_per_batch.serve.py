"""Mean host time of an engine batch outside the aligner: each
``serve.batch`` span less its ``serve.engine`` child (packing the
batch, the SAM text, splitting it per request and sending the frames),
over the batches whose engine run started in the window."""

from harness import spans


def read(ctx):
    engine: dict = {}
    for name, s, e, args in ctx.host_spans:
        if name == "serve.engine":
            b = args.get("batch")
            engine[b] = engine.get(b, 0) + e - s
    own = [(e - s - engine[args.get("batch")]) / 1e6
           for name, s, e, args in ctx.host_spans
           if name == "serve.batch" and args.get("batch") in engine]
    return spans.mean(own)
