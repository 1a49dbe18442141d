"""Requests per engine batch in the traced window, from the server's
``serve_coalesce_width`` histogram."""


def read(ctx):
    if not ctx.coalesce or ctx.coalesce[0] == 0:
        return None
    batches, requests = ctx.coalesce
    return requests / batches
