"""Seconds from the process's start to the window's: JAX's start, the
index load (and build, on a checkout's first run), the upload, the
traffic generation and the warm-up, with any compilation in them."""


def read(ctx):
    return ctx.setup_s
