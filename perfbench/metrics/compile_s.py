"""Seconds of compilation during set-up: JAX's backend-compile and
persistent-cache-read events (program spans of JAX's own monitoring)."""


def read(ctx):
    return ctx.setup["compile_s"]
