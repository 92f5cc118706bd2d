"""Process start until the window opens (host clock): data from the seed,
program build, staging and warm-up compiles."""


def read(ctx):
    return ctx.setup["setup_s"]
