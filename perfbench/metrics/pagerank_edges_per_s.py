"""Input edges (stored, not padded slots) times PageRank rounds completed
in the window, over the window's seconds (host clock)."""


def read(ctx):
    f = ctx.facts
    if "rounds" not in f:
        return None
    return f["edges"] * f["rounds"] / f["window_s"]
