"""95th percentile of the union program's run time on chip 0, in ms,
over every run in the traced window (device trace): the tail of the calls,
on the device's clock."""

import numpy as np


def read(ctx):
    t, f = ctx.trace, ctx.facts
    if t is None or "calls" not in f or not t["modules"]:
        return None
    runs = max(t["modules"].values(), key=sum)
    return 1e3 * float(np.percentile(runs, 95))
