"""Share of the traced window in which no op ran on the device, averaged
over the chips (device trace): 1 - busy / window, in %.  It reads
``idle_share.<cell kind>``, one entry for each end-to-end metric it moves."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.facts["window_s"])
