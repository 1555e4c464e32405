"""device_idle_pct: the share of the traced window the device sat idle.

100 × (1 − busy / window), busy being the union of the intervals in
which any operation ran on the device, averaged over the devices.
"""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
