"""rcll_force_roofline: the force kernel's share of its roofline.

The least time the chip could take for one force pass — the larger of
its FLOPs over peak FLOP/s and its bytes over peak HBM bandwidth, both
counted from the configuration by ``bench/roofline.py`` — over the
kernel's device time per pass. Nothing when the trace holds no kernel.
"""
from bench import roofline

KERNEL = "rcll_force"


def read(ctx):
    if ctx.trace is None or ctx.steps <= 0:
        return None
    per_pass = ctx.trace.op_s(KERNEL) / ctx.steps
    if per_pass <= 0:
        return None
    pct, _ = roofline.roofline_pct(ctx.counts, ctx.peak, per_pass)
    return pct
