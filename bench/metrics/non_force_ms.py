"""non_force_ms: device busy time outside the force kernel, per step.

The device's busy time over the traced window less the time of the
``rcll_force`` operations, per step, in ms: the rebuild, the cell-table
pack, integration and the RCLL advance together.
"""
KERNEL = "rcll_force"


def read(ctx):
    if ctx.trace is None or ctx.steps <= 0:
        return None
    s = ctx.trace.busy_s() - ctx.trace.op_s(KERNEL)
    return 1e3 * s / ctx.steps if s > 0 else None
