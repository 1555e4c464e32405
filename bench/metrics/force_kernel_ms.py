"""force_kernel_ms: device time of the force kernel per step.

The summed device time of the operations named ``rcll_force`` (the
Pallas kernel's own name) over the traced window, per step, in ms.
Nothing when the trace holds no such operation.
"""
KERNEL = "rcll_force"


def read(ctx):
    if ctx.trace is None or ctx.steps <= 0:
        return None
    s = ctx.trace.op_s(KERNEL)
    return 1e3 * s / ctx.steps if s > 0 else None
