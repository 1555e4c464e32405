"""rebuilds_per_step: rebuilds of the cell lists per step.

The solver's own counters over the traced window: the change of
``carry.rebuilds`` over the change of ``carry.steps``. An exact count.
"""


def read(ctx):
    c = ctx.counters
    if c.get("steps", 0) <= 0:
        return None
    return c["rebuilds"] / c["steps"]
