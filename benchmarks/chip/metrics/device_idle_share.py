"""Share of the traced window in which no operation ran on a device: one
minus the union of its op intervals over the window.  Largest over the
devices."""

from benchmarks.chip import trace


def read(ctx):
    if not ctx.trace.ops:
        return None
    span = ctx.hi - ctx.lo
    return max(100.0 * (1.0 - trace.busy(ops, ctx.lo, ctx.hi) / span)
               for ops in ctx.trace.ops.values())
