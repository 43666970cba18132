"""Device self time of the backward pass, ms per step: the ops under
``transpose(jvp(forward))`` less the recomputed ones, and the microbatch
sum (``accumulate``), the mean over the chips."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.class_ms(ctx, "backward")
