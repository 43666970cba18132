"""Device self time of the attention core, ms per step: the ops under
``attention`` (``models/layers.py``), in the forward pass, the recompute
and the backward pass alike, the mean over the chips."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "attention")
