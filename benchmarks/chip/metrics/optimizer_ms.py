"""Device self time of the optimizer, ms per step: the ops under
``optimizer`` (global norm, clipping, the update; ``train/step.py``), the
mean over the chips."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.class_ms(ctx, "optimizer")
