"""Device self time of the forward pass, ms per step: the ops under
``jvp(forward)`` and no transpose (``train/step.py`` opens ``forward`` inside
the function it differentiates), the mean over the chips."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.class_ms(ctx, "forward")
