"""Device self time of rematerialisation, ms per step: the forward ops
``jax.checkpoint`` runs again in the backward pass
(``rematted_computation``), the mean over the chips."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.class_ms(ctx, "recompute")
