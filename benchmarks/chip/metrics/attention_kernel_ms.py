"""Device self time of the flash-attention kernels, ms per step: the ops
under ``attention`` whose ``op_name`` ends in the kernel's ``pallas_call``
(``kernels/flash_attention``: forward, dQ and dK/dV alike), the mean over
the chips.  None where the traced program runs no such kernel, as on a
path that keeps the chunked scan; read beside ``attention_ms`` it says
how much of the attention core the kernels do."""

from benchmarks.chip import scopes

PRIMITIVE = "pallas_call"


def kernel_names(names: dict) -> dict:
    """The entries of an op-names map that are kernel calls under
    ``attention``."""
    return {n: p for n, p in names.items()
            if p.rsplit("/", 1)[-1] == PRIMITIVE
            and "attention" in scopes.scope_names(p)}


def read(ctx):
    names = kernel_names(scopes.traced_op_names(ctx))
    if not names:
        return None
    return scopes.per_step_ms(ctx, lambda ops: scopes.scope_time(
        ops, names, "attention", ctx.lo, ctx.hi))
