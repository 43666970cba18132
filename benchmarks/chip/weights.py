"""Weights from the seed, and the per-tensor entries that the check compares.

The benchmark makes the weights itself, in one jitted call on the device,
in the dtype they are trained in; the program and the reference are given
the same values.  Only the names and shapes of the parameter tree come
from the program (``jax.eval_shape`` of its ``init``), since the step has
to be fed the tree it was built for.

Rules by leaf name: ``*norm*`` ones, ``b_*`` zeros, ``embed`` N(0, 0.02^2),
every other matrix N(0, 1 / fan_in) with fan_in its second-last axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int, stream: int) -> jax.Array:
    """A raw threefry key from a seed of any size: every bit of the seed
    counts (``jax.random.PRNGKey`` keeps only the low 32 without x64)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _leaf(path, shape: jax.ShapeDtypeStruct, key):
    name = _name(path)
    if "norm" in name:
        return jnp.ones(shape.shape, shape.dtype)
    if name.startswith("b_"):
        return jnp.zeros(shape.shape, shape.dtype)
    scale = 0.02 if name == "embed" else 1.0 / math.sqrt(shape.shape[-2])
    return (jax.random.normal(key, shape.shape, jnp.float32) * scale
            ).astype(shape.dtype)


def make_params(shapes, key):
    """The parameter tree of ``shapes`` filled from ``key`` (traceable)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(flat))
    return jax.tree_util.tree_unflatten(
        treedef, [_leaf(p, s, k) for (p, s), k in zip(flat, keys)])


def entries(shapes, stages) -> list[tuple[str, str, int | None]]:
    """(entry name, leaf path, index on the leaf's leading axis) in tree
    order.  ``stages`` is the program's stage layout, one item per entry of
    ``shapes["stages"]`` with its ``first_layer``, ``period`` and
    ``repeats``.  A scanned stage stacks each of its blocks' leaves over
    the repeats on a leading axis; such a leaf gives one entry per repeat,
    named by its global layer index, so that a fault in one layer is not
    averaged over the others."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        k = jax.tree_util.keystr(path)
        st = stages[path[1].idx] if _name(path[:1]) == "stages" else None
        if st is None or st.repeats <= 1:
            out.append((k, k, None))
            continue
        block = int(_name(path[:3]).removeprefix("blk"))
        out += [(f"{k}#{st.first_layer + r * st.period + block}", k, r)
                for r in range(st.repeats)]
    return out


def entry_sq_norms(tree, ents) -> jax.Array:
    """Squared L2 norm of each entry of ``tree`` (float32, traceable)."""
    by_path = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_flatten_with_path(tree)[0]}
    out = []
    for _, k, rep in ents:
        x = by_path[k] if rep is None else by_path[k][rep]
        out.append(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return jnp.stack(out)


def diff_sq_norms(a, b, ents) -> jax.Array:
    """Squared L2 norm of each entry of ``a - b`` (float32, traceable)."""
    return entry_sq_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b),
        ents)
