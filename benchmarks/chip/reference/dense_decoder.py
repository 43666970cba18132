"""Plain reference of the dense decoder's training step: loss, gradients,
global-norm clipping and AdamW, in float32 at ``Precision.HIGHEST``.

It follows the block that the program runs (see each configuration's
``departures`` for where that block leaves the published model):

    x = embed[tokens] * sqrt(d_model)
    per layer:  x += W_o attn(rope(W_q h + b_q), rope(W_k h + b_k), W_v h + b_v)
                x += W_down (silu(W_gate h') * W_up h')
                with h = rms(x) * norm1, h' = rms(x) * norm2
    logits = (rms(x) * final_norm) @ (embed^T if tied else lm_head)
    loss = mean over tokens of logsumexp(logits) - logits[label]

Attention is causal, grouped (query head i reads key/value head
i // (heads / kv_heads)), scaled by 1 / sqrt(head_dim), with rotary
embeddings on the whole head (halves rotated, base ``rope_theta``).

Nothing of the program is imported.  Parameters are stored in the
configuration's dtype, as the program stores them: every update is worked
out in float32 and rounded to that dtype.  The computation runs in blocks
so that it fits beside nothing else on one chip: a scan over the rows of
the batch, each layer under ``jax.checkpoint``, attention by blocks of
queries against the keys they can see, and the loss by blocks of rows.

``make_dot`` takes a rounding of the operands.  The check's control is
this reference one precision below the configuration's (``LOWER``): its
parameters stored, and every matrix-product operand (and cotangent)
rounded, in that precision (``control``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512      # query rows per attention block
LOSS_BLOCK = 512   # rows per block of the output head and loss


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def make_dot(round_fn=None):
    """``dot(spec, a, b)``: an einsum in float32 at HIGHEST, or, with
    ``round_fn``, one whose operands and incoming cotangent are rounded by
    it in the forward and the backward pass alike."""
    if round_fn is None:
        return _einsum

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def dot(spec, a, b):
        return _einsum(spec, round_fn(a), round_fn(b))

    def fwd(spec, a, b):
        ra, rb = round_fn(a), round_fn(b)
        return _einsum(spec, ra, rb), (ra, rb)

    def bwd(spec, res, g):
        _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
        return vjp(round_fn(g))

    dot.defvjp(fwd, bwd)
    return dot


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, dot):
    """q [S,H,D], k/v [S,K,D] -> [S,H,D]; block i of queries reads keys
    0 .. end of block i, masked causally inside the block."""
    s, h, d = q.shape
    kv = k.shape[1]
    q = (q / math.sqrt(d)).reshape(s, kv, h // kv, d)
    outs = []
    for start in range(0, s, Q_BLOCK):
        end = min(start + Q_BLOCK, s)

        @jax.checkpoint
        def block(qb, kb, vb, start=start, end=end):
            sc = dot("qkgd,tkd->kgqt", qb, kb)
            qpos = jnp.arange(start, end)[:, None]
            sc = jnp.where(jnp.arange(end)[None, :] <= qpos, sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return dot("kgqt,tkd->qkgd", p, vb)

        outs.append(block(q[start:end], k[:end], v[:end]))
    return jnp.concatenate(outs, 0).reshape(s, h, d)


def _layer(p, x, cos, sin, model, dot):
    eps, hd = model["norm_eps"], model["head_dim"]
    s = x.shape[0]
    h = _rms(x, p["norm1"], eps)
    a = p["attn"]
    q, k, v = (dot("sd,de->se", h, a[w]) for w in ("w_q", "w_k", "w_v"))
    if "b_q" in a:
        q, k, v = q + a["b_q"], k + a["b_k"], v + a["b_v"]
    q = _rope(q.reshape(s, model["num_heads"], hd), cos, sin)
    k = _rope(k.reshape(s, model["num_kv_heads"], hd), cos, sin)
    v = v.reshape(s, model["num_kv_heads"], hd)
    o = _attention(q, k, v, dot).reshape(s, -1)
    x = x + dot("se,ed->sd", o, a["w_o"])
    h = _rms(x, p["norm2"], eps)
    m = p["mlp"]
    up = dot("sd,df->sf", h, m["w_up"])
    if model["act"] == "swiglu":
        up = jax.nn.silu(dot("sd,df->sf", h, m["w_gate"])) * up
    else:
        up = jax.nn.gelu(up)
    return x + dot("sf,fd->sd", up, m["w_down"])


def _layers(params):
    """Per-layer parameter dicts; a scanned stage stacks its layers on a
    leading axis."""
    out = []
    for stage in params["stages"]:
        blk = stage["blk00"]
        if blk["attn"]["w_q"].ndim == 3:
            out += [jax.tree.map(lambda a, l=l: a[l], blk)
                    for l in range(blk["attn"]["w_q"].shape[0])]
        else:
            out.append(blk)
    return out


def seq_nll_sum(params, tokens, labels, model, dot):
    """Sum over one sequence's tokens of -log p(label)."""
    s = tokens.shape[0]
    hd = model["head_dim"]
    freq = 1.0 / (model["rope_theta"] ** (jnp.arange(0, hd // 2,
                                                     dtype=jnp.float32)
                                          * 2 / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = params["embed"][tokens] * math.sqrt(model["d_model"])
    layer = jax.checkpoint(functools.partial(_layer, model=model, dot=dot))
    for p in _layers(params):
        x = layer(p, x, cos, sin)
    x = _rms(x, params["final_norm"], model["norm_eps"])
    tied = model["tie_embeddings"]
    w = params["embed"] if tied else params["lm_head"]
    spec = "sd,vd->sv" if tied else "sd,dv->sv"

    @jax.checkpoint
    def block(xb, lb, w):
        logits = dot(spec, xb, w)
        ll = jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - ll)

    return sum(block(x[i:i + LOSS_BLOCK], labels[i:i + LOSS_BLOCK], w)
               for i in range(0, s, LOSS_BLOCK))


def mean_loss(params, tokens, labels, model, dot):
    """Mean -log p(label) over a batch [B, S], one row at a time."""
    def body(acc, row):
        return acc + seq_nll_sum(params, row[0], row[1], model, dot), None
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (tokens, labels))
    return total / tokens.size


def lr_at(opt: dict, step: int) -> float:
    """Warmup, then cosine decay to a tenth of the peak."""
    peak, warm, total = (opt["learning_rate"], opt["warmup_steps"],
                         opt["total_steps"])
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def _decays(path) -> bool:
    name = str(getattr(path[-1], "key", path[-1]))
    return "norm" not in name and not name.startswith("b_")


def _grad_fn(model, dot):
    @jax.jit
    def grad(params, tokens, labels):
        loss, g = jax.value_and_grad(mean_loss)(params, tokens, labels,
                                                model, dot)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        return loss, g, gnorm
    return grad


def round_to(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in float32.  A cast to
    bfloat16 and back may be folded away by XLA (excess precision is
    allowed by default); ``reduce_precision`` is not."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


# the nearest precision below a configuration's, in which the control runs
LOWER = {"float32": "bfloat16"}


def control(model: dict) -> tuple[dict, object]:
    """(model, dot) of the control: ``model`` stored in the precision below
    its own, and a ``dot`` that rounds every operand to it."""
    low = LOWER[model["dtype"]]
    return (dict(model, dtype=low),
            make_dot(functools.partial(round_to, dtype=jnp.dtype(low))))


def _adam_fn(opt, dtype):
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(params, g, m, v, t, lr, scale):
        def leaf(path, p, g, m, v):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            if _decays(path):
                upd = upd + wd * p
            return round_to(p - lr * upd, dtype), m, v
        out = jax.tree_util.tree_map_with_path(leaf, params, g, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)
    return update


def train(model: dict, opt: dict, params, batches, dot=_einsum,
          on_first_grad=None):
    """Run ``len(batches)`` training steps from ``params``.

    ``batches`` are {tokens, labels} of the whole global batch.  Returns
    (losses, parameters after the last step).  ``on_first_grad(g, scale)``
    receives the first step's gradient and its clipping factor: the
    optimizer gets ``g * scale``.
    """
    dtype = jnp.dtype(model["dtype"])
    grad = _grad_fn(model, dot)
    update = _adam_fn(opt, dtype)
    p = jax.tree.map(lambda a: round_to(jnp.asarray(a, jnp.float32), dtype),
                     params)
    del params
    # The moments wait on the host while a gradient is worked out, so that
    # only the parameters sit beside it on the chip.
    m = v = None
    losses = []
    with jax.default_matmul_precision("highest"):
        for step, b in enumerate(batches):
            if m is not None:
                m, v = jax.device_get((m, v))
            loss, g, gnorm = grad(p, b["tokens"], b["labels"])
            if m is None:
                m, v = (jax.tree.map(jnp.zeros_like, g) for _ in range(2))
            clip = opt["grad_clip"]
            scale = (jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
                     if clip > 0 else jnp.ones((), jnp.float32))
            if step == 0 and on_first_grad is not None:
                on_first_grad(g, scale)
            p, m, v = update(p, g, m, v, jnp.float32(step + 1),
                             jnp.float32(lr_at(opt, step)), scale)
            del g
            losses.append(float(loss))
    return losses, p
