"""Model FLOPs per token of one training step, counted layer by layer.

The count is what the forward and backward passes need, with nothing that
the program chooses to recompute:

* 6 x every parameter that enters a matrix multiplication (2 for the
  forward product, 4 for the two backward products).  The input
  embedding is a gather and is not counted; with tied embeddings the table
  enters the output head's product once, and is counted there.
* attention's score (Q K^T) and value (P V) products: a query at position
  t of a causal layer multiplies against t keys, so over a sequence of S
  the mean is S / 2, and under a sliding window W < S it is
  W (1 - W / 2S).  Both products take 2 x keys x head_dim FLOPs per query
  head forward, times 3 for forward and backward: 12 x heads x head_dim x
  mean keys per layer.

The layers are told apart as the program tells them (``sliding_window``
with ``global_interval``; ``moe`` with ``moe_skip_first`` and
``moe_interval``).  A mixture-of-experts layer counts its router (d x the
experts it spans), its shared experts, a dense residual FFN, and of the
routed experts the share held here: top_k x held / spanned x 3 d d_expert,
with tokens spread evenly over the experts.  The model is a dict of the
program's ``ModelConfig`` fields; a key left out takes the dense default.
A mixer that this count does not cover raises ``ValueError``.
"""

from __future__ import annotations

from fractions import Fraction


def _check_covered(model: dict) -> None:
    uncovered = (
        ("Mamba layers", "mamba", model.get("mamba")),
        ("mLSTM/sLSTM layers", "xlstm_slstm_interval",
         model.get("xlstm_slstm_interval") or model.get("family") == "ssm"),
        ("an encoder-decoder", "enc_dec", model.get("enc_dec")),
        ("a vision or audio prefix", "frontend", model.get("frontend")),
    )
    for what, key, on in uncovered:
        if on:
            raise ValueError(f"the FLOP count does not cover {what} "
                             f"({key}={model.get(key)!r})")


def _head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["num_heads"]


def _window(model: dict, i: int) -> int:
    w, g = model.get("sliding_window", 0), model.get("global_interval", 0)
    if w and g and i % g == g - 1:
        return 0
    return w


def _is_moe(model: dict, i: int) -> bool:
    every = model.get("moe_interval", 1)
    return (model.get("moe") is not None
            and i >= model.get("moe_skip_first", 0)
            and i % every == every - 1)


def layer_matmul_params(model: dict, i: int, published: dict) -> Fraction:
    """Parameters of layer ``i`` that enter a matrix multiplication, with
    the routed experts weighted by the share of them a token uses here."""
    d, hd = model["d_model"], _head_dim(model)
    h, kv = model["num_heads"], model["num_kv_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if not _is_moe(model, i):
        n_mlp = 3 if model.get("act", "swiglu") == "swiglu" else 2
        return Fraction(attn + n_mlp * d * model["d_ff"])
    moe = model["moe"]
    held = moe["num_experts"]
    spanned = published.get("moe.num_experts", held)
    ffn = d * spanned + 3 * d * moe.get("shared_d_expert", 0) \
        * moe.get("num_shared_experts", 0)
    if model.get("dense_residual") and model["d_ff"] > 0:
        ffn += 3 * d * model["d_ff"]
    routed = Fraction(moe["top_k"] * held, spanned) * 3 * d * moe["d_expert"]
    return attn + ffn + routed


def _matmul(model: dict, published: dict) -> Fraction:
    _check_covered(model)
    return sum(layer_matmul_params(model, i, published)
               for i in range(model["num_layers"])) \
        + model["d_model"] * model["vocab_size"]


def mean_keys(window: int, seq_len: int) -> Fraction:
    """Keys a query of a causal layer reads, on average over a sequence."""
    if window and window < seq_len:
        return window * (1 - Fraction(window, 2 * seq_len))
    return Fraction(seq_len, 2)


def _attention(model: dict, seq_len: int) -> Fraction:
    _check_covered(model)
    return 12 * model["num_heads"] * _head_dim(model) * sum(
        mean_keys(_window(model, i), seq_len)
        for i in range(model["num_layers"]))


def _exact(x: Fraction) -> int | float:
    return int(x) if x.denominator == 1 else float(x)


def matmul_params(model: dict, published: dict | None = None) -> int | float:
    """Parameters of the decoder that enter a matrix multiplication, the
    routed experts weighted by the share a token uses."""
    return _exact(_matmul(model, published or {}))


def attention_flops_per_token(model: dict, seq_len: int) -> int | float:
    return _exact(_attention(model, seq_len))


def train_flops_per_token(model: dict, seq_len: int,
                          published: dict | None = None) -> int | float:
    """Model FLOPs of one training token at sequence length ``seq_len``.
    ``published`` maps dotted keys to the published model's values; its
    ``moe.num_experts`` is the experts the router spans, where the model
    holds a share of them."""
    return _exact(6 * _matmul(model, published or {})
                  + _attention(model, seq_len))
