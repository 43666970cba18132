"""Model FLOPs per token of one training step of a dense decoder.

The count is what the forward and backward passes need, with nothing that
the program chooses to recompute:

* 6 x every parameter that enters a matrix multiplication (2 for the
  forward product, 4 for the two backward products).  The input
  embedding is a gather and is not counted; with tied embeddings the table
  enters the output head's product once, and is counted there.
* causal attention's score (Q K^T) and value (P V) products: at position
  t a head multiplies against t keys, so over a sequence of S the mean is
  S / 2, and both products take 2 x S/2 x head_dim FLOPs per head forward,
  times 3 for forward and backward: 6 x S x heads x head_dim per layer.
"""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters of the decoder that enter a matrix multiplication."""
    d, hd = model["d_model"], model["head_dim"]
    h, kv = model["num_heads"], model["num_kv_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    n_mlp = 3 if model["act"] == "swiglu" else 2
    mlp = n_mlp * d * model["d_ff"]
    head = d * model["vocab_size"]
    return model["num_layers"] * (attn + mlp) + head


def attention_flops_per_token(model: dict, seq_len: int) -> int:
    return 6 * model["num_layers"] * model["num_heads"] * model["head_dim"] \
        * seq_len


def train_flops_per_token(model: dict, seq_len: int) -> int:
    """Model FLOPs of one training token at sequence length ``seq_len``."""
    return 6 * matmul_params(model) + attention_flops_per_token(model, seq_len)
