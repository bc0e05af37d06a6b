"""Operations a training step needs, from a configuration's shapes.

``train_flops_per_token`` counts the model FLOPs of training, forward and
backward, with no recomputation: 6 × (non-embedding parameters + output
head parameters), plus attention's score and value products over the
positions each token attends to (causal, and windowed where the
configuration has a window).  The embedding lookup is a gather and counts
nothing.
"""

from __future__ import annotations


def layer_params(m: dict) -> int:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
                       m["d_ff"])
    attn = d * h * hd * 2 + d * kv * hd * 2
    mlp = (3 if m["mlp_style"] in ("swiglu", "geglu") else 2) * d * f
    norms = 2 * d * (2 if m["norm"] == "layernorm" else 1)
    return attn + mlp + norms


def non_embedding_params(m: dict) -> int:
    final_norm = m["d_model"] * (2 if m["norm"] == "layernorm" else 1)
    return m["n_layers"] * layer_params(m) + final_norm


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["d_model"]


def mean_context(seq: int, window: int | None) -> float:
    """Mean number of key positions a query attends to, causal (and windowed)."""
    w = window or seq
    return sum(min(i + 1, w) for i in range(seq)) / seq


def train_flops_per_token(m: dict, seq: int) -> float:
    dense = 6 * (non_embedding_params(m) + head_params(m))
    attn = 12 * m["n_layers"] * m["n_heads"] * m["head_dim"] * mean_context(
        seq, m.get("sliding_window"))
    return dense + attn

