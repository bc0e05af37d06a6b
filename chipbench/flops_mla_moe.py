"""Operations a training step of a latent-attention, mixture-of-experts
model (DeepSeek-V2's block) needs, from its configuration's shapes.

``train_flops_per_token`` counts the model FLOPs of training, forward and
backward, with no recomputation: 6 × the parameters a token's products
touch, plus attention's score and value products over the positions each
token attends to (causal).  A token touches every layer's MLA projections
(``wq``, ``w_dkv``, ``w_kpe``, ``w_uk``, ``w_uv``, ``wo``), the dense
layers' MLP, each MoE layer's router and shared experts, of the routed
experts held here the share that routes to them (top_k · held ÷ router
width, in expectation), and the output head.  Scores take the query/key
width (nope + rope dims) and the value product the value width, per head.
Norms and the embedding lookup count nothing.  ``flops.train_flops_per_token``
counts every layer as a dense MLP of width ``d_ff`` and does not fit this
block.
"""

from __future__ import annotations

from flops import mean_context


def mla_params(m: dict) -> int:
    d, h, r = m["d_model"], m["n_heads"], m["mla_kv_lora_rank"]
    nope, rope, v = m["mla_qk_nope_dim"], m["mla_qk_rope_dim"], m["mla_v_dim"]
    return d * h * (nope + rope) + d * r + d * rope + r * h * (nope + v) + h * v * d


def moe_params_per_token(m: dict) -> float:
    d, f = m["d_model"], m["moe_d_ff"]
    routed_here = m["moe_top_k"] * m["moe_experts"] / m["moe_router_experts"]
    return (routed_here * 3 * d * f + 3 * d * m["moe_shared_experts"] * f
            + d * m["moe_router_experts"])


def train_flops_per_token(m: dict, seq: int) -> float:
    pattern = m["block_pattern"]
    dense = sum(k == "mla_dense" for k in pattern) * 3 * m["d_model"] * m["d_ff"]
    moe = sum(k == "mla_moe" for k in pattern) * moe_params_per_token(m)
    touched = len(pattern) * mla_params(m) + dense + moe + m["vocab_size"] * m["d_model"]
    attn = 6 * len(pattern) * m["n_heads"] * (
        m["mla_qk_nope_dim"] + m["mla_qk_rope_dim"] + m["mla_v_dim"]) * mean_context(seq, None)
    return 6 * touched + attn
