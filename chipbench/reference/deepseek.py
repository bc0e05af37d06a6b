"""DeepSeek family: the float32 reference for DeepSeek-V2-Lite.

Follows DeepSeek-V2 (arXiv:2405.04434) and DeepSeek-V2-Lite's published
config: a decoder whose layers all use multi-head latent attention (MLA)
and, after ``first_k_dense_replace`` dense layers, a DeepSeekMoE layer in
place of the MLP.

* MLA, no query compression: q = x·W_q split into 128 "nope" and 64 rope
  dims per head; the key/value latent c = RMSNorm(x·W_dkv) (rank 512,
  ``kv_a_layernorm``), k_nope = c·W_uk, v = c·W_uv per head, and one rope
  key x·W_kpe that all heads share.  Softmax scale 1/√192 times YaRN's
  mscale(mscale_all_dim)².
* YaRN rotary embedding (``rope_scaling``: factor 40 over 4096 original
  positions, β_fast 32, β_slow 1, mscale = mscale_all_dim = 0.707, so
  cos/sin are unscaled): per frequency pair a linear ramp between the
  original frequency and the frequency ÷ 40, between the dims at which a
  frequency turns β_fast and β_slow times over 4096 positions.
* RoPE rotates interleaved pairs (dims 0/1, 2/3, …).  DeepSeek's own code
  first permutes each head's rope dims from interleaved pairs to halves
  and then rotates halves; both query and key are permuted alike, so the
  scores are the same numbers: this is the published function, not a
  departure.
* DeepSeekMoE: a softmax router over all the router's experts, the top 6
  taken greedily, raw gates (``norm_topk_prob`` false); 2 shared SwiGLU
  experts as one of width 2 × 1408; the per-sequence balance loss
  α·Σ_i f_i·P_i with f_i = E/(k·S)·count_i and P_i the row's mean score.
  Of the routed experts only the chip's share is held (ids ``[0, held)``
  of the router's width): each held expert is applied to every token and
  weighted by its gate, which is zero where the token did not choose it.
  What the experts held elsewhere would add is left out, as in the program.

Departures, which the program makes too:

* RMSNorm's weight is stored as an offset from one (``x̂ · (1 + w)``);
* training is dropless: DeepSeek-V2's own training dropped tokens over a
  device-level capacity factor of 1.0 (the Hugging Face modeling code is
  dropless).

The parameter tree has the program's layout: one segment per run of equal
block kinds, its layers' weights stacked along a leading axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import F32, NEG, Init, Mat, fan_in_normal, token_nll_sum


def runs(pattern) -> list[tuple[str, int]]:
    """The block pattern as (kind, count) runs: the program's segments."""
    out: list[tuple[str, int]] = []
    for kind in pattern:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return out


def layout(m: dict) -> dict:
    d, h, r, V = m["d_model"], m["n_heads"], m["mla_kv_lora_rank"], m["vocab_size"]
    nope, rope, vd = m["mla_qk_nope_dim"], m["mla_qk_rope_dim"], m["mla_v_dim"]
    held, router, fe = m["moe_experts"], m["moe_router_experts"], m["moe_d_ff"]
    sf = m["moe_shared_experts"] * fe

    def segment(kind: str, L: int) -> dict:
        seg = {
            "ln1": {"w": Init((L, d), "zeros")},
            "attn": {"kv_norm": {"w": Init((L, r), "zeros")},
                     "w_dkv": fan_in_normal((L, d, r), d),
                     "w_kpe": fan_in_normal((L, d, rope), d),
                     "w_uk": fan_in_normal((L, r, h, nope), r),
                     "w_uv": fan_in_normal((L, r, h, vd), r),
                     "wo": fan_in_normal((L, h, vd, d), h * vd),
                     "wq": fan_in_normal((L, d, h, nope + rope), d)},
            "ln2": {"w": Init((L, d), "zeros")},
        }
        if kind == "mla_dense":
            f = m["d_ff"]
            seg["mlp"] = {"wi": fan_in_normal((L, d, f), d), "wg": fan_in_normal((L, d, f), d),
                          "wo": fan_in_normal((L, f, d), f)}
        else:
            seg["moe"] = {"router": fan_in_normal((L, d, router), d),
                          "wi": fan_in_normal((L, held, d, fe), d),
                          "wg": fan_in_normal((L, held, d, fe), d),
                          "wo": fan_in_normal((L, held, fe, d), fe),
                          "shared": {"wi": fan_in_normal((L, d, sf), d),
                                     "wg": fan_in_normal((L, d, sf), d),
                                     "wo": fan_in_normal((L, sf, d), sf)}}
        return seg

    return {
        "embed": Init((V, d), "normal", 0.02),
        "segments": [segment(kind, n) for kind, n in runs(m["block_pattern"])],
        "final_norm": {"w": Init((d,), "zeros")},
        "lm_head": fan_in_normal((d, V), d),
    }


def _rmsnorm(x, w, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(m: dict):
    """YaRN's inverse frequencies of the rope dims (DeepSeek-V2's
    ``yarn_find_correction_range`` and linear ramp)."""
    dim, base = m["mla_qk_rope_dim"], m["rope_theta"]
    f, orig = m["rope_yarn_factor"], m["rope_yarn_original_len"]

    def turns_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(turns_dim(m["rope_yarn_beta_fast"])), 0)
    hi = min(math.ceil(turns_dim(m["rope_yarn_beta_slow"])), dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    return extra / f * ramp + extra * (1.0 - ramp)


def softmax_scale(m: dict) -> float:
    s = 1.0 / math.sqrt(m["mla_qk_nope_dim"] + m["mla_qk_rope_dim"])
    return s * _mscale(m["rope_yarn_factor"], m["rope_yarn_mscale_all_dim"]) ** 2


def rope(x, positions, m: dict):
    """Rotate interleaved pairs of x [B,S,H,D] by position, YaRN frequencies
    (cos/sin unscaled: mscale ÷ mscale_all_dim = 0.707 ÷ 0.707)."""
    ang = positions[:, None].astype(jnp.float32) * yarn_inv_freq(m)[None, :]  # [S, D/2]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def attention(q, k, v, scale: float, q_block: int, mat: Mat = F32):
    """Causal softmax attention, q/k [B,S,H,Dqk], v [B,S,H,Dv]; queries in
    blocks of ``q_block`` rows over the keys they can see, each block
    rematerialised in the backward pass."""
    s = q.shape[1]
    outs = []
    for start in range(0, s, q_block):
        stop = min(start + q_block, s)

        @jax.checkpoint
        def block(qb, kb, vb, start=start, stop=stop):
            sc = mat("bqhd,bkhd->bhqk", qb, kb) * scale
            ok = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
            p = jax.nn.softmax(jnp.where(ok, sc, NEG), axis=-1)
            return mat("bhqk,bkhd->bqhd", p, vb)

        outs.append(block(q[:, start:stop], k[:, :stop], v[:, :stop]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def mla(a, x, pos, m: dict, mat: Mat, q_block: int):
    nope, rd = m["mla_qk_nope_dim"], m["mla_qk_rope_dim"]
    q = mat("bsd,dhk->bshk", x, a["wq"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, m)], -1)
    c = _rmsnorm(mat("bsd,dr->bsr", x, a["w_dkv"]), a["kv_norm"]["w"])
    k_pe = rope(mat("bsd,dk->bsk", x, a["w_kpe"])[:, :, None, :], pos, m)
    k_nope = mat("bsr,rhk->bshk", c, a["w_uk"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3], rd))], -1)
    v = mat("bsr,rhk->bshk", c, a["w_uv"])
    o = attention(q, k, v, softmax_scale(m), q_block, mat)
    return mat("bshk,hkd->bsd", o, a["wo"])


def swiglu(p, x, mat: Mat):
    u = jax.nn.silu(mat("bsd,df->bsf", x, p["wg"])) * mat("bsd,df->bsf", x, p["wi"])
    return mat("bsf,fd->bsd", u, p["wo"])


def moe(p, x, m: dict, mat: Mat):
    """(the held experts' part + the shared experts, the rows' balance losses [B])."""
    k, held = m["moe_top_k"], m["moe_experts"]
    s = x.shape[1]
    probs = jax.nn.softmax(mat("bsd,de->bse", x, p["router"]), axis=-1)
    e = probs.shape[-1]
    top, idx = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [B,S,k,E]
    gate = jnp.einsum("bsk,bske->bse", top, chosen)[..., :held]
    u = (jax.nn.silu(mat("bsd,edf->bsef", x, p["wg"]))
         * mat("bsd,edf->bsef", x, p["wi"]))
    y = mat("bsef,efd->bsd", u * gate[..., None], p["wo"]) + swiglu(p["shared"], x, mat)
    f = jnp.sum(chosen, axis=(1, 2)) * (e / (k * s))
    return y, jnp.sum(f * jnp.mean(probs, axis=1), axis=-1)


def hidden(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    """Final-norm hidden states [B,S,d] and the summed balance losses [B]."""
    b, s = tokens.shape
    pos = jnp.arange(s)
    x = params["embed"][tokens]
    aux = jnp.zeros((b,), jnp.float32)
    for (kind, _), seg in zip(runs(m["block_pattern"]), params["segments"]):

        @jax.checkpoint
        def layer(carry, p, kind=kind):
            x, aux = carry
            x = x + mla(p["attn"], _rmsnorm(x, p["ln1"]["w"]), pos, m, mat, q_block)
            h = _rmsnorm(x, p["ln2"]["w"])
            if kind == "mla_dense":
                return (x + swiglu(p["mlp"], h, mat), aux), None
            y, a = moe(p["moe"], h, m, mat)
            return (x + y, aux + a), None

        (x, aux), _ = jax.lax.scan(layer, (x, aux), seg)
    return _rmsnorm(x, params["final_norm"]["w"]), aux


def logits(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    return mat("bsd,dv->bsv", hidden(params, tokens, m, mat, q_block)[0], params["lm_head"])


def loss_sum(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    """Summed next-token NLL over the rows of ``tokens`` plus α·(S−1)·Σ_rows
    of each row's balance losses: divided by B·(S−1), the program's loss."""
    x, aux = hidden(params, tokens, m, mat, q_block)
    nll = token_nll_sum(mat("bsd,dv->bsv", x, params["lm_head"])[:, :-1], tokens[:, 1:])
    return nll + m["aux_loss_weight"] * (tokens.shape[1] - 1) * jnp.sum(aux)
