"""Attention: GQA / MQA / sliding-window / MLA / cross, flash + dense + chunked paths.

Three execution paths, all numerically interchangeable:

  * flash    — on TPU, full-sequence causal (with or without a window) and
               bidirectional attention, training and prefill, goes through
               the fused kernel in ``repro.kernels`` (forward and backward;
               no [Sq, Skv] array in HBM, dead causal blocks skipped);
  * dense    — materializes [Sq, Skv] scores; used off TPU for short
               sequences, for prefix masks and decode, and as the reference;
  * chunked  — online-softmax over KV chunks (lax.scan), bounding the score
               working set to [Sq, chunk]; off TPU above ``dense_attn_limit``.

Decode maintains a KV cache; sliding-window archs (h2o-danube) use a ring
buffer of ``window`` slots so a 500k-token stream needs O(window) memory.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.layers import (apply_rope, dense_init, init_norm, rmsnorm,
                                 yarn_frequencies, yarn_mscale)

Array = jax.Array

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def build_mask(q_pos: Array, kv_pos: Array, kind: str = "causal",
               window: Optional[int] = None, prefix_len: int = 0) -> Array:
    """Boolean [.., Sq, Skv] mask; True = attend.

    kinds: "causal" | "bidirectional" | "prefix" (bidirectional over tokens
    with position < prefix_len, causal after — PaliGemma-style prefix-LM).
    ``window``: additionally restrict to kv within ``window`` positions.
    """
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    valid = k >= 0  # ring-buffer slots that were never written carry pos=-1
    if kind == "bidirectional":
        m = valid
    elif kind == "prefix":
        causal = k <= q
        in_prefix = k < prefix_len
        m = (causal | in_prefix) & valid
    else:  # causal
        m = (k <= q) & valid
    if window is not None:
        m = m & (q - k < window)
    return m


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def repeat_kv(x: Array, n_rep: int) -> Array:
    """[B,S,KV,D] → [B,S,KV*n_rep,D] by broadcasting each kv head."""
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(b, s, kv * n_rep, d)


def dense_attention(q: Array, k: Array, v: Array, mask: Array,
                    scale: Optional[float] = None) -> Array:
    """q [B,Sq,H,Dk], k [B,Skv,KV,Dk], v [B,Skv,KV,Dv], mask [B?,Sq,Skv].

    GQA-native: when H > KV the query heads are grouped as [KV, H/KV] and
    contracted against the KV heads directly — the broadcast K/V copies a
    `repeat_kv` would materialize (η× KV bytes) never exist.
    """
    b, sq, h, dk = q.shape
    kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    if mask.ndim == 2:
        mask = mask[None]
    if h == kv:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        s = jnp.where(mask[:, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)
    n_rep = h // kv
    qg = q.reshape(b, sq, kv, n_rep, dk)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32) * scale
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, v)
    return out.reshape(b, sq, h, v.shape[-1])


def chunked_attention(q: Array, k: Array, v: Array, q_pos: Array, kv_pos: Array,
                      kind: str = "causal", window: Optional[int] = None,
                      prefix_len: int = 0, chunk: int = 1024,
                      scale: Optional[float] = None) -> Array:
    """Online-softmax attention over KV chunks; O(Sq·chunk) score memory.

    GQA-native like ``dense_attention``: k/v keep their KV heads, query
    heads are grouped — no broadcast materialization.
    """
    b, sq, h, dk = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    n_rep = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    if skv % chunk:
        pad = (-skv) % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        skv += pad
    n_chunks = skv // chunk
    kc = k.reshape(b, n_chunks, chunk, kvh, dk).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kvh, dv).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    qf = q.reshape(b, sq, kvh, n_rep, dk).astype(jnp.float32)

    def step(carry, xs):
        acc, m, l = carry
        kb, vb, pb = xs
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qf, kb.astype(jnp.float32)) * scale
        msk = build_mask(q_pos, pb, kind, window, prefix_len)  # [B,Sq,chunk]
        s = jnp.where(msk[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhrqk,bkhd->bhrqd", p, vb.astype(jnp.float32))
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, kvh, n_rep, sq, dv), jnp.float32)
    m0 = jnp.full((b, kvh, n_rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, n_rep, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # [B,KV,R,Sq,Dv] → [B,Sq,H,Dv]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# standard (GQA) attention block
# ---------------------------------------------------------------------------

def init_attention(rng: Array, d: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool = False, dtype=jnp.float32) -> dict:
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d, n_heads, head_dim), dtype=dtype),
        "wk": dense_init(ks[1], (d, n_kv, head_dim), dtype=dtype),
        "wv": dense_init(ks[2], (d, n_kv, head_dim), dtype=dtype),
        "wo": dense_init(ks[3], (n_heads, head_dim, d), dtype=dtype),
    }
    if qkv_bias:  # codeqwen/qwen1.5 carries qkv biases
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
    return p


def _project_qkv(p: dict, x: Array, xkv: Array, positions: Array,
                 kv_positions: Array, cfg) -> tuple[Array, Array, Array]:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.use_rope:
        rd = int(cfg.head_dim * cfg.partial_rotary_factor)
        q = apply_rope(q, positions, cfg.rope_theta, rd)
        k = apply_rope(k, kv_positions, cfg.rope_theta, rd)
    return q, k, v


def _flash_takes(mask_kind: str) -> bool:
    """On TPU the flash kernel takes causal self-attention (with or without
    a window) and bidirectional attention; prefix masks, and every other
    backend, keep the jnp paths."""
    return kops.on_tpu() and mask_kind in ("causal", "bidirectional")


def _flash(q: Array, k: Array, v: Array, mask_kind: str, window: Optional[int],
           scale: Optional[float] = None) -> Array:
    with jax.named_scope("flash"):
        return kops.flash_attention(q, k, v, causal=mask_kind == "causal",
                                    window=window, scale=scale)


def attention_forward(p: dict, x: Array, positions: Array, cfg,
                      mask_kind: str = "causal", prefix_len: int = 0,
                      xkv: Optional[Array] = None,
                      kv_positions: Optional[Array] = None) -> Array:
    """Full-sequence attention (train/prefill) over positions 0..S−1.
    ``xkv`` enables cross-attn."""
    xkv = x if xkv is None else xkv
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(p, x, xkv, positions, kv_positions, cfg)
    window = cfg.sliding_window if mask_kind == "causal" else None
    if _flash_takes(mask_kind):
        out = _flash(q, k, v, mask_kind, window)
    elif x.shape[1] * xkv.shape[1] > cfg.dense_attn_limit:
        out = chunked_attention(q, k, v, positions, kv_positions, mask_kind,
                                window, prefix_len, chunk=cfg.attn_chunk)
    else:
        mask = build_mask(positions, kv_positions, mask_kind, window, prefix_len)
        out = dense_attention(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# KV cache (decode) — bf16 or int8 (KIVI-style per-token-per-head scales)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    if dtype == jnp.int8 or dtype == "int8":
        return {
            "k": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
            "v": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
            # symmetric per-(token, head) scales — KIVI-style; halves the
            # per-token HBM stream vs bf16 (the decode memory term)
            "k_scale": jnp.zeros((batch, max_len, n_kv), jnp.float32),
            "v_scale": jnp.zeros((batch, max_len, n_kv), jnp.float32),
            "pos": jnp.full((batch, max_len), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def _quant_kv(x: Array) -> tuple[Array, Array]:
    """[B,S,KV,D] → int8 payload + per-(token, head) scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequant_kv(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_from_prefill(k: Array, v: Array, positions: Array, max_len: int) -> dict:
    """Build a cache holding prefill KV (positions 0..S−1), padded to max_len."""
    b, s, kv, hd = k.shape
    c = init_kv_cache(b, max_len, kv, hd, k.dtype)
    c["k"] = jax.lax.dynamic_update_slice(c["k"], k.astype(c["k"].dtype), (0, 0, 0, 0))
    c["v"] = jax.lax.dynamic_update_slice(c["v"], v.astype(c["v"].dtype), (0, 0, 0, 0))
    c["pos"] = jax.lax.dynamic_update_slice(c["pos"], positions.astype(jnp.int32), (0, 0))
    return c


def decode_attention(p: dict, x: Array, cache: dict, position: Array, cfg) -> tuple[Array, dict]:
    """One-token decode: update the (ring) cache, attend over it.

    ``x``: [B, 1, D]; ``position``: scalar int32 (current absolute position);
    ring semantics when ``cfg.sliding_window`` is set (slot = pos % window).
    """
    b = x.shape[0]
    max_len = cache["k"].shape[1]
    pos_b = jnp.broadcast_to(position[None], (b,))[:, None]  # [B,1]
    q, k, v = _project_qkv(p, x, x, pos_b, pos_b, cfg)
    slot = position % max_len  # ring buffer; max_len == window for SWA archs
    quantized = "k_scale" in cache
    if quantized:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq, (0, slot, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq, (0, slot, 0, 0)),
            "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], ks, (0, slot, 0)),
            "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], vs, (0, slot, 0)),
            "pos": jax.lax.dynamic_update_slice(cache["pos"], jnp.broadcast_to(position, (b, 1)).astype(jnp.int32), (0, slot)),
        }
        kk = _dequant_kv(cache["k"], cache["k_scale"], x.dtype)
        vv = _dequant_kv(cache["v"], cache["v_scale"], x.dtype)
    else:
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0)),
            "pos": jax.lax.dynamic_update_slice(cache["pos"], jnp.broadcast_to(position, (b, 1)).astype(jnp.int32), (0, slot)),
        }
        kk = cache["k"].astype(x.dtype)
        vv = cache["v"].astype(x.dtype)
    mask = build_mask(pos_b, cache["pos"], "causal", cfg.sliding_window)
    out = dense_attention(q, kk, vv, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(rng: Array, d: int, n_heads: int, kv_lora_rank: int,
             qk_nope_dim: int, qk_rope_dim: int, v_dim: int, dtype=jnp.float32) -> dict:
    ks = jax.random.split(rng, 6)
    return {
        # queries (lite model: no q-lora)
        "wq": dense_init(ks[0], (d, n_heads, qk_nope_dim + qk_rope_dim), dtype=dtype),
        # latent KV compression, RMS-normalised (``kv_a_layernorm``)
        "w_dkv": dense_init(ks[1], (d, kv_lora_rank), dtype=dtype),
        "kv_norm": init_norm("rmsnorm", kv_lora_rank),
        "w_kpe": dense_init(ks[2], (d, qk_rope_dim), dtype=dtype),  # shared across heads
        # decompression
        "w_uk": dense_init(ks[3], (kv_lora_rank, n_heads, qk_nope_dim), dtype=dtype),
        "w_uv": dense_init(ks[4], (kv_lora_rank, n_heads, v_dim), dtype=dtype),
        "wo": dense_init(ks[5], (n_heads, v_dim, d), dtype=dtype),
    }


def _mla_rope(x: Array, positions: Array, cfg) -> Array:
    """RoPE over MLA's rope dims, with YaRN's frequencies where the config
    sets a YaRN factor."""
    f = cfg.rope_yarn_factor
    if not f:
        return apply_rope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, inv_freq=yarn_frequencies(
        x.shape[-1], cfg.rope_theta, f, cfg.rope_yarn_original_len,
        cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow))


def mla_softmax_scale(cfg) -> float:
    """1/√(qk_nope + qk_rope), times YaRN's mscale(mscale_all_dim)² where set."""
    scale = 1.0 / math.sqrt(cfg.mla_qk_nope_dim + cfg.mla_qk_rope_dim)
    if cfg.rope_yarn_factor:
        scale *= yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim) ** 2
    return scale


def _mla_query(p: dict, x: Array, positions: Array, cfg) -> Array:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_pe = q[..., :cfg.mla_qk_nope_dim], q[..., cfg.mla_qk_nope_dim:]
    return jnp.concatenate([q_nope, _mla_rope(q_pe, positions, cfg)], axis=-1)


def _mla_compress(p: dict, x: Array, positions: Array, cfg) -> tuple[Array, Array]:
    """What a server caches per token: the normalised latent c_kv [B,S,rank]
    and the rotated key k_pe [B,S,rope] that all heads share."""
    dt = x.dtype
    with jax.named_scope("latent"):
        c_kv = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt)),
                       p["kv_norm"]["w"])
    k_pe = jnp.einsum("bsd,dk->bsk", x, p["w_kpe"].astype(dt))
    return c_kv, _mla_rope(k_pe[:, :, None, :], positions, cfg)[:, :, 0, :]


def _mla_expand(p: dict, c_kv: Array, k_pe: Array, cfg) -> tuple[Array, Array]:
    """Per-head keys [B,S,H,nope+rope] and values [B,S,H,v] from the latent."""
    dt = c_kv.dtype
    with jax.named_scope("latent"):
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(dt))
        v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"].astype(dt))
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (*k_nope.shape[:3], cfg.mla_qk_rope_dim))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def mla_forward(p: dict, x: Array, positions: Array, cfg) -> Array:
    """Full-sequence MLA over positions 0..S−1. The latent c_kv (rank 512)
    + shared k_pe (64) are what a production server caches — 576
    floats/token vs 2·H·D = 4096."""
    s = x.shape[1]
    qc = _mla_query(p, x, positions, cfg)
    k, v = _mla_expand(p, *_mla_compress(p, x, positions, cfg), cfg)
    scale = mla_softmax_scale(cfg)
    if _flash_takes("causal"):
        out = _flash(qc, k, v, "causal", None, scale)
    elif s * s > cfg.dense_attn_limit:
        out = chunked_attention(qc, k, v, positions, positions, "causal",
                                None, 0, chunk=cfg.attn_chunk, scale=scale)
    else:
        mask = build_mask(positions, positions, "causal")
        out = dense_attention(qc, k, v, mask, scale=scale)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def init_mla_cache(batch: int, max_len: int, kv_lora_rank: int, rope_dim: int,
                   dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, kv_lora_rank), dtype),
        "k_pe": jnp.zeros((batch, max_len, rope_dim), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def mla_decode(p: dict, x: Array, cache: dict, position: Array, cfg) -> tuple[Array, dict]:
    """One-token MLA decode against the latent cache, which holds the
    normalised c_kv and the rotated k_pe, as ``mla_forward`` computes them."""
    dt = x.dtype
    b = x.shape[0]
    pos_b = jnp.broadcast_to(position[None], (b,))[:, None]
    qc = _mla_query(p, x, pos_b, cfg)
    c_new, kpe_new = _mla_compress(p, x, pos_b, cfg)
    slot = position % cache["c_kv"].shape[1]
    cache = {
        "c_kv": jax.lax.dynamic_update_slice(cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, slot, 0)),
        "k_pe": jax.lax.dynamic_update_slice(cache["k_pe"], kpe_new.astype(cache["k_pe"].dtype), (0, slot, 0)),
        "pos": jax.lax.dynamic_update_slice(cache["pos"], jnp.broadcast_to(position, (b, 1)).astype(jnp.int32), (0, slot)),
    }
    k, v = _mla_expand(p, cache["c_kv"].astype(dt), cache["k_pe"].astype(dt), cfg)
    mask = build_mask(pos_b, cache["pos"], "causal")
    out = dense_attention(qc, k, v, mask, scale=mla_softmax_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), cache
