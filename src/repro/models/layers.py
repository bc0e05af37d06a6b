"""Shared neural-net layers (pure JAX, pytree params)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(rng: Array, shape: tuple[int, ...], scale: float = 1.0,
               dtype=jnp.float32) -> Array:
    """Truncated-normal fan-in init (LeCun-style)."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = scale / math.sqrt(fan_in)
    return (jax.random.truncated_normal(rng, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(rng: Array, shape: tuple[int, int], dtype=jnp.float32) -> Array:
    return (jax.random.normal(rng, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    """RMSNorm, fp32 statistics regardless of activation dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layernorm(x: Array, weight: Array, bias: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def init_norm(cfg_norm: str, d: int) -> dict:
    if cfg_norm == "rmsnorm":
        return {"w": jnp.zeros((d,), jnp.float32)}  # stored as (1 + w)
    return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def apply_norm(cfg_norm: str, p: dict, x: Array) -> Array:
    if cfg_norm == "rmsnorm":
        return rmsnorm(x, p["w"])
    return layernorm(x, p["w"], p["b"])


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0,
                     rotary_dim: Optional[int] = None) -> Array:
    """Inverse frequencies for RoPE over the first ``rotary_dim`` dims."""
    rd = rotary_dim or head_dim
    return 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))


def yarn_frequencies(dim: int, theta: float, factor: float, original_len: int,
                     beta_fast: float, beta_slow: float) -> Array:
    """YaRN's inverse frequencies over ``dim`` rotary dims (DeepSeek-V2's
    ``rope_scaling``, arXiv:2309.00071): pair ``i`` keeps its RoPE frequency
    below the dim at which a frequency turns ``beta_fast`` times over
    ``original_len`` positions, takes it ÷ ``factor`` above the dim of
    ``beta_slow`` turns, and a linear blend of the two between."""
    def correction_dim(turns: float) -> float:
        return dim * math.log(original_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(correction_dim(beta_fast)), 0)
    hi = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if hi == lo:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    inv = rope_frequencies(dim, theta)
    return inv / factor * ramp + inv * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature 0.1 · mscale · ln(factor) + 1 (1 without scaling)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(x: Array, positions: Array, theta: float = 10000.0,
               rotary_dim: Optional[int] = None, inv_freq: Optional[Array] = None) -> Array:
    """Rotate ``x`` [..., S, H, D] by position. ``positions``: [..., S].

    Supports partial rotary (GLM-style): only the first ``rotary_dim`` dims
    are rotated, the remainder passes through.  ``inv_freq`` replaces
    RoPE's frequencies (YaRN's).
    """
    d = x.shape[-1]
    rd = rotary_dim or d
    inv = rope_frequencies(d, theta, rd) if inv_freq is None else inv_freq  # [rd/2]
    ang = positions[..., :, None].astype(jnp.float32) * inv  # [..., S, rd/2]
    cos = jnp.cos(ang)[..., None, :]  # [..., S, 1, rd/2]
    sin = jnp.sin(ang)[..., None, :]
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    rot = rot.reshape(*x.shape[:-1], rd).astype(x.dtype)
    if rd == d:
        return rot
    return jnp.concatenate([rot, x[..., rd:]], axis=-1)


def sinusoidal_positions(seq_len: int, d: int, offset=0) -> Array:
    """Whisper-style sinusoidal absolute embeddings, computed functionally.

    ``offset`` may be a traced scalar (decode position).
    """
    pos = (jnp.arange(seq_len, dtype=jnp.float32) + offset)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (dim / d))
    out = jnp.zeros((seq_len, d), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang))
    out = out.at[:, 1::2].set(jnp.cos(ang))
    return out


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(rng: Array, d: int, d_ff: int, style: str, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    if style in ("swiglu", "geglu"):
        return {
            "wi": dense_init(k1, (d, d_ff), dtype=dtype),
            "wg": dense_init(k2, (d, d_ff), dtype=dtype),
            "wo": dense_init(k3, (d_ff, d), dtype=dtype),
        }
    return {  # plain 2-matrix MLP (whisper: GELU)
        "wi": dense_init(k1, (d, d_ff), dtype=dtype),
        "wo": dense_init(k2, (d_ff, d), dtype=dtype),
    }


def apply_mlp(p: dict, x: Array, style: str) -> Array:
    if style == "swiglu":
        h = jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (x @ p["wi"].astype(x.dtype))
    elif style == "geglu":
        h = jax.nn.gelu(x @ p["wg"].astype(x.dtype), approximate=True) * (x @ p["wi"].astype(x.dtype))
    else:
        h = jax.nn.gelu(x @ p["wi"].astype(x.dtype), approximate=True)
    return h @ p["wo"].astype(x.dtype)


def mlp_flops(d: int, d_ff: int, style: str) -> int:
    """Per-token forward FLOPs (used by analytic roofline)."""
    mats = 3 if style in ("swiglu", "geglu") else 2
    return 2 * mats * d * d_ff


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def cross_entropy(logits: Array, targets: Array, mask: Optional[Array] = None) -> Array:
    """Mean token cross-entropy; logits promoted to fp32."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
