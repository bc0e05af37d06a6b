"""Jit'd public wrappers for the Pallas kernels.

This is the one place that chooses ``interpret``: compiled Mosaic kernels
on TPU, the Pallas interpreter on any other backend.  The kernel modules
take ``interpret`` as a required argument, so no caller gets the
interpreter on the chip by default.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import grad_compress as gc
from repro.kernels import rmsnorm as rn


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None) -> jax.Array:
    """[B,S,H,D] layout wrapper (matches ``repro.models.attention``).

    k/v may have fewer (KV) heads — the GQA broadcast happens inside the
    kernel's BlockSpec index map, never materialized.
    """
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kv, skv, d)
    out = fa.flash_attention_bhsd(qr, kr, vr, causal=causal, window=window,
                                  interpret=_interpret())
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@jax.jit
def fused_rmsnorm(x: jax.Array, w: jax.Array) -> jax.Array:
    return rn.rmsnorm_pallas(x, w, interpret=_interpret())


@jax.jit
def quantize_int8(x: jax.Array):
    return gc.quantize_int8_pallas(x, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n",))
def dequantize_int8(q: jax.Array, scales: jax.Array, n: int):
    return gc.dequantize_int8_pallas(q, scales, n, interpret=_interpret())
