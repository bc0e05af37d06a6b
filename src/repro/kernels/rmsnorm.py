"""Fused RMSNorm Pallas kernel: one HBM pass for stats + scale.

Grid over row blocks; each block holds (block_rows, d) in VMEM, computes
fp32 row statistics and writes the normalized, (1+w)-scaled rows — the
unfused jnp version reads x twice (stats, then scale) and materializes the
fp32 intermediate in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, w: jax.Array, eps: float = 1e-6,
                   block_rows: int = 128, *, interpret: bool) -> jax.Array:
    """x: [..., d]; w: [d] (stored as residual scale, applied as 1+w)."""
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((rows + pad) // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(shape)
