"""int8 gradient quantization Pallas kernels (compressed collectives).

Per-256-block symmetric quantization: one VMEM pass computes |max|, scale,
and the rounded int8 payload — the jnp reference makes three HBM passes
(abs-max, divide, round/clip).  Used by the compressed LUMORPH collectives
(``repro.optim.grad_comm``) to cut the β-term ~4× vs fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QUANT_BLOCK = 256
#: rows per grid step; the 1-D per-row scales are tiled T(1024) by XLA on
#: TPU, and Mosaic refuses a scale block that does not match that tile
SCALE_BLOCK = 1024


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # [rows, QUANT_BLOCK]
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...][:, None]


def quantize_int8_pallas(x: jax.Array, *,
                         interpret: bool) -> tuple[jax.Array, jax.Array]:
    """flat fp32 x → (int8 payload, per-block fp32 scales)."""
    n = x.shape[0]
    pad = (-n) % QUANT_BLOCK
    x2 = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(-1, QUANT_BLOCK)
    rows = x2.shape[0]
    br = min(SCALE_BLOCK, rows)
    rpad = (-rows) % br
    if rpad:
        x2 = jnp.pad(x2, ((0, rpad), (0, 0)))
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=((rows + rpad) // br,),
        in_specs=[pl.BlockSpec((br, QUANT_BLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, QUANT_BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((br,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((rows + rpad, QUANT_BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((rows + rpad,), jnp.float32)],
        interpret=interpret,
    )(x2)
    return q[:rows].reshape(-1)[: n + pad][:n + pad], s[:rows]


def dequantize_int8_pallas(q: jax.Array, scales: jax.Array, n: int, *,
                           interpret: bool) -> jax.Array:
    q2 = q.reshape(-1, QUANT_BLOCK)
    rows = q2.shape[0]
    br = min(SCALE_BLOCK, rows)
    rpad = (-rows) % br
    if rpad:
        q2 = jnp.pad(q2, ((0, rpad), (0, 0)))
        scales = jnp.pad(scales, (0, rpad))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=((rows + rpad) // br,),
        in_specs=[pl.BlockSpec((br, QUANT_BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((br,), lambda i: (i,))],
        out_specs=pl.BlockSpec((br, QUANT_BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + rpad, QUANT_BLOCK), jnp.float32),
        interpret=interpret,
    )(q2, scales)
    return out[:rows].reshape(-1)[:n]
