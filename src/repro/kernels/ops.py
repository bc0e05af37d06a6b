"""Jit'd public wrappers for the Pallas kernels.

This is the one place that chooses ``interpret``: compiled Mosaic kernels
on TPU, the Pallas interpreter on any other backend.  The kernel modules
take ``interpret`` as a required argument, so no caller gets the
interpreter on the chip by default.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as fa
from repro.kernels import grad_compress as gc
from repro.kernels import rmsnorm as rn


def on_tpu() -> bool:
    """Whether the program is traced for a TPU.  There the kernels compile
    with Mosaic, and the models' full-sequence attention takes
    ``flash_attention``; on any other backend the models keep their jnp
    paths (the reference) and the kernels run in the Pallas interpreter."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Flash attention with its backward, in the models' [B,S,H,D] layout.

    q [B,Sq,H,Dk], k [B,Skv,KV,Dk], v [B,Skv,KV,Dv] → [B,Sq,H,Dv].  k/v may
    have fewer (KV) heads: the GQA broadcast happens in the kernel's index
    map, never materialized.  ``scale`` defaults to 1/√Dk.  Causal masks
    take positions 0..S−1 on both sides (Sq == Skv); ``window`` keeps keys
    with q − k < window.
    """
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    kernel = functools.partial(_flash_attention, causal=causal, window=window,
                               scale=float(scale), interpret=_interpret())
    mesh = jax.sharding.get_abstract_mesh()
    auto = tuple(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if not auto or mesh.size == 1:
        return kernel(q, k, v)
    # Mosaic kernels are not partitioned automatically: map the kernel over
    # the mesh axes still partitioned automatically, each device with its
    # batch rows ("model" excepted) and heads ("model").
    batch = tuple(a for a in auto if a != "model")
    rows = batch if q.shape[0] % math.prod(mesh.shape[a] for a in batch) == 0 else None
    heads = None
    if "model" in auto and not (q.shape[2] % mesh.shape["model"]
                                or k.shape[2] % mesh.shape["model"]):
        heads = "model"
    spec = P(rows or None, None, heads, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                         axis_names=set(auto), check_vma=False)(q, k, v)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "scale", "interpret"))
def _flash_attention(q, k, v, *, causal, window, scale, interpret):
    out = fa.flash_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window, scale=scale,
        interpret=interpret)
    return out.transpose(0, 2, 1, 3)


@jax.jit
def fused_rmsnorm(x: jax.Array, w: jax.Array) -> jax.Array:
    return rn.rmsnorm_pallas(x, w, interpret=_interpret())


@jax.jit
def quantize_int8(x: jax.Array):
    return gc.quantize_int8_pallas(x, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n",))
def dequantize_int8(q: jax.Array, scales: jax.Array, n: int):
    return gc.dequantize_int8_pallas(q, scales, n, interpret=_interpret())
