"""Flash attention Pallas TPU kernel (causal / bidirectional, GQA, SWA).

TPU-native design (not a CUDA port):
  * grid = (batch·q_heads, q_blocks, kv_blocks) with the KV dimension
    innermost ("arbitrary" semantics) so the fp32 accumulator, running max
    and denominator live in **VMEM scratch** across KV iterations;
  * Q/K/V blocks are staged HBM→VMEM by ``BlockSpec`` index maps; the GQA
    kv-head broadcast happens in the *index map* (q-head ÷ group size), so
    grouped KV is never materialized per-head;
  * block shapes default to (128, head_dim) — MXU-aligned (≥ 128 lanes);
  * causal + sliding-window masking via block-position iota; fully-masked
    blocks still iterate but skip the matmul through ``@pl.when``.

Validated against ``ref.reference_attention`` in interpret mode on CPU
(``tests/test_kernels.py``) and on the chip (``chip_smoke.py``);
``tests/test_tpu_compile.py`` compiles it for a described v5e.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: Optional[int],
                 bq: int, bk: int, seq_q: int, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_kv  # padding
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window

    # block-level early out: skip matmuls when the whole block is masked
    block_live = jnp.bool_(True)
    if causal:
        block_live &= (ki * bk) <= (qi * bq + bq - 1)
    if window is not None:
        block_live &= ((qi * bq) - (ki * bk + bk - 1)) < window

    @pl.when(block_live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq,bk]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         scale: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool) -> jax.Array:
    """q: [BH, Sq, D]; k/v: [BKV, Skv, D] with BH = BKV·n_rep.  → [BH, Sq, D].

    BH-major layout: head index varies fastest within a batch entry so the
    GQA index map is ``bh // n_rep`` after batch alignment.
    """
    bh, sq, d = q.shape
    bkv, skv, _ = k.shape
    assert bh % bkv == 0, (bh, bkv)
    n_rep = bh // bkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    sq_pad = (-sq) % bq
    skv_pad = (-skv) % bk
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0)))
    if skv_pad:
        k = jnp.pad(k, ((0, 0), (0, skv_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad), (0, 0)))
    grid = (bh, (sq + sq_pad) // bq, (skv + skv_pad) // bk)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, seq_q=sq, seq_kv=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, n_rep=n_rep: (b // n_rep, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, n_rep=n_rep: (b // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + sq_pad, d), q.dtype),
        scratch_shapes=[
            _vmem((bq, d), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]


def _vmem(shape, dtype):
    """Explicit VMEM scratch spec (also honored by the interpreter)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
