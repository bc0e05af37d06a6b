"""Flash attention on TPU: the splash kernel shipped with JAX, with its backward.

One fused kernel serves training and prefill (causal, sliding-window and
bidirectional/cross attention, GQA, a value width other than the key
width as MLA has):

  * online softmax over KV blocks held in VMEM; bf16 operands into the
    MXU with f32 accumulation; the per-row log-sum-exp is saved for the
    backward, so no [Sq, Skv] array is ever written to HBM;
  * the mask is known when the program is traced, so blocks that are
    entirely dead under the causal or window mask are skipped, forward
    and backward (splash's block-sparse ``MaskInfo``);
  * the backward is a ``jax.custom_vjp`` over splash's dq and dkv kernels;
  * GQA is native: q heads index their KV head in the kernel's index map.

Lengths that do not tile into the block are padded: padded keys sit after
every real query (causal) or carry another segment id (bidirectional), so
no real row sees them.

Validated against ``ref.reference_attention`` in interpret mode on CPU,
forward and gradients (``tests/test_kernels.py``), and on the chip
(``chip_smoke.py``); ``tests/test_tpu_compile.py`` compiles it for a
described v5e, alone and inside the train step.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

#: largest q and kv block; the lane width (128) is the smallest
BLOCK = 512
LANES = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block(s: int) -> int:
    return min(BLOCK, _round_up(s, LANES))


def _kernel(heads: int, sq: int, skv: int, causal: bool, window: Optional[int],
            bq: int, bkv: int, interpret: bool):
    """The splash kernel for one padded shape and mask.  Its block map is
    numpy work at trace time, which splash caches by mask."""
    if not causal:
        mask = splash.FullMask((sq, skv))
    elif window is None:
        mask = splash.CausalMask((sq, skv))
    else:  # q − k < window, k ≤ q
        mask = splash.LocalMask((sq, skv), window_size=(window - 1, 0), offset=0)
    sizes = splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=bq, block_kv_dq=bkv)
    return splash.make_splash_mha(
        splash.MultiHeadMask([mask] * heads), block_sizes=sizes,
        head_shards=1, q_seq_shards=1, interpret=interpret)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool, window: Optional[int], scale: float,
                         interpret: bool) -> jax.Array:
    """q [B, H, Sq, Dk], k [B, KV, Skv, Dk], v [B, KV, Skv, Dv], H a multiple
    of KV; causal needs Sq == Skv (positions 0..S−1 on both sides).
    → [B, H, Sq, Dv] in q's dtype."""
    b, h, sq, _ = q.shape
    skv = k.shape[2]
    if causal and sq != skv:
        raise ValueError(f"causal flash attention needs Sq == Skv, got {sq}, {skv}")
    if window is not None and not causal:
        raise ValueError("a window needs a causal mask")
    if window is not None and window >= sq:
        window = None  # a window as long as the sequence is plain causal
    bq, bkv = _block(sq), _block(skv)
    sq_p, skv_p = _round_up(sq, bq), _round_up(skv, bkv)
    q = jnp.pad(q * jnp.asarray(scale, q.dtype),
                ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    kernel = _kernel(h, sq_p, skv_p, causal, window, bq, bkv, interpret)
    if causal or skv_p == skv:
        out = jax.vmap(kernel)(q, k, v)
    else:  # keys past Skv carry segment 1, which no query has
        seg = splash.SegmentIds(
            q=jnp.zeros((sq_p,), jnp.int32),
            kv=(jnp.arange(skv_p) >= skv).astype(jnp.int32))
        out = jax.vmap(lambda q, k, v: kernel(q, k, v, segment_ids=seg))(q, k, v)
    return out[:, :, :sq]
