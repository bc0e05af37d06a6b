"""Gradient communication: bucketing → LUMORPH collective dispatch →
optional int8 compression with error feedback.

This is where the paper's contribution is a *first-class training feature*:

  * gradients are flattened and packed into size-targeted **buckets**
    (small buffers are exactly the α-dominated regime where the paper's
    log-round algorithms beat Ring — Fig 4a's mechanism);
  * each bucket is ALLREDUCEd by ``ring`` / ``lumorph2`` / ``lumorph4`` /
    ``auto`` — ``auto`` consults the α–β cost model **per bucket** and picks
    the cheapest schedule (beyond-paper: the paper fixes one algorithm per
    job);
  * optional **int8 compression** quantizes every shipped chunk with
    per-block scales and dequant-accumulates at the receiver, cutting the
    β-term 4× vs fp32 (beyond-paper; complements the paper's α-cutting).
    Compression is a per-hop payload transform over the *same* Schedule
    IR the uncompressed collectives compile from — not a separate loop.
    Callers maintain an error-feedback buffer so quantization error is
    re-injected the next step instead of lost.

All functions here run **inside** ``jax.shard_map`` bodies (manual dp axes,
auto model axis) — see ``repro.launch.train``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import collectives
from repro.core.cost_model import LUMORPH_LINK, LinkModel, select_algorithm

PyTree = Any
Array = jax.Array

DEFAULT_BUCKET_BYTES = 25 * 1024 * 1024  # 25 MB, torch-DDP-style default


@dataclasses.dataclass(frozen=True)
class Bucket:
    start: int  # element offsets into the flat gradient vector
    end: int

    @property
    def n_elems(self) -> int:
        return self.end - self.start


def make_buckets(total_elems: int,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 bytes_per_elem: int = 4) -> list[Bucket]:
    """DDP-style flat bucketing: the whole gradient is one flat fp32 vector
    cut into ~bucket_bytes ranges (tensor boundaries ignored — stacked
    layer params would otherwise form multi-hundred-MB β-bound buckets).
    Buckets fill in leaf order ≈ backward-pass order, enabling overlap."""
    target = max(1, bucket_bytes // bytes_per_elem)
    out = []
    off = 0
    while off < total_elems:
        end = min(off + target, total_elems)
        out.append(Bucket(off, end))
        off = end
    return out


# ---------------------------------------------------------------------------
# int8 compression
# ---------------------------------------------------------------------------

QUANT_BLOCK = 256


def quantize_int8(x: Array) -> tuple[Array, Array]:
    """Per-block symmetric int8 quantization. x: flat fp32 → (q, scales)."""
    n = x.shape[0]
    pad = (-n) % QUANT_BLOCK
    xf = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(-1, QUANT_BLOCK)
    amax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0].astype(jnp.float32)


def dequantize_int8(q: Array, scales: Array, n: int) -> Array:
    xf = q.astype(jnp.float32).reshape(-1, QUANT_BLOCK) * scales[:, None]
    return xf.reshape(-1)[:n]


def _int8_encode(piece: Array) -> tuple[Array, Array]:
    """Per-hop payload transform: quantize the shipped chunks to int8 with
    per-block fp32 scales (1/64 byte overhead)."""
    return quantize_int8(piece.reshape(-1))


def _int8_decode(payload: tuple[Array, Array], like: Array) -> Array:
    q, sc = payload
    return dequantize_int8(q, sc, like.size).reshape(like.shape)


def compressed_all_reduce(x: Array, axis_name: str,
                          n_chunks: int = 1) -> Array:
    """LUMORPH-2 recursive halving/doubling with int8 payloads.

    The *same* Schedule IR as the uncompressed collective, compiled with
    an int8 encode/decode pair wrapped around every hop: shipped chunks
    are quantized (per-block scales ride along as fp32), the receiver
    dequant-accumulates in fp32.  Wire bytes ≈ n (int8) + n/64 (scales)
    vs 4n fp32: ~3.8× β reduction.

    ``n_chunks > 1`` runs the chunked/pipelined lowering instead
    (:func:`repro.core.collectives.overlapped_all_reduce`): the int8
    transform composes per-chunk — every wave's hops quantize their own
    1/C slice with the same per-block scales machinery, so compression and
    overlap stack rather than exclude each other.
    """
    p = jax.lax.axis_size(axis_name)
    if p == 1:
        return x
    if p & (p - 1):
        raise ValueError("compressed allreduce requires a power-of-two axis")
    if n_chunks > 1:
        return collectives.overlapped_all_reduce(
            x.astype(jnp.float32), axis_name, "lumorph2", n_chunks=n_chunks,
            encode=_int8_encode, decode=_int8_decode).astype(x.dtype)
    fn = collectives.compile_schedule(
        collectives.schedule_for_execution("lumorph2", p), axis_name,
        encode=_int8_encode, decode=_int8_decode)
    return fn(x.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# bucketed gradient all-reduce (inside shard_map)
# ---------------------------------------------------------------------------

def all_reduce_grads(grads: PyTree, axis_names: tuple[str, ...],
                     algo: str = "auto",
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                     link: LinkModel = LUMORPH_LINK,
                     compress: bool = False,
                     error_feedback: Optional[PyTree] = None,
                     mean: bool = True,
                     wire_dtype=jnp.bfloat16,
                     overlap_chunks: int = 1) -> tuple[PyTree, Optional[PyTree], list[tuple[int, str]]]:
    """ALLREDUCE ``grads`` over the (manual) data axes with LUMORPH
    collectives, bucket by bucket.

    ``overlap_chunks > 1`` lowers every bucket through the chunked wave
    pipeline (``overlapped_all_reduce``): each bucket's payload is split
    into that many slices whose collectives the XLA scheduler can overlap
    with neighbouring compute — the PCCL-style execution mode behind
    ``--overlap`` in ``repro.launch.train``.  Numerics are unchanged
    (differentially tested in ``tests/test_overlap.py``); ``1`` keeps the
    bit-exact monolithic path.

    Returns (reduced_grads, new_error_feedback, bucket_log) where
    bucket_log records (bytes, algo) per bucket for EXPERIMENTS.md.

    Multiple dp axes (pod, data) are **flattened into one product axis**
    (ppermute partner maps over the combined index) — a composed per-axis
    hierarchy ships ~2× the bytes (each level re-reduces the full buffer;
    measured in EXPERIMENTS.md §Perf c3).  Payloads travel as ``wire_dtype``
    (bf16 by default — gradients are bf16-born in mixed-precision training;
    accumulation happens in fp32 after each hop via the algorithms' adds).
    """
    leaves, treedef = jax.tree.flatten(grads)
    ef_new_leaves: Optional[list[Array]] = None
    if compress and error_feedback is not None:
        # EF-SGD (Karimireddy et al.): compensate with last step's residual,
        # store the *local* quantization residual for the next step.  The
        # per-hop requantization inside the collective adds further (small,
        # uncompensated) error — see DESIGN.md §8.
        ef_leaves = jax.tree.leaves(error_feedback)
        comp = [g.astype(jnp.float32) + e for g, e in zip(leaves, ef_leaves)]
        ef_new_leaves = []
        for c in comp:
            q, sc = quantize_int8(c.reshape(-1))
            deq = dequantize_int8(q, sc, c.size).reshape(c.shape)
            ef_new_leaves.append(c - deq)
        leaves = comp
    shapes = [l.shape for l in leaves]
    sizes = [l.size for l in leaves]
    comm_dtype = jnp.float32 if compress else wire_dtype
    flat = jnp.concatenate([l.astype(comm_dtype).reshape(-1) for l in leaves])
    buckets = make_buckets(flat.size, bucket_bytes)

    axis = axis_names if len(axis_names) > 1 else axis_names[0]
    p_total = jax.lax.axis_size(axis)

    log: list[tuple[int, str]] = []
    reduced_parts = []
    for b in buckets:
        piece = flat[b.start:b.end]
        n_bytes = piece.size * jnp.dtype(comm_dtype).itemsize
        chosen = algo
        if algo == "auto":
            chosen = select_algorithm(n_bytes, p_total, link)
        log.append((n_bytes, chosen + ("+int8" if compress else "")
                    + (f"+ovl{overlap_chunks}" if overlap_chunks > 1 else "")))
        if compress:
            piece = compressed_all_reduce(piece, axis, n_chunks=overlap_chunks)
        elif overlap_chunks > 1:
            piece = collectives.overlapped_all_reduce(
                piece, axis, chosen, n_chunks=overlap_chunks)
        else:
            piece = collectives.all_reduce(piece, axis, chosen)
        reduced_parts.append(piece)
    reduced = jnp.concatenate(reduced_parts) if len(reduced_parts) > 1 else reduced_parts[0]
    reduced = reduced.astype(jnp.float32)
    if mean:
        reduced = reduced / p_total
    out_leaves = []
    off = 0
    orig = jax.tree.leaves(grads)
    for shp, n, g in zip(shapes, sizes, orig):
        out_leaves.append(reduced[off:off + n].reshape(shp).astype(g.dtype))
        off += n
    new_ef = (jax.tree.unflatten(treedef, ef_new_leaves)
              if ef_new_leaves is not None else None)
    return jax.tree.unflatten(treedef, out_leaves), new_ef, log
