"""Executable LUMORPH collectives, compiled from the Schedule IR (paper §4).

There are **no hand-written per-algorithm round loops here**: every
algorithm (ring, LUMORPH-2, LUMORPH-4, tree) is a ``Schedule`` built by
``repro.core.scheduler`` and lowered by :func:`compile_schedule` into a
sequence of ``jax.lax.ppermute`` rounds — the TPU-native analogue of
programming MZI circuits.  A :class:`~repro.core.scheduler.Transfer`'s
``perm`` *is* the circuit configuration the LUMORPH scheduler would
install for that hop, so execution, pricing, and simulation all read the
same object.

All compiled programs run **inside** ``shard_map`` over a named mesh axis
and compute a mathematically exact ALLREDUCE (validated against
``lax.psum``).  Rounds are Python-level loops (log p or p−1 iterations)
so every round has static shapes.  The buffer stays one flat vector:
every rank's row of a transfer's chunk tables is one contiguous run of
equal length, so a hop reads and writes a single span, whose offset is
the only data-dependent part (a small static table indexed by the traced
``axis_index``).

:func:`compile_schedule` also accepts a per-hop **payload transform**
(``encode``/``decode``) — e.g. int8 quantization with per-block scales
(see ``repro.optim.grad_comm.compressed_all_reduce``): the transform sees
every shipped piece, and the IR stays the single source of truth for the
round structure.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.scheduler import (ChunkedSchedule, Schedule, Transfer,
                                  build_schedule, chunk_schedule)

__all__ = ["compile_schedule", "schedule_for_execution", "chunk_schedule",
           "ChunkedSchedule", "overlapped_all_reduce", "all_reduce",
           "make_all_reduce", "make_overlapped_all_reduce", "ALGOS"]

Array = jax.Array
#: encode(piece) -> payload pytree shipped over the wire
Encode = Callable[[Array], Any]
#: decode(payload, like) -> array shaped/typed like ``like``
Decode = Callable[[Any, Array], Array]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _flatten_pad(x: Array, multiple: int) -> tuple[Array, int]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % multiple
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, n


def _unflatten(flat: Array, n: int, shape) -> Array:
    return flat[:n].reshape(shape)


# ---------------------------------------------------------------------------
# the schedule -> shard_map compiler
# ---------------------------------------------------------------------------

def _runs(t: Transfer, p: int, n_chunks: int) -> tuple[np.ndarray, np.ndarray, int]:
    """First chunk of each rank's send and recv run, and the run length.

    Raises ``ValueError`` unless every rank's row of both tables is one
    ascending run of ``k`` consecutive chunks inside the buffer — the
    shape every builder emits, and the one the span lowering relies on.
    """
    send = np.asarray(t.send, dtype=np.int64)
    recv = np.asarray(t.recv, dtype=np.int64)
    if send.ndim != 2 or send.shape != recv.shape or send.shape[0] != p:
        raise ValueError(
            f"transfer tables must both be ({p}, k); got send "
            f"{send.shape}, recv {recv.shape}")
    k = send.shape[1]
    step = np.arange(k)
    for name, table in (("send", send), ("recv", recv)):
        first = table[:, 0]
        if (not np.array_equal(table, first[:, None] + step)
                or first.min() < 0 or first.max() + k > n_chunks):
            raise ValueError(
                f"{name} table is not one contiguous run of {k} chunks per "
                f"rank within {n_chunks} chunks: {table.tolist()}")
    return send[:, 0], recv[:, 0], k


def compile_schedule(schedule: Schedule, axis_name: str,
                     encode: Optional[Encode] = None,
                     decode: Optional[Decode] = None) -> Callable[[Array], Array]:
    """Lower a :class:`Schedule` to an ALLREDUCE running over ``axis_name``.

    The returned function must be called inside ``shard_map``; rank ``i``
    of the mesh axis plays ``schedule.participants[i]``.  Each
    :class:`Transfer` becomes one ``ppermute`` of one contiguous span of
    the flat buffer: a rank slices its send run at the offset its row of
    the chunk tables gives (looked up with the traced ``axis_index``),
    ships it, and adds the received span into, or writes it over, its recv
    run.  Where only some ranks are destinations of an overwrite, the
    others keep their span (``ppermute`` hands them zeros).  Tables that
    are not one contiguous run per rank raise ``ValueError`` here.
    ``encode``/``decode`` wrap every hop's payload (quantization, dtype
    casts, …); ``decode`` receives the original span as its shape/dtype
    witness.
    """
    # execution is the one consumer that needs the per-rank chunk tables:
    # build them now (pricing/simulation read only the schedule's shape)
    schedule.materialize()
    p = len(schedule.participants)
    n_chunks = schedule.n_chunks
    hops = []
    for rnd in schedule.rounds:
        for t in rnd.transfers:
            send0, recv0, k = _runs(t, p, n_chunks)
            is_dst = np.zeros((p,), dtype=bool)
            for _, d in t.perm:
                is_dst[d] = True
            # an overwrite must not clobber ranks that receive nothing
            dst = None if t.reduce or is_dst.all() else is_dst
            hops.append((t.perm, t.reduce, send0, recv0, k, dst))

    def fn(x: Array) -> Array:
        axis = jax.lax.axis_size(axis_name)
        if axis != p:
            raise ValueError(
                f"schedule has {p} participants but axis {axis_name!r} is "
                f"{axis}-wide — a mismatched perm would silently drop ranks")
        if p == 1 or not hops:
            return x
        idx = jax.lax.axis_index(axis_name)
        shape = x.shape
        flat, n = _flatten_pad(x, n_chunks)
        chunk = flat.shape[0] // n_chunks

        def offset(first_chunks: np.ndarray) -> Array:
            return jnp.asarray(first_chunks * chunk, dtype=jnp.int32)[idx]

        for perm, reduce, send0, recv0, k, dst in hops:
            size = k * chunk
            piece = jax.lax.dynamic_slice_in_dim(flat, offset(send0), size)
            payload = encode(piece) if encode is not None else piece
            got = jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis_name, perm), payload)
            if decode is not None:
                got = decode(got, piece)
            at = offset(recv0)
            if reduce:
                # non-destinations receive zeros: accumulating is a no-op
                got = jax.lax.dynamic_slice_in_dim(flat, at, size) + got
            elif dst is not None:
                got = jnp.where(jnp.asarray(dst)[idx], got,
                                jax.lax.dynamic_slice_in_dim(flat, at, size))
            flat = jax.lax.dynamic_update_slice_in_dim(flat, got, at, axis=0)
        return _unflatten(flat, n, shape)

    return fn


@functools.lru_cache(maxsize=256)
def schedule_for_execution(algo: str, p: int,
                           n_chunks: int = 1) -> "Schedule | ChunkedSchedule":
    """The canonical rank-space schedule for executing ``algo`` over ``p``
    devices (participants 0..p−1; byte metadata irrelevant to execution).

    ``n_chunks > 1`` returns the chunked (wave) lowering instead.  The LRU
    is keyed on ``(algo, p, n_chunks)`` — keying on ``(algo, p)`` alone
    would let a chunked variant alias the monolithic executable (or vice
    versa) and silently hand ``compile_schedule`` the wrong program shape;
    ``tests/test_overlap.py`` pins the non-contamination.  Cleared by
    ``cost_model.clear_pricing_caches`` like every module-level cache.
    """
    if n_chunks == 1:
        return build_schedule(algo, tuple(range(p)), 0.0)
    return chunk_schedule(schedule_for_execution(algo, p), n_chunks)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _compiled(algo: str):
    def run(x: Array, axis_name: str) -> Array:
        p = jax.lax.axis_size(axis_name)
        return compile_schedule(schedule_for_execution(algo, p), axis_name)(x)
    run.__name__ = f"{algo}_all_reduce"
    return run


ALGOS: dict[str, Callable] = {
    "ring": _compiled("ring"),
    "lumorph2": _compiled("lumorph2"),
    "lumorph4": _compiled("lumorph4"),
    "tree": _compiled("tree"),
    "psum": lambda x, axis_name: jax.lax.psum(x, axis_name),
}


def all_reduce(x: Array, axis_name: str, algo: str = "lumorph2") -> Array:
    """ALLREDUCE ``x`` over ``axis_name`` with the named LUMORPH algorithm.

    Paper §3 dispatch rule: power-of-two allocations use recursive
    doubling/halving (or quartering); anything else uses Ring.  (The
    ``lumorph2`` builder applies the same fallback, so dispatch and IR
    agree by construction.)
    """
    p = jax.lax.axis_size(axis_name)
    if algo in ("lumorph2",) and p & (p - 1):
        algo = "ring"
    try:
        fn = ALGOS[algo]
    except KeyError:
        raise ValueError(f"unknown collective {algo!r}; have {sorted(ALGOS)}")
    return fn(x, axis_name)


def overlapped_all_reduce(x: Array, axis_name: str, algo: str = "lumorph2",
                          n_chunks: int = 1,
                          compute: Optional[Callable[[Array], Array]] = None,
                          encode: Optional[Encode] = None,
                          decode: Optional[Decode] = None,
                          schedule: "Optional[Schedule | ChunkedSchedule]" = None,
                          ) -> Array:
    """Chunked, pipelined ALLREDUCE over ``axis_name`` (PCCL-style).

    The buffer is split into ``n_chunks`` equal payload slices; each slice
    runs the full collective program as its own reduce-scatter + all-gather
    waves (``scheduler.chunk_schedule``), and ``compute`` — e.g. a Pallas
    kernel consuming each reduced bucket — is issued on chunk ``k−1``
    *after* chunk ``k``'s ppermutes, so the XLA scheduler can hide the wire
    time behind the compute stream (on CPU the interleaving is still
    traced, just not concurrent).  Must be called inside ``shard_map``.

    Equivalence contract (``tests/test_overlap.py``): for every algorithm,
    chunk count, and dtype the result equals ``lax.psum`` to tolerance, and
    ``n_chunks=1`` with ``compute=None`` is **bit-identical** to the
    monolithic :func:`all_reduce` path — the wave split and re-slicing add
    no arithmetic.  ``encode``/``decode`` wrap every hop of every wave, so
    the int8 payload transform composes per-chunk unchanged.

    ``compute`` (when given) maps each *reduced* slice to its output slice
    (shapes preserved); the returned array concatenates the computed
    slices.  ``schedule`` overrides the rank-space program — pass a
    pod-built ``hier:*`` Schedule (or a prebuilt :class:`ChunkedSchedule`)
    whose participant count matches the axis.
    """
    p = jax.lax.axis_size(axis_name)
    if schedule is None:
        a = algo
        if a in ("lumorph2",) and p & (p - 1):
            a = "ring"  # same paper-§3 dispatch as all_reduce
        chunked = schedule_for_execution(a, p, n_chunks)
        if not isinstance(chunked, ChunkedSchedule):
            chunked = chunk_schedule(chunked, n_chunks)
    else:
        chunked = (schedule if isinstance(schedule, ChunkedSchedule)
                   else chunk_schedule(schedule, n_chunks))
    C = chunked.n_chunks
    if len(chunked.participants) != p:
        raise ValueError(
            f"schedule has {len(chunked.participants)} participants but "
            f"axis {axis_name!r} is {p}-wide")

    shape = x.shape
    flat, n = _flatten_pad(x, C)
    size = flat.shape[0] // C
    slices = [flat[c * size:(c + 1) * size] for c in range(C)]

    # one compiled fn per shared wave schedule (chunks reuse the programs)
    fns: dict[int, Callable[[Array], Array]] = {}
    per_chunk: list[list[Callable[[Array], Array]]] = [[] for _ in range(C)]
    for w in chunked.waves:
        f = fns.get(id(w.schedule))
        if f is None:
            f = fns[id(w.schedule)] = compile_schedule(
                w.schedule, axis_name, encode=encode, decode=decode)
        per_chunk[w.chunk].append(f)

    reduced: list[Optional[Array]] = [None] * C
    outs: list[Optional[Array]] = [None] * C

    def finish(c: int) -> None:
        outs[c] = reduced[c] if compute is None else compute(reduced[c])

    for c in range(C):
        y = slices[c]
        for f in per_chunk[c]:  # issue chunk c's waves (rs then ag)
            y = f(y)
        reduced[c] = y
        if c > 0:
            finish(c - 1)  # chunk c−1's compute rides behind chunk c's comm
    finish(C - 1)
    out = jnp.concatenate(outs) if C > 1 else outs[0]
    return _unflatten(out, n, shape)


def make_overlapped_all_reduce(mesh: Mesh, axis_name: str,
                               algo: str = "lumorph2", n_chunks: int = 1,
                               compute: Optional[Callable[[Array], Array]] = None,
                               schedule: "Optional[Schedule | ChunkedSchedule]" = None,
                               ) -> Callable[[Array], Array]:
    """Jitted global-array wrapper of :func:`overlapped_all_reduce` (the
    chunked sibling of :func:`make_all_reduce`; same sharding contract)."""
    fn = jax.shard_map(
        lambda v: overlapped_all_reduce(v[0], axis_name, algo,
                                        n_chunks=n_chunks, compute=compute,
                                        schedule=schedule)[None],
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=P(axis_name),
        axis_names={axis_name},
        check_vma=False,
    )
    return jax.jit(fn)


def make_all_reduce(mesh: Mesh, axis_name: str, algo: str = "lumorph2",
                    extra_specs: P | None = None) -> Callable[[Array], Array]:
    """Build a jitted global-array ALLREDUCE over one mesh axis.

    The input is expected sharded with ``axis_name`` as its leading axis
    (one slice per chip); output is identically sharded, every slice holding
    the sum.  Used by tests and the gradient-communication layer.
    """
    fn = jax.shard_map(
        lambda v: all_reduce(v[0], axis_name, algo)[None],
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=P(axis_name),
        axis_names={axis_name},
        check_vma=False,  # our ppermute allreduce provably replicates, but
                          # the VMA checker cannot see through the rounds
    )
    return jax.jit(fn)
