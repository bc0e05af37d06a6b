"""The span lowering of the Schedule IR: every hop is one contiguous span.

``compile_schedule`` keeps each buffer flat and lowers every
:class:`~repro.core.scheduler.Transfer` onto one span per rank: a dynamic
slice at the offset the rank's row of the chunk tables gives, one
``ppermute``, and one dynamic update of the receive span.  This file holds
it to three things:

  * **contiguity** — every transfer of every schedule the IR builds (flat
    algorithms, chunked waves, ``hierarchical_schedule`` compositions) has
    one ascending run of equal length per rank, which the lowering relies
    on; a hand-made table that is not one run makes ``compile_schedule``
    raise ``ValueError``;
  * **bit identity** (multi-device subprocess) — the lowering equals a
    numpy player of the IR's tables bit for bit, in the wire dtype, with
    the adds in the IR's order, monolithic and chunked;
  * **structure** — the traced gradient exchange holds no gather, no
    scatter and no select over a whole bucket.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core.collectives import compile_schedule
from repro.core.scheduler import (Round, Schedule, Transfer, build_schedule,
                                  chunk_schedule, hierarchical_schedule)
from repro.optim.grad_comm import all_reduce_grads, make_buckets

SRC = str(Path(__file__).resolve().parents[1] / "src")

FLAT = ("ring", "lumorph2", "lumorph4", "tree")
CPR = 32  # chips per rack of the hierarchical layouts


def _transfers(schedule: Schedule):
    schedule.materialize()
    return [t for rnd in schedule.rounds for t in rnd.transfers]


def _assert_spans(schedule: Schedule) -> None:
    p = len(schedule.participants)
    for t in _transfers(schedule):
        for table in (np.asarray(t.send), np.asarray(t.recv)):
            assert table.ndim == 2 and table.shape[0] == p, table.shape
            k = table.shape[1]
            first = table[:, :1]
            assert np.array_equal(table, first + np.arange(k)), table
            assert first.min() >= 0 and first.max() + k <= schedule.n_chunks
    compile_schedule(schedule, "d")  # lowers without raising


# ---------------------------------------------------------------------------
# contiguity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 3, 4, 6, 8])
@pytest.mark.parametrize("algo", FLAT)
def test_flat_schedule_hops_are_spans(algo, p):
    _assert_spans(build_schedule(algo, tuple(range(p)), 1e6))


@pytest.mark.parametrize("C", [2, 4, 7])
@pytest.mark.parametrize("p", [4, 6])
@pytest.mark.parametrize("algo", FLAT)
def test_chunked_wave_hops_are_spans(algo, p, C):
    chunked = chunk_schedule(build_schedule(algo, tuple(range(p)), 1e6), C)
    for w in chunked.waves:
        _assert_spans(w.schedule)


@pytest.mark.parametrize("n_racks", [2, 3, 4])
@pytest.mark.parametrize("intra", ["ring", "lumorph2", "lumorph4"])
def test_hierarchical_hops_are_spans(intra, n_racks):
    for share in (2, 4):
        chips = tuple(r * CPR + i for r in range(n_racks) for i in range(share))
        sched = hierarchical_schedule(chips, 1e6, CPR, intra=intra)
        _assert_spans(sched)
        for w in chunk_schedule(sched, 3).waves:
            _assert_spans(w.schedule)


@pytest.mark.parametrize("send,recv", [
    pytest.param([[0, 2], [1, 3]], [[1, 3], [0, 2]], id="gap"),
    pytest.param([[1, 0], [3, 2]], [[2, 3], [0, 1]], id="descending"),
    pytest.param([[0, 1], [2, 3]], [[3, 4], [0, 1]], id="past-the-end"),
])
def test_noncontiguous_table_raises(send, recv):
    transfer = Transfer(perm=((0, 1), (1, 0)),
                        send=np.asarray(send, np.int32),
                        recv=np.asarray(recv, np.int32))
    sched = Schedule("hand", (0, 1), (Round([(0, 1), (1, 0)], 1.0,
                                            transfers=(transfer,)),),
                     1.0, n_chunks=4)
    with pytest.raises(ValueError, match="contiguous"):
        compile_schedule(sched, "d")


# ---------------------------------------------------------------------------
# bit identity against a numpy player of the IR
# ---------------------------------------------------------------------------

def _play(schedule: Schedule, xs: np.ndarray) -> np.ndarray:
    """Every rank's buffer after ``schedule``, played on host arrays.

    ``xs`` is ``(p, n)`` in the wire dtype.  Each rank's buffer is zero
    padded to ``n_chunks`` equal chunks; a transfer ships the chunks of
    each source's ``send`` row (read before anything lands) and adds them
    to, or writes them over, the chunks of the destination's ``recv`` row.
    """
    p, n = xs.shape
    K = schedule.n_chunks
    bufs = np.zeros((p, -(-n // K) * K), xs.dtype)
    bufs[:, :n] = xs
    bufs = bufs.reshape(p, K, -1)
    for t in _transfers(schedule):
        shipped = {s: bufs[s, t.send[s]].copy() for s, _ in t.perm}
        for s, d in t.perm:
            if t.reduce:
                bufs[d, t.recv[d]] = bufs[d, t.recv[d]] + shipped[s]
            else:
                bufs[d, t.recv[d]] = shipped[s]
    return bufs.reshape(p, -1)[:, :n]


def _play_chunked(schedule: Schedule, C: int, xs: np.ndarray) -> np.ndarray:
    """:func:`_play` over ``C`` equal slices, each through its own waves."""
    p, n = xs.shape
    padded = np.zeros((p, -(-n // C) * C), xs.dtype)
    padded[:, :n] = xs
    size = padded.shape[1] // C
    chunked = chunk_schedule(schedule, C)
    outs = []
    for c in range(C):
        y = padded[:, c * size:(c + 1) * size]
        for w in chunked.waves_of_chunk(c):
            y = _play(w.schedule, y)
        outs.append(y)
    return np.concatenate(outs, axis=1)[:, :n]


MESHES = {4: (0, 1, 2, 3), 6: (3, 11, 4, 40, 25, 17)}  # 6: scattered chips
MODES = ("mono", 1, 2, 4, 7)  # monolithic, then chunked at C
DTYPES = {"f32": (np.float32, np.uint32), "bf16": (ml_dtypes.bfloat16, np.uint16)}
WIDTH = 37  # divisible by no chunk count of any case
CASES = [f"{p}-{algo}-{mode}-{dt}" for p in MESHES for algo in FLAT
         for mode in MODES for dt in DTYPES]

LOWER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core.collectives import compile_schedule, overlapped_all_reduce
from repro.core.scheduler import build_schedule

ins = np.load({inputs!r})
out = {{}}
for case in {cases!r}:
    p, algo, mode, dt = case.split("-")
    p = int(p)
    mesh = Mesh(np.asarray(jax.devices()[:p]), ("d",))
    sched = build_schedule(algo, {meshes!r}[p], 1e6)
    if mode == "mono":
        fn = compile_schedule(sched, "d")
    else:
        fn = lambda v, C=int(mode), s=sched: overlapped_all_reduce(
            v, "d", n_chunks=C, schedule=s)
    bits = ins[f"{{p}}-{{dt}}"]
    x = jax.lax.bitcast_convert_type(
        jnp.asarray(bits), jnp.float32 if dt == "f32" else jnp.bfloat16)
    f = jax.jit(jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                in_specs=P("d", None), out_specs=P("d", None),
                axis_names={{"d"}}, check_vma=False))
    y = f(jax.device_put(x, NamedSharding(mesh, P("d", None))))
    out[case] = np.asarray(jax.lax.bitcast_convert_type(y, bits.dtype))
np.savez({outputs!r}, **out)
print("SUBPROCESS_OK")
"""


def _inputs(p: int, dt: str) -> np.ndarray:
    dtype, _ = DTYPES[dt]
    return np.random.RandomState(p).randn(p, WIDTH).astype(dtype)


@pytest.fixture(scope="module")
def lowered(tmp_path_factory):
    """Every case's output bits from one multi-device subprocess."""
    tmp = tmp_path_factory.mktemp("spans")
    inputs, outputs = str(tmp / "inputs.npz"), str(tmp / "outputs.npz")
    np.savez(inputs, **{f"{p}-{dt}": _inputs(p, dt).view(DTYPES[dt][1])
                        for p in MESHES for dt in DTYPES})
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    code = LOWER.format(src=SRC, inputs=inputs, outputs=outputs,
                        cases=CASES, meshes=MESHES)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
    with np.load(outputs) as f:
        return dict(f)


@pytest.mark.parametrize("case", CASES)
def test_lowering_matches_ir_player_bit_for_bit(lowered, case):
    p, algo, mode, dt = case.split("-")
    p = int(p)
    xs = _inputs(p, dt)
    sched = build_schedule(algo, MESHES[p], 1e6)
    want = (_play(sched, xs) if mode == "mono"
            else _play_chunked(sched, int(mode), xs))
    got = lowered[case].view(DTYPES[dt][0])
    np.testing.assert_array_equal(got.view(DTYPES[dt][1]),
                                  want.view(DTYPES[dt][1]))
    # and the played IR is an ALLREDUCE
    np.testing.assert_allclose(want.astype(np.float32),
                               np.tile(xs.astype(np.float32).sum(0), (p, 1)),
                               rtol=5e-2 if dt == "bf16" else 1e-5, atol=5e-2)


# ---------------------------------------------------------------------------
# structure of the traced exchange
# ---------------------------------------------------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_lumorph4_exchange_has_no_gather_scatter_or_bucket_select():
    """``all_reduce_grads`` with ``lumorph4`` on a 4-rank axis, traced:
    each hop is slices, a permute, an add and an update, nothing that
    reads or rewrites a bucket through an index array or a whole-bucket
    select."""
    leaves = {"w": jnp.zeros((3, 40, 8)), "b": jnp.zeros((333,)),
              "n": jnp.zeros((7, 9), jnp.bfloat16)}
    bucket_bytes = 1024
    total = sum(x.size for x in leaves.values())
    buckets = make_buckets(total, bucket_bytes)
    assert len(buckets) > 2
    smallest = min(b.n_elems for b in buckets)

    def body(g):
        out, _, _ = all_reduce_grads(g, ("d",), algo="lumorph4",
                                       bucket_bytes=bucket_bytes)
        return out

    f = jax.shard_map(body, mesh=AbstractMesh((4,), ("d",)), in_specs=P(),
                      out_specs=P(), axis_names={"d"}, check_vma=False)
    eqns = list(_eqns(jax.make_jaxpr(f)(leaves).jaxpr))
    names = {e.primitive.name for e in eqns}
    assert "ppermute" in names and "dynamic_update_slice" in names, names
    assert not {n for n in names if "gather" in n or "scatter" in n}, names
    for e in eqns:
        if e.primitive.name == "select_n":
            sizes = [int(np.prod(v.aval.shape)) for v in e.invars]
            assert max(sizes) < smallest, (sizes, smallest)
