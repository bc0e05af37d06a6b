"""Chunked overlapped collectives: the differential equivalence harness.

The chunked lowering (``scheduler.chunk_schedule`` →
``collectives.overlapped_all_reduce``) re-emits a Schedule's rounds as
per-chunk reduce-scatter/all-gather **waves** on ``1/C`` payload slices.
This file is the proof obligation that the transformation is invisible:

  * **differential equivalence** (slow, multi-device subprocess) — for
    every algorithm ``candidate_algos`` admits on a 2-rack pod layout
    (flat + ``hier:*``) × chunk counts {1, 2, 4, 7} × payload modes
    {f32, bf16, int8-transform}, the overlapped result equals the
    monolithic ``compile_schedule`` program and ``lax.psum`` to dtype
    tolerance — on *noncontiguous, scrambled* chip orderings — and
    ``n_chunks=1`` is **bit-identical** to the monolithic path;
  * **wave partitioning** (properties) — every base round lands in
    exactly one wave per chunk, phases stay ordered (rs before its ag
    dual), circuit-pair arrays are shared by identity (the MZI-window
    fast path sees through chunking), and bytes scale by exactly 1/C;
  * **pricing coherence** — ``sum(wave_costs) ≡ cost`` (the serial,
    overlap-disabled program), ``C=1`` prices bit-identically to the
    base schedule, chunking only ever *adds* α/MZI cost, and
    ``pipeline_time`` stays inside its [max, sum] envelope;
  * **laziness** — chunking, pricing, and validating chunked programs
    build zero Transfer tables;
  * **cache keying** (regression) — ``schedule_for_execution`` is keyed
    on ``(algo, p, n_chunks)``: chunked executables never alias the
    monolithic entry or each other.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cost_model as cm
from repro.core.fabric import CircuitError
from repro.core.rack import Pod
from repro.core.scheduler import (build_any_schedule, build_schedule,
                                  candidate_algos, chunk_schedule,
                                  transfer_tables_built)

SRC = str(Path(__file__).resolve().parents[1] / "src")

FLAT = ("ring", "lumorph2", "lumorph4", "tree")
HIER = ("hier:ring", "hier:lumorph2", "hier:lumorph4")
TILES = 8
CPR = 32  # chips per rack in the pod-geometry properties


def _pod(n_racks: int = 2) -> Pod:
    return Pod(n_racks=n_racks, chips_per_rack=CPR,
               fibers_per_server_pair=4 * TILES)


def _spanning_chips(p: int, n_racks: int = 2) -> tuple[int, ...]:
    share = p // n_racks
    return tuple(r * CPR + i for r in range(n_racks) for i in range(share))


# ---------------------------------------------------------------------------
# wave partitioning (properties over the shape-only IR)
# ---------------------------------------------------------------------------

@given(st.sampled_from(FLAT), st.sampled_from([2, 3, 4, 6, 8, 16]),
       st.integers(1, 8), st.floats(1e3, 1e9))
@settings(max_examples=100, deadline=None)
def test_every_round_lands_in_exactly_one_wave(algo, p, C, n_bytes):
    """Per chunk: the wave rounds, concatenated in wave order, are the
    base program — same circuits (by identity), same phase tags, bytes
    scaled by exactly 1/C.  Nothing dropped, nothing duplicated."""
    base = build_schedule(algo, tuple(range(p)), n_bytes)
    chunked = chunk_schedule(base, C)
    phases_seen = {w.phase for w in chunked.waves}
    assert len(chunked.waves) == C * len(phases_seen)
    for c in range(C):
        waves = chunked.waves_of_chunk(c)
        phases = [w.phase for w in waves]
        # rs strictly precedes its ag dual; no interleaving, no repeats
        assert phases in ([], ["rs"], ["ag"], ["rs", "ag"])
        rounds = [r for w in waves for r in w.schedule.rounds]
        assert len(rounds) == len(base.rounds)
        for rb, rc in zip(base.rounds, rounds):
            assert rc.pairs_arr is rb.pairs_arr  # circuit sharing: the
            # `arr is prev_arr` MZI fast path must see through chunking
            assert rc.reduce == rb.reduce
            assert rc.tier == rb.tier
            assert rc.egress_fanout == rb.egress_fanout
            assert rc.bytes_per_circuit == rb.bytes_per_circuit * (1.0 / C)
        for w in waves:
            assert all(r.reduce == (w.phase == "rs")
                       for r in w.schedule.rounds)


@given(st.sampled_from(FLAT + HIER), st.integers(1, 6))
@settings(max_examples=40, deadline=None)
def test_waves_validate_on_pod_fabric(algo, C):
    """Per-wave photonic feasibility (TRX banks, fiber/rail budgets) on a
    2-rack pod: waves run one at a time on the wire, so each must satisfy
    the same limits the base program does."""
    pod = _pod()
    chips = _spanning_chips(8)
    sched = build_any_schedule(algo, chips, 1e7, chips_per_rack=CPR)
    try:
        sched.validate(pod)
    except CircuitError:
        return  # base inadmissible on this fabric: chunking can't fix it
    chunked = chunk_schedule(sched, C)
    chunked.validate(pod)  # must not raise — base validates, waves must too
    for w in chunked.waves:
        assert w.schedule.participants == sched.participants


# ---------------------------------------------------------------------------
# pricing coherence
# ---------------------------------------------------------------------------

@given(st.sampled_from(FLAT), st.sampled_from([2, 3, 4, 8, 12, 16]),
       st.integers(1, 8), st.floats(1e3, 1e9))
@settings(max_examples=100, deadline=None)
def test_wave_costs_sum_to_serial_cost(algo, p, C, n_bytes):
    """Overlap disabled, the chunked program is just the serial
    concatenation of its waves: the per-wave attribution must re-add to
    ``cost`` (both per-wave and per-chunk groupings)."""
    chunked = chunk_schedule(build_schedule(algo, tuple(range(p)), n_bytes), C)
    for link in (cm.LUMORPH_LINK, cm.IDEAL_SWITCH):
        total = chunked.cost(link)
        waves = chunked.wave_costs(link)
        assert len(waves) == len(chunked.waves)
        assert sum(waves) == pytest.approx(total, rel=1e-12, abs=1e-18)
        chunks = chunked.chunk_costs(link)
        assert len(chunks) == C
        assert sum(chunks) == pytest.approx(total, rel=1e-12, abs=1e-18)
        assert all(s >= 0.0 for s in waves)


@given(st.sampled_from(FLAT), st.sampled_from([2, 4, 8, 16, 32]),
       st.floats(1e3, 1e9))
@settings(max_examples=100, deadline=None)
def test_chunks1_prices_bit_identical_to_base(algo, p, n_bytes):
    """C=1 is the monolithic program under another name: its serial cost
    must equal the base schedule's cost exactly (==, not approx — golden
    traces price through the same rounds)."""
    base = build_schedule(algo, tuple(range(p)), n_bytes)
    chunked = chunk_schedule(base, 1)
    pod = _pod()
    for link in (cm.LUMORPH_LINK, cm.IDEAL_SWITCH):
        assert chunked.cost(link) == base.cost(link)
    assert cm.chunked_algorithm_cost(algo, n_bytes, p, cm.LUMORPH_LINK, 1) \
        == cm.algorithm_cost(algo, n_bytes, p, cm.LUMORPH_LINK)
    if p <= 2 * CPR:
        chips = _spanning_chips(p) if p >= 2 else (0,)
        s = build_any_schedule(algo, chips, n_bytes, chips_per_rack=CPR)
        assert chunk_schedule(s, 1).cost(cm.LUMORPH_LINK, rack=pod) \
            == s.cost(cm.LUMORPH_LINK, rack=pod)


@given(st.sampled_from(FLAT), st.sampled_from([2, 4, 8, 16]),
       st.integers(2, 12), st.floats(1e3, 1e9))
@settings(max_examples=100, deadline=None)
def test_chunking_only_adds_alpha(algo, p, C, n_bytes):
    """Chunking repeats every round C× at 1/C bytes: β is conserved, α
    and MZI windows can only grow — serial chunked cost ≥ monolithic."""
    mono = cm.algorithm_cost(algo, n_bytes, p, cm.LUMORPH_LINK)
    chunked = cm.chunked_algorithm_cost(algo, n_bytes, p, cm.LUMORPH_LINK, C)
    assert chunked >= mono * (1.0 - 1e-12)
    # and the overhead is pure α/reconfig: on an ideal switch with zero α
    # and zero reconfig the two are equal
    zero_alpha = cm.LinkModel(alpha=0.0, bw=cm.LUMORPH_LINK.bw,
                              reconfig=0.0, name="zero-alpha")
    assert cm.chunked_algorithm_cost(algo, n_bytes, p, zero_alpha, C) \
        == pytest.approx(cm.algorithm_cost(algo, n_bytes, p, zero_alpha),
                         rel=1e-12)


@given(st.lists(st.floats(0.0, 1.0), min_size=0, max_size=8),
       st.floats(0.0, 1.0))
@settings(max_examples=200, deadline=None)
def test_pipeline_time_envelope(comm, compute):
    """The two-engine recurrence can never beat either engine running
    alone (max bound) nor lose to full serialization (sum bound)."""
    t = cm.pipeline_time(comm, compute)
    assert t >= max(sum(comm), compute) - 1e-12
    assert t <= sum(comm) + compute + 1e-12
    assert cm.pipeline_time(comm, 0.0) == pytest.approx(sum(comm))
    assert cm.pipeline_time([], compute) == compute


def test_overlapped_step_time_consistency():
    link = cm.LUMORPH_LINK
    n, p, compute = 64e6, 16, 2e-4
    # C=1 is the unoverlapped baseline: compute + monolithic collective
    assert cm.overlapped_step_time("lumorph2", n, p, link, 1, compute) \
        == compute + cm.algorithm_cost("lumorph2", n, p, link)
    for C in (2, 4, 8):
        t = cm.overlapped_step_time("lumorph2", n, p, link, C, compute)
        serial = cm.chunked_algorithm_cost("lumorph2", n, p, link, C)
        assert max(serial, compute) - 1e-15 <= t <= serial + compute + 1e-15
    # lumorph2 on a non-power-of-two falls back to ring (paper §3) — the
    # cache key must canonicalize identically on both entry points
    assert cm.overlapped_step_time("lumorph2", n, 6, link, 4, compute) \
        == cm.overlapped_step_time("ring", n, 6, link, 4, compute)
    assert cm.chunked_algorithm_cost("lumorph2", n, 6, link, 4) \
        == cm.chunked_algorithm_cost("ring", n, 6, link, 4)
    with pytest.raises(ValueError):
        cm.chunked_algorithm_cost("dnc", n, p, link, 2)


def test_overlap_wins_in_the_balanced_regime():
    """The claim the benchmark gates: at the paper-scale operating point
    (p=256, 256 MB, LUMORPH-2) with compute ≈ comm, 8-way chunking hides
    most of the wire time — >1.3× over the unoverlapped step."""
    link, n, p = cm.LUMORPH_LINK, 256e6, 256
    comm = cm.algorithm_cost("lumorph2", n, p, link)
    t_mono = cm.overlapped_step_time("lumorph2", n, p, link, 1, comm)
    t_ovl = cm.overlapped_step_time("lumorph2", n, p, link, 8, comm)
    assert t_mono / t_ovl > 1.3, (t_mono, t_ovl)


# ---------------------------------------------------------------------------
# laziness: chunked planning builds zero Transfer tables
# ---------------------------------------------------------------------------

def test_chunked_planning_materializes_nothing():
    pod = _pod()
    chips = _spanning_chips(8)
    before = transfer_tables_built()
    for algo in candidate_algos(FLAT, chips, CPR):
        sched = build_any_schedule(algo, chips, 1e7, chips_per_rack=CPR)
        for C in (1, 2, 4, 7):
            chunked = chunk_schedule(sched, C)
            chunked.cost(cm.LUMORPH_LINK)
            chunked.cost(cm.LUMORPH_LINK, rack=pod)
            chunked.wave_costs(cm.LUMORPH_LINK, pod)
            chunked.chunk_costs(cm.LUMORPH_LINK)
            chunked.overlapped_cost(cm.LUMORPH_LINK, compute_s=1e-4)
            chunked.validate(pod)
    assert transfer_tables_built() == before, \
        "chunked planning materialized Transfer tables"


# ---------------------------------------------------------------------------
# cache keying regression: (algo, p) → (algo, p, n_chunks)
# ---------------------------------------------------------------------------

def test_schedule_for_execution_keys_on_n_chunks():
    """The executable-schedule LRU must not cross-contaminate chunked and
    monolithic entries (the bug class: keying on (algo, p) alone hands
    compile_schedule a ChunkedSchedule where a Schedule is expected)."""
    from repro.core import collectives as cl
    cl.schedule_for_execution.cache_clear()
    mono = cl.schedule_for_execution("ring", 8)
    chunked = cl.schedule_for_execution("ring", 8, 4)
    assert isinstance(chunked, cl.ChunkedSchedule)
    assert not isinstance(mono, cl.ChunkedSchedule)
    # the chunked variant wraps the *cached* monolithic program …
    assert chunked.base is mono
    # … and neither key clobbers the other
    assert cl.schedule_for_execution("ring", 8) is mono
    assert cl.schedule_for_execution("ring", 8, 4) is chunked
    other = cl.schedule_for_execution("ring", 8, 2)
    assert other is not chunked and other.n_chunks == 2
    assert cl.schedule_for_execution("ring", 8, 1) is not chunked
    # clear_pricing_caches flushes the executable cache (chunked included)
    cm.clear_pricing_caches()
    assert cl.schedule_for_execution.cache_info().currsize == 0


# ---------------------------------------------------------------------------
# differential equivalence (multi-device, subprocess — slow tier)
# ---------------------------------------------------------------------------

CHECK = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.core.collectives import (compile_schedule,
                                    make_overlapped_all_reduce,
                                    overlapped_all_reduce)
from repro.core.scheduler import build_any_schedule, candidate_algos
from repro.optim.grad_comm import _int8_decode, _int8_encode

MODE = {mode!r}
p = 8
CPR = 32
mesh = jax.make_mesh((p,), ("d",), axis_types=(AxisType.Auto,))
flat_chips = (5, 12, 3, 40, 21, 9, 33, 18)  # scattered, noncontiguous
pod_chips = (2, 0, 3, 1, 34, 32, 35, 33)    # 2 racks x 4, scrambled
algos = candidate_algos(("ring", "lumorph2", "lumorph4", "tree"),
                        pod_chips, CPR)
assert any(a.startswith("hier:") for a in algos), algos

rng = np.random.RandomState(0)
xf = rng.randn(p, 37)  # 37: odd width so chunk/wave padding is exercised
expect = np.tile(xf.sum(0, keepdims=True), (p, 1)).astype(np.float32)

if MODE == "f32":
    dtype, rtol, enc, dec = jnp.float32, 1e-5, None, None
elif MODE == "bf16":
    dtype, rtol, enc, dec = jnp.bfloat16, 5e-2, None, None
else:  # int8 per-hop payload transform over an fp32 buffer
    dtype, rtol, enc, dec = jnp.float32, 5e-2, _int8_encode, _int8_decode

xs = jax.device_put(jnp.asarray(xf).astype(dtype),
                    NamedSharding(mesh, P("d", None)))

def run(fn):
    f = jax.jit(jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                in_specs=P("d", None), out_specs=P("d", None),
                axis_names={{"d"}}, check_vma=False))
    return np.asarray(f(xs).astype(jnp.float32))

def relerr(a):
    return np.abs(a - expect).max() / np.abs(expect).max()

assert relerr(run(lambda v: jax.lax.psum(v, "d"))) < rtol, "psum reference"

for algo in algos:
    chips = pod_chips if algo.startswith("hier:") else flat_chips
    sched = build_any_schedule(algo, chips, 4096.0, chips_per_rack=CPR)
    mono = run(compile_schedule(sched, "d", encode=enc, decode=dec))
    assert relerr(mono) < rtol, (algo, "mono", relerr(mono))
    for C in (1, 2, 4, 7):
        out = run(lambda v, C=C: overlapped_all_reduce(
            v, "d", n_chunks=C, schedule=sched, encode=enc, decode=dec))
        assert relerr(out) < rtol, (algo, C, relerr(out))
        if C == 1:
            # the wave split adds no arithmetic: bit-identical to monolithic
            assert np.array_equal(out, mono), (algo, MODE)

if MODE == "f32":
    # compute fused into the pipeline: chunk k-1's kernel behind chunk k's
    # waves — result is compute(psum(x)) exactly
    f = make_overlapped_all_reduce(mesh, "d", algo="ring", n_chunks=4,
                                   compute=lambda y: y * 2.0)
    out = np.asarray(f(xs))
    assert np.allclose(out, 2.0 * expect, rtol=1e-5, atol=1e-5)
print("SUBPROCESS_OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["f32", "bf16", "int8"])
def test_overlapped_equivalence_multidevice(mode):
    """overlapped_all_reduce ≡ compile_schedule ≡ lax.psum, for every
    admissible algorithm (flat on scattered chips + hier:* on a scrambled
    2-rack pod layout) × C ∈ {1, 2, 4, 7}, per payload mode."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", CHECK.format(src=SRC, mode=mode)],
        capture_output=True, text=True, timeout=900, env=env)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
