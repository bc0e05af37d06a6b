"""Pod-scale fabric: hierarchical Schedule-IR composition, rail pricing,
rack-first allocation, and the pod simulator.

The property suite pins the new tier the same way ``test_schedule_ir``
pins the rack tier:

  * **permutation programs** — a composed hierarchical schedule is a
    well-formed Schedule-IR program: every round's transfers are partial
    permutations whose union tiles the round's circuit pairs, chunk
    tables are rank-complete and in range (hypothesis-driven, p up to
    512 via the heavy ``slow`` sweep);
  * **TRX/rail feasibility** — every round respects per-chip TRX limits
    on the pod, and the inter stage's per-rack-pair rail demand is
    bounded by the per-rack share;
  * **cost decomposition** — ``Schedule.cost`` against a Pod equals the
    sum of the per-tier ``cost_by_tier`` terms, the tier-1 term exists
    iff the schedule crosses racks, and the composed rounds' tier tags
    agree with the pod geometry;
  * **execution** — a compiled hierarchical schedule reproduces
    ``lax.psum`` (multi-device, in a subprocess).
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cost_model as cm
from repro.core.allocator import AllocationError, PodAllocator, make_allocator
from repro.core.fabric import CircuitError
from repro.core.rack import Pod, default_pod
from repro.core.scheduler import (build_any_schedule, build_schedule,
                                  candidate_algos, compose_hierarchical,
                                  hierarchical_schedule, order_for_locality,
                                  rail_demand)
from repro.sim import RackSimulator, pod_churn_trace

INTRAS = ("ring", "lumorph2", "lumorph4")


def _pod_chips(n_racks: int, m: int, chips_per_rack: int) -> tuple[int, ...]:
    """The first ``m`` chips of each of ``n_racks`` racks."""
    return tuple(c for r in range(n_racks)
                 for c in range(r * chips_per_rack, r * chips_per_rack + m))


def _check_program(sched, p: int) -> None:
    """Schedule-IR well-formedness (mirrors test_schedule_ir's contract)."""
    sched.materialize()  # transfers are lazy; inspecting them builds them
    chips = sched.participants
    assert len(chips) == p
    for rnd in sched.rounds:
        from_transfers = []
        for t in rnd.transfers:
            srcs = [s for s, _ in t.perm]
            dsts = [d for _, d in t.perm]
            assert len(set(srcs)) == len(srcs), "duplicate sender in one ppermute"
            assert len(set(dsts)) == len(dsts), "duplicate receiver in one ppermute"
            from_transfers.extend((chips[s], chips[d]) for s, d in t.perm)
            assert t.send.shape == t.recv.shape == (p, t.send.shape[1])
            assert (0 <= t.send).all() and (t.send < sched.n_chunks).all()
            assert (0 <= t.recv).all() and (t.recv < sched.n_chunks).all()
        assert sorted(from_transfers) == sorted(rnd.pairs), \
            "transfer perms must tile the round's circuit pairs"


# ---------------------------------------------------------------------------
# hierarchical composition: permutation programs + feasibility + cost
# ---------------------------------------------------------------------------

@given(st.sampled_from(INTRAS), st.sampled_from([1, 2, 3, 4, 6, 8, 16]),
       st.integers(2, 4))
@settings(max_examples=40, deadline=None)
def test_hierarchical_is_valid_permutation_program(intra, m, n_racks):
    cpr = 16
    chips = _pod_chips(n_racks, m, cpr)
    sched = hierarchical_schedule(chips, 1e6, cpr, intra=intra)
    _check_program(sched, m * n_racks)
    assert sched.participants == chips
    # the inter stage exists iff > 1 rack participates
    tags = {r.tier for r in sched.rounds}
    assert 1 in tags
    assert sched.n_chunks % max(m, 1) == 0


@pytest.mark.slow
@pytest.mark.parametrize("intra,m,n_racks", [
    ("ring", 256, 2), ("lumorph2", 256, 2), ("lumorph4", 256, 2),
    ("lumorph4", 128, 4), ("lumorph2", 128, 4), ("lumorph4", 64, 8),
    ("ring", 170, 3),
])
def test_hierarchical_program_at_512_chips(intra, m, n_racks):
    """The full contract at the benchmark's pod scale (p ≈ 512)."""
    cpr = 256
    chips = _pod_chips(n_racks, m, cpr)
    sched = hierarchical_schedule(chips, 64 * 2**20, cpr, intra=intra)
    _check_program(sched, m * n_racks)
    pod = Pod(n_racks=n_racks, chips_per_rack=cpr, fibers_per_server_pair=32)
    sched.validate(pod, check_fibers=False)  # TRX always feasible
    tiers = sched.cost_by_tier(cm.LUMORPH_LINK, rack=pod)
    assert sched.cost(cm.LUMORPH_LINK, rack=pod) == pytest.approx(
        sum(tiers.values()), rel=1e-12)


@given(st.sampled_from(INTRAS), st.sampled_from([1, 2, 4, 8]),
       st.integers(2, 4))
@settings(max_examples=40, deadline=None)
def test_hierarchical_trx_and_rail_feasibility(intra, m, n_racks):
    cpr = 8
    chips = _pod_chips(n_racks, m, cpr)
    sched = hierarchical_schedule(chips, 1e6, cpr, intra=intra)
    pod = Pod(n_racks=n_racks, chips_per_rack=cpr, tiles_per_server=4,
              fibers_per_server_pair=64, rails_per_rack_pair=2 * m)
    # TRX limits hold on every round even with the rail budget enforced:
    # the inter stage never asks a rack pair for more than 2·m circuits
    # (each shard-owner group contributes ≤ 1 circuit per direction)
    sched.validate(pod, check_fibers=True)
    assert rail_demand(sched, cpr) <= 2 * m
    # a rail-starved pod raises only when budgets are enforced
    tight = Pod(n_racks=n_racks, chips_per_rack=cpr, tiles_per_server=4,
                fibers_per_server_pair=64, rails_per_rack_pair=1)
    sched.validate(tight, check_fibers=False)
    if m > 1:
        with pytest.raises(CircuitError):
            sched.validate(tight, check_fibers=True)


@given(st.sampled_from(INTRAS), st.sampled_from([1, 2, 4, 8, 16, 32, 64]),
       st.integers(2, 4), st.floats(1e3, 1e9))
@settings(max_examples=40, deadline=None)
def test_hierarchical_cost_decomposes_by_tier(intra, m, n_racks, n_bytes):
    """`compose_hierarchical` cost == Σ per-tier `Schedule.cost` terms
    (p up to 512 via the boundary draws: m=64 × R=4 plus the slow sweep),
    and the tier tags agree with the pod geometry."""
    if m * n_racks > 512:
        return
    cpr = 64
    chips = _pod_chips(n_racks, m, cpr)
    sched = hierarchical_schedule(chips, n_bytes, cpr, intra=intra)
    pod = Pod(n_racks=n_racks, chips_per_rack=cpr, fibers_per_server_pair=32)
    link = cm.LUMORPH_LINK
    tiers = sched.cost_by_tier(link, rack=pod)
    assert sched.cost(link, rack=pod) == pytest.approx(
        sum(tiers.values()), rel=1e-12)
    assert set(tiers) <= {0, 1} and 1 in tiers and tiers[1] > 0
    # tags vs geometry: a round is tagged inter iff it crosses racks
    for rnd in sched.rounds:
        crossing = any(s // cpr != d // cpr for s, d in rnd.pairs)
        assert (rnd.tier == 1) == crossing
    # flat schedules decompose consistently too
    flat = build_schedule(intra, chips, n_bytes)
    flat_tiers = flat.cost_by_tier(link, rack=pod)
    assert flat.cost(link, rack=pod) == pytest.approx(
        sum(flat_tiers.values()), rel=1e-12)


def test_hierarchical_single_rack_degenerates_to_flat():
    chips = tuple(range(8))
    sched = hierarchical_schedule(chips, 1e6, 64, intra="lumorph2")
    assert sched.algo == "lumorph2"
    assert sched.cost(cm.LUMORPH_LINK) == pytest.approx(
        build_schedule("lumorph2", chips, 1e6).cost(cm.LUMORPH_LINK))


def test_hierarchical_rejects_bad_compositions():
    with pytest.raises(ValueError):  # unequal shares
        hierarchical_schedule((0, 1, 2, 64), 1e6, 64)
    with pytest.raises(ValueError):  # tree cannot anchor a composition
        hierarchical_schedule(_pod_chips(2, 4, 64), 1e6, 64, intra="tree")
    with pytest.raises(ValueError):  # unknown inter stage
        compose_hierarchical(
            [build_schedule("ring", range(4), 1e6),
             build_schedule("ring", range(64, 68), 1e6)], inter="torus")
    with pytest.raises(ValueError):  # shared chips across racks
        compose_hierarchical([build_schedule("ring", (0, 1), 1e6),
                              build_schedule("ring", (1, 2), 1e6)])
    with pytest.raises(ValueError):  # structurally different racks
        compose_hierarchical([build_schedule("ring", (0, 1), 1e6),
                              build_schedule("lumorph2", (4, 5), 1e6)])


def test_hierarchical_beats_flat_ring_and_rhd_at_pod_scale():
    """The benchmark claim in miniature: at 512 chips over 4 racks the
    composed program is strictly cheaper than flat Ring and flat RHD,
    and at least matches the best flat algorithm."""
    pod = Pod(n_racks=4, chips_per_rack=128, fibers_per_server_pair=32)
    chips = tuple(range(512))
    link = cm.LUMORPH_LINK
    n = float(64 << 20)
    best_hier = min(hierarchical_schedule(chips, n, 128, intra=a)
                    .cost(link, rack=pod) for a in INTRAS)
    flat = {a: build_schedule(a, chips, n).cost(link, rack=pod)
            for a in ("ring", "lumorph2", "lumorph4")}
    assert best_hier < flat["ring"]
    assert best_hier < flat["lumorph2"]
    assert best_hier <= min(flat.values())


def test_candidate_algos_gates_on_equal_shares():
    algos = ("ring", "lumorph2", "lumorph4")
    flat_only = candidate_algos(algos, range(8), None)
    assert flat_only == algos
    equal = candidate_algos(algos, _pod_chips(2, 4, 64), 64)
    assert set(equal) == set(algos) | {f"hier:{a}" for a in algos}
    unequal = candidate_algos(algos, (0, 1, 2, 64), 64)
    assert unequal == algos
    assert "hier:tree" not in candidate_algos(("tree",), _pod_chips(2, 4, 64), 64)


def test_build_any_schedule_dispatches_hier():
    chips = _pod_chips(2, 4, 64)
    sched = build_any_schedule("hier:lumorph2", chips, 1e6, chips_per_rack=64)
    assert sched.algo == "hier:lumorph2:ring"
    with pytest.raises(ValueError):
        build_any_schedule("hier:lumorph2", chips, 1e6)  # no pod geometry


# ---------------------------------------------------------------------------
# compiled execution: the composed program is a real ALLREDUCE
# ---------------------------------------------------------------------------

SRC = str(Path(__file__).resolve().parents[1] / "src")

COMPILED_CHECK = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.core.collectives import compile_schedule
from repro.core.scheduler import hierarchical_schedule

rng = np.random.RandomState(11)
cases = [
    (8, (0, 1, 2, 3, 8, 9, 10, 11), "ring"),       # 2 racks x 4
    (8, (0, 1, 2, 3, 8, 9, 10, 11), "lumorph2"),
    (8, (0, 1, 2, 3, 8, 9, 10, 11), "lumorph4"),
    (8, (5, 3, 1, 7, 12, 14, 9, 15), "lumorph2"),  # scattered per-rack chips
    (6, (0, 1, 8, 9, 16, 17), "ring"),             # 3 racks x 2
]
for p, chips, intra in cases:
    mesh = jax.make_mesh((p,), ("d",), axis_types=(AxisType.Auto,))
    x = rng.randn(p, 37).astype(np.float32)
    expect = np.tile(x.sum(0, keepdims=True), (p, 1))
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("d", None)))
    sched = hierarchical_schedule(chips, 1e6, 8, intra=intra)
    f = jax.jit(jax.shard_map(
        lambda v: compile_schedule(sched, "d")(v[0])[None], mesh=mesh,
        in_specs=P("d", None), out_specs=P("d", None),
        axis_names={{"d"}}, check_vma=False))
    out = np.asarray(f(xs))
    assert np.allclose(out, expect, rtol=1e-5, atol=1e-5), (p, chips, intra)
print("SUBPROCESS_OK")
"""


@pytest.mark.slow
def test_compiled_hierarchical_matches_psum():
    """A composed hierarchical schedule executes to an exact ALLREDUCE on
    fake multi-device meshes (2×4, scattered chips, and 3×2 racks)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", COMPILED_CHECK.format(src=SRC)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# Pod resource model
# ---------------------------------------------------------------------------

def test_pod_addressing_and_defaults():
    pod = default_pod(n_racks=2, chips_per_rack=256)
    assert pod.n_chips == 512
    assert pod.rack_of(0) == 0 and pod.rack_of(511) == 1
    assert pod.server_of(257) == 32 + 0 and pod.tile_of(257) == 1
    assert pod.rails_per_rack_pair == 64  # cpr // 4


def test_pod_circuits_consume_rails():
    pod = Pod(n_racks=2, chips_per_rack=8, tiles_per_server=4,
              rails_per_rack_pair=1)
    c1 = pod.establish(0, 9)  # cross-rack
    assert c1.via_rail == 0
    with pytest.raises(CircuitError):
        pod.establish(1, 10)  # rail pool exhausted
    intra = pod.establish(1, 2)  # intra-rack unaffected
    assert intra.via_rail is None
    pod.teardown(c1)
    c2 = pod.establish(1, 10)  # rail freed
    assert c2.via_rail == 0
    pod.teardown(c2)
    pod.teardown(intra)
    assert not pod.live_circuits()


def test_pod_reconfigure_charges_rail_window_when_crossing():
    pod = Pod(n_racks=2, chips_per_rack=8, tiles_per_server=4)
    pod.reconfigure([(0, 1)])
    assert pod.reconfig_time == pytest.approx(cm.MZI_RECONFIG_DELAY)
    pod.reconfigure([(0, 9)])
    assert pod.reconfig_time == pytest.approx(
        cm.MZI_RECONFIG_DELAY + cm.RAIL_RECONFIG_DELAY)


def test_flat_crossing_rounds_priced_at_rail_link():
    """Any round with a rack-crossing circuit is governed by the slower
    rail link, so a flat schedule gets strictly more expensive when its
    chips are split across racks (same relative layout)."""
    link = cm.LUMORPH_LINK
    pod = Pod(n_racks=2, chips_per_rack=64, fibers_per_server_pair=32)
    one_rack = build_schedule("ring", tuple(range(16)), 1e7)
    split = build_schedule("ring", _pod_chips(2, 8, 64), 1e7)
    assert split.cost(link, rack=pod) > one_rack.cost(link, rack=pod)
    assert one_rack.cost_by_tier(link, rack=pod).keys() == {0}


# ---------------------------------------------------------------------------
# pod-aware allocation
# ---------------------------------------------------------------------------

def test_pod_allocator_rack_first_best_fit():
    a = PodAllocator(64, chips_per_rack=32, tiles_per_server=8)
    a.allocate("big", 20)  # lands in rack 0 (tie → lowest id)
    assert {c // 32 for c in a.allocations["big"].chips} == {0}
    # 12 free in rack 0, 32 in rack 1: best-fit sends a 10-wide tenant
    # to rack 0, preserving rack 1's hole for pod-scale tenants
    b = a.allocate("small", 10)
    assert {c // 32 for c in b.chips} == {0}
    # a tenant only rack 1 can hold goes there, zero crossings
    c = a.allocate("wide", 30)
    assert {x // 32 for x in c.chips} == {1}


def test_pod_allocator_equal_split_when_spanning():
    a = PodAllocator(64, chips_per_rack=32, tiles_per_server=8)
    alloc = a.allocate("span", 48)  # no rack holds 48: span 2, 24 each
    per_rack = {r: sum(1 for c in alloc.chips if c // 32 == r) for r in (0, 1)}
    assert per_rack == {0: 24, 1: 24}
    # equal shares ⇒ the hierarchical candidates are admissible
    assert any(x.startswith("hier:") for x in candidate_algos(
        ("ring",), alloc.chips, 32))


def test_pod_allocator_greedy_when_unequal():
    a = PodAllocator(64, chips_per_rack=32, tiles_per_server=8)
    a.allocate("seed", 8)  # rack 0 → 24 free there, 32 in rack 1
    alloc = a.allocate("span", 50)  # 25+25 impossible: greedy 32+18
    per_rack = {r: sum(1 for c in alloc.chips if c // 32 == r) for r in (0, 1)}
    assert per_rack == {1: 32, 0: 18}


def test_pod_allocator_confined_mode_rejects_spanning():
    a = PodAllocator(64, chips_per_rack=32, tiles_per_server=8,
                     span_racks=False)
    a.allocate("fits", 32)
    with pytest.raises(AllocationError):
        a.allocate("wide", 40)
    # conservation: the failed attempt must not leak chips
    assert len(a.free) == 32


def test_make_allocator_pod_kind():
    a = make_allocator("pod", 64, chips_per_rack=32)
    assert isinstance(a, PodAllocator)


def test_order_for_locality_groups_racks():
    chips = [0, 64, 1, 65, 2, 66, 3, 67]
    ordered = order_for_locality(chips, 8, chips_per_rack=64)
    assert ordered == [0, 1, 2, 3, 64, 65, 66, 67]
    # rack shares stay contiguous → hierarchical grouping is stable
    racks = [c // 64 for c in ordered]
    assert racks == sorted(racks)


# ---------------------------------------------------------------------------
# pod simulation
# ---------------------------------------------------------------------------

def _small_pod_trace(**kw):
    args = dict(n_chips=64, chips_per_rack=32, failure_rate=0.02, seed=3)
    args.update(kw)
    return pod_churn_trace(60, **args)


def test_pod_sim_deterministic_and_conserving():
    trace = _small_pod_trace()
    m1 = RackSimulator("lumorph", trace, n_chips=64, n_racks=2,
                       morph=True).run()
    m2 = RackSimulator("lumorph", trace, n_chips=64, n_racks=2,
                       morph=True).run()
    assert m1.summary() == m2.summary()
    assert m1.accepted + m1.rejected == m1.arrivals


def test_pod_sim_spanning_accepts_what_confinement_cannot():
    """Tenants wider than one rack are structurally rejected by the
    rack-confined baseline and always admissible under spanning (the
    pod-tier version of the Fig 2a fragmentation-free property)."""
    from repro.sim.workload import JobSpec, Trace

    trace = Trace((JobSpec("a", 0.0, 40, steps=2),
                   JobSpec("b", 100.0, 48, steps=2)))
    span = RackSimulator("lumorph", trace, n_chips=64, n_racks=2).run()
    confined = RackSimulator("lumorph", trace, n_chips=64, n_racks=2,
                             span_racks=False).run()
    assert span.acceptance_rate == 1.0
    assert confined.acceptance_rate == 0.0
    assert confined.fragmentation_rejects == 2  # chips were free pod-wide


def test_pod_sim_spanning_never_fragmentation_rejects():
    trace = _small_pod_trace()
    span = RackSimulator("lumorph", trace, n_chips=64, n_racks=2,
                         morph=True).run()
    assert span.fragmentation_rejects == 0


def test_pod_sim_requires_photonic_discipline():
    trace = _small_pod_trace()
    with pytest.raises(ValueError):
        RackSimulator("torus", trace, n_chips=64, n_racks=2)
    with pytest.raises(ValueError):
        RackSimulator("lumorph", trace, n_chips=63, n_racks=2)


def test_pod_sim_prices_spanning_tenants_hierarchically():
    """A tenant holding equal shares of two racks must be priced no worse
    than the flat candidates alone (the hier candidate can only help)."""
    from repro.sim.workload import JobSpec, Trace

    trace = Trace((JobSpec("span", 0.0, 64, steps=3),))
    sim = RackSimulator("lumorph", trace, n_chips=64, n_racks=2)
    m = sim.run()
    rec = m.tenants["span"]
    assert rec.completed and rec.steps_done == 3
    chips = tuple(order_for_locality(tuple(range(64)), 8, chips_per_rack=32))
    flat_best = min(sim._algo_cost(a, chips, trace.jobs[0].coll_bytes)
                    for a in ("ring", "lumorph2", "lumorph4"))
    priced = rec.collective_s / rec.steps_done
    assert priced <= flat_best * (1 + 1e-12)


def test_pod_morph_prefers_same_rack_compaction():
    from repro.morph import plan_compaction

    # tenant scattered across servers of rack 1, plenty free in rack 0:
    # the pod-aware planner compacts within rack 1 instead of migrating
    chips = [32, 36, 40, 44]  # one per server (tiles=4) in rack 1
    free = list(range(0, 32)) + [33, 34, 35, 37]
    plan = plan_compaction("t", chips, free, tiles_per_server=4,
                           state_bytes=1e6, chips_per_rack=32)
    assert plan is not None
    assert {c // 32 for c in plan.new_chips} == {1}, \
        "compaction must stay in the tenant's rack when possible"


def test_pod_compaction_escapes_full_rack():
    """When the tenant's majority rack has no room but another rack can
    host the whole slice, the planner proposes the rack-span-1 target —
    whether the cross-rack state moves pay off is the policy's pricing
    call, not the planner's."""
    from repro.morph import plan_compaction

    chips = [0, 1, 2, 33]  # 3 in rack 0 (rack 0 otherwise full), 1 in rack 1
    free = [34, 35, 36, 40]  # room only in rack 1
    plan = plan_compaction("t", chips, free, tiles_per_server=4,
                           state_bytes=1e6, chips_per_rack=32)
    assert plan is not None
    assert {c // 32 for c in plan.new_chips} == {1}


def test_morph_cost_charges_rail_window_when_spanning():
    """Re-establishing a rack-spanning slice's collective circuits goes
    through the rack-tier OCS, so the plan's final window is the rail
    reconfiguration delay, not the on-wafer MZI window."""
    from repro.morph import plan_bypass

    pod = Pod(n_racks=2, chips_per_rack=32, tiles_per_server=4)
    spanning = plan_bypass("t", [0, 1, 2, 3], dead=[0], free=[33],
                           tiles_per_server=4, state_bytes=1e6,
                           chips_per_rack=32)
    assert {c // 32 for c in spanning.new_chips} == {0, 1}
    assert spanning.cost(cm.LUMORPH_LINK, rack=pod).reestablish_s == \
        pytest.approx(cm.RAIL_RECONFIG_DELAY)
    local = plan_bypass("t", [0, 1, 2, 3], dead=[0], free=[4],
                        tiles_per_server=4, state_bytes=1e6,
                        chips_per_rack=32)
    assert local.cost(cm.LUMORPH_LINK, rack=pod).reestablish_s == \
        pytest.approx(cm.MZI_RECONFIG_DELAY)


def test_pod_confined_bypass_cannot_span_racks():
    """In a rack-confined pod, a failure bypass may not draw spares from
    another rack (that would silently violate the confinement invariant);
    the tenant falls through to the elastic shrink inside its own rack.
    The spanning pod, given the same trace, bypasses at full width."""
    from repro.sim.workload import FailureSpec, JobSpec, Trace

    trace = Trace((JobSpec("a", 0.0, 32, steps=20),
                   JobSpec("b", 1.0, 28, steps=20)),
                  (FailureSpec(5.0, (0, 1)),))
    confined = RackSimulator("lumorph", trace, n_chips=64, n_racks=2,
                             span_racks=False, morph=True)
    m = confined.run()
    # rack-1 spares are off limits: the bypass degenerates to keeping the
    # 30 survivors (still better than the elastic pow2 shrink to 16) and
    # the tenant stays entirely inside rack 0
    assert m.tenants["a"].shrunk_to == 30
    for a in confined.allocator.allocations.values():
        assert len({c // 32 for c in a.chips}) == 1
    spanning = RackSimulator("lumorph", trace, n_chips=64, n_racks=2,
                             morph=True).run()
    assert spanning.tenants["a"].bypassed >= 1
    assert spanning.tenants["a"].shrunk_to is None  # rack-1 spares used
