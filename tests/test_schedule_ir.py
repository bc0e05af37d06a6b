"""Schedule IR: the one-source-of-truth contract.

Pins the three derivations of a Schedule against each other:

  * **pricing** — ``Schedule.cost`` == the legacy closed-form α–β formulas
    (demoted to cross-checks) for every algorithm × p ∈ {2..64} × sizes,
    and ``algorithm_cost`` delegates to the IR;
  * **execution** — every builder's transfer lowering is well-formed
    (perms are partial permutations that tile the round's circuit pairs,
    chunk ids in range), and compiled schedules reproduce ``lax.psum``
    (multi-device, in a subprocess) — including noncontiguous
    participants and the tree builder;
  * **reconfigurations** — per-algorithm MZI window counts match the
    paper's analysis (Ring=1, RHD=2·log2 p −1, LUMORPH-4=2·L−1,
    tree=2·⌈log2 p⌉);
  * **fabric pricing** — fiber time-sharing charges scattered placements
    more than locality-ordered ones and never discounts.
"""

import math
import os
import subprocess
import sys
from pathlib import Path

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cost_model as cm
from repro.core.fabric import LumorphRack
from repro.core.scheduler import (SCHEDULE_BUILDERS, build_schedule,
                                  order_for_locality, tree_schedule)

ALGOS = tuple(sorted(SCHEDULE_BUILDERS))


def _closed_form(algo: str, n: float, p: int, link: cm.LinkModel) -> float:
    if algo == "lumorph2" and p & (p - 1):
        algo = "ring"  # paper §3 fallback, mirrored by the rhd builder
    return cm.ALGORITHMS[algo](n, p, link)


@given(st.sampled_from(ALGOS), st.integers(2, 64), st.floats(1e2, 1e10),
       st.sampled_from([cm.LUMORPH_LINK, cm.IDEAL_SWITCH, cm.TPU_LINK]))
@settings(max_examples=200, deadline=None)
def test_ir_cost_equals_closed_form(algo, p, n, link):
    sched = build_schedule(algo, tuple(range(p)), n)
    assert sched.cost(link) == pytest.approx(_closed_form(algo, n, p, link),
                                             rel=1e-9), (algo, p, n)


@given(st.sampled_from(ALGOS), st.integers(1, 64), st.floats(1e2, 1e10))
@settings(max_examples=100, deadline=None)
def test_algorithm_cost_delegates_to_ir(algo, p, n):
    link = cm.LUMORPH_LINK
    sched = build_schedule("ring" if algo == "lumorph2" and p & (p - 1) else algo,
                           tuple(range(p)), n)
    assert cm.algorithm_cost(algo, n, p, link) == pytest.approx(
        sched.cost(link), rel=1e-12)


@pytest.mark.parametrize("p", [2, 4, 8, 16, 32, 64])
def test_reconfiguration_counts_match_paper(p):
    n = 1e6
    assert build_schedule("ring", range(p), n).reconfigurations() == 1
    assert build_schedule("lumorph2", range(p), n).reconfigurations() == \
        2 * int(math.log2(p)) - 1
    radices = cm.mixed_radix_factorization(p, 4)
    assert build_schedule("lumorph4", range(p), n).reconfigurations() == \
        2 * len(radices) - 1
    assert build_schedule("tree", range(p), n).reconfigurations() == \
        2 * math.ceil(math.log2(p))


@given(st.sampled_from(ALGOS), st.integers(1, 24))
@settings(max_examples=80, deadline=None)
def test_transfer_lowering_is_well_formed(algo, p):
    """Each round's transfers: partial permutations whose union is exactly
    the round's circuit pairs; chunk tables rank-complete and in range.
    (Transfer tables are lazy — materialize() is the execution-side step
    that builds them; pricing never calls it.)"""
    chips = tuple(range(100, 100 + p))  # noncontiguous chip ids
    sched = build_schedule(algo, chips, 1e6).materialize()
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


def test_tree_handles_non_powers_of_two():
    for p in (2, 3, 5, 6, 7, 12):
        sched = tree_schedule(tuple(range(p)), 1e6)
        assert len(sched.rounds) == 2 * math.ceil(math.log2(p))
        participants = {c for r in sched.rounds for pair in r.pairs for c in pair}
        assert participants == set(range(p))


def test_fiber_timesharing_never_discounts():
    link = cm.LUMORPH_LINK
    rack = LumorphRack(n_servers=4, tiles_per_server=8,
                       fibers_per_server_pair=16)
    for algo in ALGOS:
        sched = build_schedule(algo, tuple(range(32)), 1e6)
        assert sched.cost(link, rack=rack) >= sched.cost(link), algo


def test_fiber_timesharing_prices_placement():
    """A scattered 16-chip tenant pays fiber time-sharing that the
    locality-ordered placement of the same chips avoids (or reduces)."""
    link = cm.LUMORPH_LINK
    rack = LumorphRack(n_servers=4, tiles_per_server=8,
                       fibers_per_server_pair=16)
    # pathological order: adjacent ranks alternate servers
    scattered = tuple(range(0, 32, 4)) + tuple(range(1, 32, 4))
    interleaved = tuple(x for pair in zip(scattered[:8], scattered[8:])
                        for x in pair)
    ordered = tuple(order_for_locality(interleaved, 8))
    bad = build_schedule("lumorph2", interleaved, 1e7).cost(link, rack=rack)
    good = build_schedule("lumorph2", ordered, 1e7).cost(link, rack=rack)
    assert good <= bad
    # intra-server schedules never touch fibers: rack pricing is exact
    intra = build_schedule("lumorph2", tuple(range(8)), 1e7)
    assert intra.cost(link, rack=rack) == pytest.approx(intra.cost(link))


SRC = str(Path(__file__).resolve().parents[1] / "src")

COMPILED_CHECK = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.core.collectives import compile_schedule
from repro.core.scheduler import build_schedule

p = 6
mesh = jax.make_mesh((p,), ("d",), axis_types=(AxisType.Auto,))
rng = np.random.RandomState(7)
x = rng.randn(p, 23).astype(np.float32)
expect = np.tile(x.sum(0, keepdims=True), (p, 1))
xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("d", None)))
chips = (3, 11, 4, 40, 25, 17)  # scattered tenant: rank i plays chips[i]
for algo in ("ring", "lumorph2", "lumorph4", "tree"):
    sched = build_schedule(algo, chips, 1e6)
    f = jax.jit(jax.shard_map(
        lambda v: compile_schedule(sched, "d")(v[0])[None], mesh=mesh,
        in_specs=P("d", None), out_specs=P("d", None),
        axis_names={{"d"}}, check_vma=False))
    out = np.asarray(f(xs))
    assert np.allclose(out, expect, rtol=1e-5, atol=1e-5), algo
print("SUBPROCESS_OK")
"""


@pytest.mark.slow
def test_compiled_schedules_match_psum_noncontiguous():
    """compile_schedule on schedules built over *noncontiguous* chips (the
    sim's case) still computes an exact ALLREDUCE at non-power-of-two p."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", COMPILED_CHECK.format(src=SRC)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
