"""The benchmark's trace reduction, on a small recorded trace whose numbers
are worked out by hand in the comments."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "chipbench"))

import trace_reduce  # noqa: E402

DATA = Path(__file__).parent / "data"

# A hand-worked record shaped like a TPU op line: ops one after another,
# a ``while`` container beside the ops of its body, asynchronous
# collectives as a short ``-start`` and a waiting ``-done`` with other ops
# between them.  Window span 500 .. 10000 ns.
HAND = {
    "devices": {
        "/device:TPU:0": [
            ["copy.9", 100, 400],
            ["while.5", 1000, 4000],
            ["fusion.1 bf16[8,512,1024]", 1000, 1000],
            ["collective-permute-start.4", 2000, 100],
            ["fusion.2", 2100, 900],
            ["collective-permute-done.4 bf16[1024]", 3000, 500],
            ["fusion.1 bf16[8,512,1024]", 3600, 1400],
            ["all-reduce-start.3", 5500, 100],
            ["fusion.3", 5600, 400],
            ["all-reduce-done.3 f32[8]", 6500, 500],
            ["fusion.1 bf16[8,512,1024]", 7000, 500],
        ],
        "/device:TPU:1": [
            ["collective-permute-start.8", 200, 50],
            ["collective-permute-done.8", 600, 300],
            ["fusion.1 bf16[8,512,1024]", 1000, 2000],
            ["all-gather-start.7", 3000, 100],
            ["fusion.1 bf16[8,512,1024]", 3100, 900],
            ["all-gather-done.7", 4000, 500],
            ["fusion.1 bf16[8,512,1024]", 9000, 2000],
        ],
    },
    "host": [
        ["chipbench.window", 500, 9500],
        ["chipbench.data", 500, 500],
        ["chipbench.step", 1000, 6000],
        ["chipbench.loss_read", 7000, 3000],
        ["other.span", 0, 20000],
    ],
}


@pytest.fixture
def reduced():
    return trace_reduce.reduce(HAND)


def test_window_and_busy(reduced):
    # TPU:0 in the window: while 1000-5000 covers its body (the idle
    # 3500-3600 inside it counts busy), 5500-6000, 6500-7500 → 5500;
    # copy.9 ends at 500, outside.  TPU:1: done 600-900, 1000-4500,
    # 9000-10000 (clipped) → 4800.
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(9500e-9)
    assert reduced["busy_s"] == pytest.approx((5500 + 4800) / 2 * 1e-9)


def test_collectives_and_exposed(reduced):
    # TPU:0 in flight: permute 2000-3500, all-reduce 5500-7000 → 3000.
    # Exposed: the permute less fusion.2 (2100-3000) → 600; the all-reduce
    # less fusion.3 (5600-6000) → 1100, the wait 6000-6500 with it; the
    # while around the permute hides nothing → 1700.
    # TPU:1: permute started before the window, 500-900 → 400, nothing
    # beside it; all-gather 3000-4500 less fusion.1 3100-4000 → 600 of
    # 1500 → collective 1900, exposed 1000.
    assert reduced["collective_s"] == pytest.approx((3000 + 1900) / 2 * 1e-9)
    assert reduced["collective_exposed_s"] == pytest.approx((1700 + 1000) / 2 * 1e-9)


def test_device_ops_ranked(reduced):
    ops = dict(reduced["device_ops"])
    # fusion.1: TPU:0 1000 + 1400 + 500, TPU:1 2000 + 900 + 1000 → 6800 / 2.
    assert ops["fusion.1 bf16[8,512,1024]"] == pytest.approx(3400e-9)
    assert ops["while.5"] == pytest.approx(2000e-9)
    assert reduced["device_ops"][0][0] == "fusion.1 bf16[8,512,1024]"
    assert len(reduced["device_ops"]) <= 10


def test_idle_gaps_by_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    # TPU:0 gaps: 500-1000 (data), 5000-5500 and 6000-6500 (step),
    # 7500-10000 (loss_read).  TPU:1: 500-600 and 900-1000 (data),
    # 4500-9000: step overlaps 4500-7000 (2500) more than loss_read
    # 7000-9000 (2000) → step.
    assert gaps["chipbench.data"] == pytest.approx((500 + 200) / 2 * 1e-9)
    assert gaps["chipbench.step"] == pytest.approx((1000 + 4500) / 2 * 1e-9)
    assert gaps["chipbench.loss_read"] == pytest.approx(2500 / 2 * 1e-9)
    idle = sum(gaps.values())
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_host_spans_counted(reduced):
    assert reduced["spans"]["chipbench.step"] == [1, pytest.approx(6000e-9)]
    assert "chipbench.window" not in reduced["spans"]


def test_collective_intervals_pair_in_order():
    # a loop body's collective repeats under one name: each start pairs
    # with the next done; a start left open ends at its own end.
    ev = [["collective-permute-start.2", 0, 10], ["fusion.1", 10, 50],
          ["collective-permute-done.2", 60, 5], ["collective-permute-start.2", 70, 10],
          ["collective-permute-done.2", 90, 10], ["all-reduce.1", 120, 30],
          ["all-gather-start", 160, 5]]
    assert trace_reduce.collective_intervals(ev) == [(0, 65), (70, 100), (120, 150),
                                                     (160, 165)]


def test_nothing_to_read():
    assert trace_reduce.reduce({"devices": {}, "host": []}) is None


@pytest.mark.parametrize("name,kind", [
    ("all-reduce-start.12", "all-reduce"), ("collective-permute-done.3", "collective-permute"),
    ("all-gather.1", "all-gather"), ("fusion.3", "fusion"), ("reduce-scatter", "reduce-scatter"),
])
def test_op_kind(name, kind):
    assert trace_reduce.op_kind(name) == kind


def test_subtract_intervals():
    a = [[0, 10], [20, 30]]
    b = [[2, 4], [8, 22], [25, 26]]
    assert trace_reduce._subtract(a, b) == [[0, 2], [4, 8], [22, 25], [26, 30]]


def test_load_keeps_the_harness_spans(tmp_path):
    """A trace taken here (CPU, no TPU plane) loads to a record with the
    harness's host spans and no device ops."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path / "t"), profiler_options=trace_reduce.options()):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            jnp.ones((64, 64)).sum().block_until_ready()
    rec = trace_reduce.load(str(tmp_path / "t"))
    assert [n for n, _, _ in rec["host"]] == ["chipbench.window"]
    assert rec["devices"] == {}
    assert trace_reduce.reduce(rec) is None


def test_short_name():
    long = "%fusion.544 = bf16[8,512,4096]{2,1,0:T(8,128)(2,1)} fusion(bf16[8] %p)"
    assert trace_reduce.short_name(long) == "fusion.544 bf16[8,512,4096]"
    assert trace_reduce.short_name("%while.9 = (s32[], bf16[8]) while(%t)") == "while.9"
    assert trace_reduce.short_name("fusion.1") == "fusion.1"


def _sweep(rec: dict) -> dict:
    """Busy, in-flight and exposed time by brute force: every stretch
    between two event boundaries, classified by what covers its middle."""
    (_, w0, wd), = [h for h in rec["host"] if h[0] == trace_reduce.WINDOW]
    w1 = w0 + wd
    busy = coll = exposed = 0.0
    for events in rec["devices"].values():
        ops = [(s, s + d) for _, s, d in events]
        hiding = [(s, s + d) for n, s, d in events if trace_reduce.op_kind(n)
                  not in trace_reduce.COLLECTIVES + trace_reduce.CONTAINERS]
        flying = trace_reduce.collective_intervals(events)
        cuts = sorted({w0, w1} | {min(max(t, w0), w1) for iv in ops + flying for t in iv})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            covered = lambda ivs: any(s <= mid < e for s, e in ivs)
            busy += (b - a) * covered(ops)
            if covered(flying):
                coll += b - a
                exposed += (b - a) * (not covered(hiding))
    n = len(rec["devices"])
    return {"busy_s": busy * 1e-9 / n, "collective_s": coll * 1e-9 / n,
            "collective_exposed_s": exposed * 1e-9 / n}


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_slice_matches_a_sweep(name):
    """Slices of traces recorded on a TPU v5e through ``load``: the
    reduction agrees with a brute-force sweep of the same record."""
    rec = json.loads((DATA / name).read_text())
    got, want = trace_reduce.reduce(rec), _sweep(rec)
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-9, abs=1e-15), k
    assert got["busy_s"] <= got["window_s"]
    assert got["collective_exposed_s"] <= got["collective_s"]
