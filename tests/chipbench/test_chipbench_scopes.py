"""The program's device-side scopes and ``chipbench/scope_reduce.py``,
which reads them: the train steps of the smoke configurations, lowered on
the CPU, put every op under the scope that names its layer, through
``value_and_grad``, ``jax.checkpoint``, ``lax.scan`` and ``shard_map``; and
the reduction attributes op time to scopes as a brute-force sweep does."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "chipbench"))

import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

DATA = Path(__file__).parent / "data"
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

_LOC_NAME = re.compile(r'^(#loc\d*) = loc\("((?:[^"\\]|\\.)*)"', re.M)
_OP = re.compile(r"= \"?(?:stablehlo|mhlo)\.([a-z_]+)\b[^\n]*loc\((#loc\d*)\)")

# Lowers a step on four virtual CPU devices and writes its text to argv[1].
_LUMORPH4 = """
import sys
import jax, jax.numpy as jnp
from repro.configs import bert_large
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.sharding.policy import make_policy
cfg = bert_large.smoke_config()
policy = make_policy(cfg, make_host_mesh(data=4, model=1))
step = steps.make_train_step(cfg, policy, comm="lumorph4", bucket_bytes=int(sys.argv[2]))
ps = tf.param_shapes(cfg)
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
low = step.lower(ps, steps.opt_shapes(cfg, ps), batch)
open(sys.argv[1], "w").write(low.as_text(debug_info=True))
"""


def lowered_ops(text: str) -> list[tuple[str, str]]:
    """(op, op_name) of every op of a lowered module's text whose location
    carries a name."""
    names = dict(_LOC_NAME.findall(text))
    return [(op, names[loc]) for op, loc in _OP.findall(text) if loc in names]


def step_lowered(cfg):
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.sharding.policy import make_policy

    step = steps.make_train_step(cfg, make_policy(cfg, make_host_mesh(data=1, model=1)))
    ps = tf.param_shapes(cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    return step.lower(ps, steps.opt_shapes(cfg, ps), batch)


def check_layers(ops: list[tuple[str, str]], n_leaves: int):
    """Every matrix product under a layer that a metric reads; AdamW's
    square roots (one per leaf and the global norm's) and its schedule's
    cosine under ``optimizer`` (RoPE's cosines sit under ``attention``); and
    an op under ``embed``."""
    dots = [scope_reduce.scope_of(n) for op, n in ops if op == "dot_general"]
    assert dots and set(dots) <= {"attention", "mlp", "head"}, set(dots)
    assert {"attention", "mlp", "head"} <= set(dots)
    sqrts = [scope_reduce.scope_of(n) for op, n in ops if op == "sqrt"]
    assert sqrts == ["optimizer"] * (n_leaves + 1), sqrts
    assert ("cosine", "optimizer") in {(op, scope_reduce.scope_of(n)) for op, n in ops}
    assert "embed" in {scope_reduce.scope_of(n) for _, n in ops}


@pytest.mark.parametrize("family", ["bert_large", "h2o_danube_1_8b"])
def test_one_chip_step_scopes(family):
    import importlib

    from repro.models import transformer as tf

    cfg = importlib.import_module(f"repro.configs.{family}").smoke_config()
    n_leaves = len(jax.tree.leaves(tf.param_shapes(cfg)))
    lowered = step_lowered(cfg)
    check_layers(lowered_ops(lowered.as_text(debug_info=True)), n_leaves)
    # The compiled program's op names are those a device trace carries: a
    # called function's ops there hold the whole path, the backward's
    # ``transpose(...)`` included.
    compiled = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    seen = {(scope_reduce.scope_of(n), scope_reduce.direction_of(n)) for n in compiled}
    want = {(s, d) for s in ("embed", "attention", "mlp", "head") for d in ("fwd", "bwd")}
    assert want <= seen, want - seen


def test_lumorph4_step_scopes(tmp_path):
    """On a 2x2 of virtual devices: every permute of the exchange sits
    under the ``grad_comm/bucket<i>`` of its bucket, every bucket has
    its permutes, and pack and unpack hold the rest of the exchange."""
    from repro.configs import bert_large
    from repro.models import transformer as tf
    from repro.optim.grad_comm import make_buckets

    bucket_bytes = 64 * 1024
    out = tmp_path / "lowered.txt"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", _LUMORPH4, str(out), str(bucket_bytes)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    ops = lowered_ops(out.read_text())
    shapes = tf.param_shapes(bert_large.smoke_config())
    n_buckets = len(make_buckets(sum(x.size for x in jax.tree.leaves(shapes)), bucket_bytes))
    assert n_buckets > 1
    permutes = [scope_reduce.scope_of(n) for op, n in ops if op == "collective_permute"]
    assert permutes and all(re.fullmatch(r"grad_comm/bucket\d+", s) for s in permutes)
    assert set(permutes) == {f"grad_comm/bucket{i}" for i in range(n_buckets)}
    scopes = {scope_reduce.scope_of(n) for _, n in ops}
    assert {"grad_comm/pack", "grad_comm/unpack"} <= scopes
    check_layers(ops, len(jax.tree.leaves(shapes)))


@pytest.mark.parametrize("op_name,scope,direction", [
    ("jit(step)/jvp()/while/body/closed_call/block/attention/bsd,dhk->bshk/dot_general",
     "attention", "fwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/"
     "block/mlp/dot_general", "mlp", "bwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/block/add", "block", "bwd"),
    ("jit(step)/transpose(jvp(head))/bsd,vd->bsv/dot_general", "head", "bwd"),
    ("jit(step)/jvp(head)/jit(log_softmax)/reduce_max", "head", "fwd"),
    ("jit(step)/transpose(jvp(embed))/scatter-add", "embed", "bwd"),
    ("jit(step)/jvp()/while/body/closed_call/block/moe/gather", "moe", "fwd"),
    ("jit(step)/optimizer/jit(clip)/max", "optimizer", "fwd"),
    ("jit(step)/shard_map/grad_comm/bucket12/jit(_take)/select_n", "grad_comm/bucket12", "fwd"),
    ("jit(step)/shard_map/grad_comm/pack/concatenate", "grad_comm/pack", "fwd"),
    ("jit(step)/shard_map/grad_comm/unpack/div", "grad_comm/unpack", "fwd"),
    ("jit(step)/shard_map/grad_comm/reshape", "grad_comm", "fwd"),
    ("jit(step)/pack/bucket1/concatenate", "none", "fwd"),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice", "none", "bwd"),
    ("jit(step)/blocks/mlp2/dot_general", "none", "fwd"),
    ("", "none", "fwd"),
])
def test_scope_of(op_name, scope, direction):
    """The innermost program scope on an op's name stack, through the
    ``jvp(``/``transpose(`` wrappers and ``checkpoint`` parts JAX writes."""
    assert scope_reduce.scope_of(op_name) == scope
    assert scope_reduce.direction_of(op_name) == direction


# One chip's op line with scopes beside it (window 0 .. 1000 ns); a second
# chip runs one op of the head.  The loop has no scope entry.
HAND_SCOPED = {
    "devices": {
        "/device:TPU:0": [
            ["while.1", 0, 600],
            ["fusion.1", 0, 100],
            ["fusion.2", 100, 150],
            ["collective-permute-start.1", 250, 10],
            ["fusion.3", 260, 140],
            ["collective-permute-done.1", 400, 50],
            ["fusion.4", 450, 50],
            ["copy.1", 600, 50],
            ["fusion.5", 900, 200],
        ],
        "/device:TPU:1": [["fusion.9", 0, 200]],
    },
    "scoped": {
        "/device:TPU:0": [
            ["attention", "fwd", 0, 100],
            ["mlp", "bwd", 100, 150],
            ["grad_comm/bucket0", "fwd", 250, 10],
            ["grad_comm/bucket0", "fwd", 260, 140],
            ["grad_comm/bucket0", "fwd", 400, 50],
            ["grad_comm/pack", "fwd", 450, 50],
            ["none", "bwd", 600, 50],
            ["optimizer", "fwd", 900, 200],
        ],
        "/device:TPU:1": [["head", "bwd", 0, 200]],
    },
    "host": [["chipbench.window", 0, 1000]],
}


def test_scope_times_by_hand():
    # TPU:0: attention 100, mlp 150, bucket0 10 + 140 + 50, pack 50, none
    # 50, optimizer 900-1000 (clipped) 100; TPU:1: head 200.  Halved.
    # The exchange's compute: fusion.3 140 + fusion.4 50, not the permute.
    r = scope_reduce.reduce(HAND_SCOPED)
    want = {"attention": 100, "mlp": 150, "grad_comm/bucket0": 200, "grad_comm/pack": 50,
            "none": 50, "optimizer": 100, "head": 200}
    assert r["scope_s"] == pytest.approx({k: v / 2 * 1e-9 for k, v in want.items()})
    assert r["scope_dir_s"]["mlp"] == pytest.approx({"fwd": 0.0, "bwd": 75e-9})
    assert r["scope_dir_s"]["head"] == pytest.approx({"fwd": 0.0, "bwd": 100e-9})
    assert r["scope_dir_s"]["attention"] == pytest.approx({"fwd": 50e-9, "bwd": 0.0})
    assert r["grad_comm_compute_s"] == pytest.approx(190 / 2 * 1e-9)
    # the container and the ops add up to the busy time
    assert sum(r["scope_s"].values()) == pytest.approx((500 + 50 + 100 + 200) / 2 * 1e-9)


def test_scopes_out_of_step_with_the_op_line():
    rec = json.loads(json.dumps(HAND_SCOPED))
    rec["scoped"]["/device:TPU:0"].pop(3)
    with pytest.raises(ValueError):
        scope_reduce.reduce(rec)


def test_record_without_scopes_reads_no_scope():
    r = scope_reduce.reduce({k: v for k, v in HAND_SCOPED.items() if k != "scoped"})
    assert r["scope_s"] == {} and r["scope_dir_s"] == {} and r["grad_comm_compute_s"] == 0.0


# What the benchmark's own reduction reads on each recorded slice, the
# scoped one included: the numbers the collective and idle metrics divide.
READ_BEFORE_SCOPES = {
    "a_step_boundary.json": {"window_s": 0.006, "busy_s": 0.003064353,
                             "collective_s": 0.0, "collective_exposed_s": 0.0},
    "d_allreduce_rounds.json": {"window_s": 0.0014, "busy_s": 0.0013947375,
                                "collective_s": 0.00121207,
                                "collective_exposed_s": 8.687e-06},
    "d_scoped_exchange.json": {"window_s": 0.001, "busy_s": 0.0009998895,
                               "collective_s": 0.0009169185,
                               "collective_exposed_s": 4.1705e-06},
}


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_slice_reads_as_before_scopes(name):
    got = trace_reduce.reduce(json.loads((DATA / name).read_text()))
    for k, v in READ_BEFORE_SCOPES[name].items():
        assert got[k] == pytest.approx(v, rel=1e-12, abs=1e-18), k


def _scope_sweep(rec: dict) -> tuple[dict, float]:
    """Time by scope, and the exchange's compute, by brute force: every
    stretch between two op boundaries goes to the scope of each
    non-container op that covers its middle."""
    (_, w0, wd), = [h for h in rec["host"] if h[0] == trace_reduce.WINDOW]
    w1 = w0 + wd
    scopes: dict[str, float] = {}
    compute = 0.0
    for plane, events in rec["devices"].items():
        ops = [e for e in events if trace_reduce.op_kind(e[0]) not in trace_reduce.CONTAINERS]
        tagged = [(sc[0], n, s, s + d) for (n, s, d), sc in zip(ops, rec["scoped"][plane])]
        cuts = sorted({w0, w1} | {min(max(t, w0), w1) for _, _, s, e in tagged for t in (s, e)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            for scope, n, s, e in tagged:
                if s <= mid < e:
                    scopes[scope] = scopes.get(scope, 0.0) + (b - a)
                    if (scope.startswith("grad_comm")
                            and trace_reduce.op_kind(n) not in trace_reduce.COLLECTIVES):
                        compute += b - a
    n = len(rec["devices"])
    return {k: v * 1e-9 / n for k, v in scopes.items()}, compute * 1e-9 / n


SCOPED = sorted(p.name for p in DATA.glob("*.json") if "scoped" in json.loads(p.read_text()))


@pytest.mark.parametrize("name", SCOPED)
def test_recorded_scopes_match_a_sweep(name):
    """A slice recorded on a TPU v5e with the program's scopes: time by
    scope and the exchange's own compute agree with the brute-force sweep,
    and the scopes' time is the non-container time of the op line."""
    rec = json.loads((DATA / name).read_text())
    got = scope_reduce.reduce(rec)
    scopes, compute = _scope_sweep(rec)
    assert got["scope_s"] == pytest.approx(scopes, rel=1e-9, abs=1e-15)
    assert got["grad_comm_compute_s"] == pytest.approx(compute, rel=1e-9, abs=1e-15)
    for k, split in got["scope_dir_s"].items():
        assert sum(split.values()) == pytest.approx(got["scope_s"][k], rel=1e-9)
    assert sum(got["scope_s"].values()) <= trace_reduce.reduce(rec)["busy_s"] * (1 + 1e-9)
    assert 0 < got["grad_comm_compute_s"] < sum(
        v for k, v in got["scope_s"].items() if k.startswith("grad_comm"))


def _pb(*fields) -> bytes:
    """A protobuf message from ``(field number, value)`` pairs: an int as a
    varint, ``str``/``bytes`` length-delimited."""
    def varint(n: int) -> bytes:
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _plane(name: str, line: str, t0_ns: int, stat_names: dict, events: list) -> bytes:
    """An ``XPlane`` with one line; ``events`` are ``(event name, offset ps,
    duration ps, [(stat name, str or ("ref", stat name))])``."""
    ids = {n: i + 1 for i, n in enumerate(stat_names)}
    metas, evs = [], []
    for i, (ev_name, off, dur, stats) in enumerate(events, start=100):
        xstats = [_pb((1, ids[k]), (7, ids[v[1]]) if isinstance(v, tuple) else (5, v))
                  for k, v in stats]
        metas.append(_pb((1, i), (2, _pb((1, i), (2, ev_name), *[(5, s) for s in xstats]))))
        evs.append(_pb((1, i), (2, off), (3, dur)))
    return _pb((2, name), (3, _pb((1, 1), (2, line), (3, t0_ns), *[(4, e) for e in evs])),
               *[(4, m) for m in metas],
               *[(5, _pb((1, i), (2, _pb((1, i), (2, n))))) for n, i in ids.items()])


def test_load_reads_op_names_from_event_metadata(tmp_path):
    """A TPU trace written by hand: each op's ``tf_op`` (inline, or as a
    reference to a stat name) gives its scope; loops get none."""
    attn = "jit(step)/jvp()/while/body/closed_call/block/attention/dot_general:"
    tpu = _plane("/device:TPU:0", "XLA Ops", 1000, ["tf_op", "flops", attn], [
        ("%while.2 = (s32[]) while(%t)", 0, 900_000, []),
        ("%fusion.1 = bf16[8]{0} fusion(%p)", 0, 400_000, [("tf_op", ("ref", attn))]),
        ("%fusion.3 = f32[4]{0} fusion(%q)", 400_000, 300_000,
         [("flops", "7"), ("tf_op", "jit(step)/transpose(jvp(head))/dot_general:")]),
        ("%copy.4 = f32[4]{0} copy(%r)", 700_000, 200_000, []),
    ])
    host = _plane("/host:CPU", "python", 1000, [], [("chipbench.window", 0, 1_000_000, [])])
    path = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_pb((1, tpu), (1, host)))
    assert scope_reduce.op_names(path.read_bytes()) == {"/device:TPU:0": {
        "%while.2 = (s32[]) while(%t)": "", "%fusion.1 = bf16[8]{0} fusion(%p)": attn,
        "%fusion.3 = f32[4]{0} fusion(%q)": "jit(step)/transpose(jvp(head))/dot_general:",
        "%copy.4 = f32[4]{0} copy(%r)": ""}}
    rec = scope_reduce.load(str(tmp_path))
    assert rec["devices"]["/device:TPU:0"][1] == ["fusion.1 bf16[8]", 1000, 400]
    assert rec["scoped"] == {"/device:TPU:0": [["attention", "fwd", 1000, 400],
                                               ["head", "bwd", 1400, 300],
                                               ["none", "fwd", 1700, 200]]}
    assert rec["host"] == [["chipbench.window", 1000, 1000]]
    assert {k: v for k, v in rec.items() if k != "scoped"} == trace_reduce.load(str(tmp_path))
    r = scope_reduce.reduce(rec)
    assert r["scope_s"] == pytest.approx({"attention": 400e-9, "head": 300e-9, "none": 200e-9})


def test_cli_without_tpu_exits_nonzero():
    r = subprocess.run([sys.executable, "chipbench/scope_reduce.py", "--workload",
                        "bert-large.train.b8x512", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=300)
    assert r.returncode == 3
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
