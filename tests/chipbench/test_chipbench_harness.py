"""The benchmark harness on the CPU: it refuses to run without a TPU, finds
a cell made of new files alone, and reports ``correct`` false when the
timed path is broken underneath."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "chipbench"))

import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SMOKE_SIZES = {
    "bert-large": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                       d_ff=128, vocab_size=256),
    "h2o-danube-1.8b-pp4": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                                head_dim=16, d_ff=128, vocab_size=256, sliding_window=16),
}
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def add_smoke_cell(root: Path, like: str, name: str, chips: int = 1,
                   optimizer: dict | None = None) -> str:
    """Add a cell shaped like ``like`` at smoke size, by new files and new
    ``BENCHMARK.json`` entries only; ``optimizer`` overrides its settings."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == like)
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((root / conf["file"]).read_text())
    config["name"] = name + "-cfg"
    config["model"].update(SMOKE_SIZES[wl["config"]])
    cfile = f"chipbench/configs/{name}-cfg.json"
    (root / cfile).write_text(json.dumps(config))
    traffic = json.loads((root / "chipbench/traffic" / f"{like}.json").read_text())
    traffic.update(global_batch=4 * chips, seq_len=32)
    traffic["check"].update(row_block=2, q_block=16)
    traffic["optimizer"].update(optimizer or {})
    (root / "chipbench/traffic" / f"{name}.json").write_text(json.dumps(traffic))
    bench["configs"].append(dict(conf, name=name + "-cfg", file=cfile))
    bench["workloads"].append(dict(wl, name=name, config=name + "-cfg", chips=chips))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


@pytest.fixture
def tree(tmp_path):
    """A checkout holding the benchmark and the program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path


def measure(root, cell, seed=5):
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
                      "--trace", "0"])
    return run.measure(args, root=root, require_tpu=False, compile_cache=False)


def test_no_tpu_exits_nonzero():
    cell = BENCH["workloads"][0]["name"]
    r = subprocess.run([sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
                        "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=CPU_ENV,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    cell = BENCH["workloads"][0]["name"]
    r = subprocess.run([sys.executable, *BENCH["command"][1:], "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       env=CPU_ENV, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.BenchError):
        run.peak_of("TPU v0 imaginary", require=True)
    assert run.peak_of("TPU v5 lite", require=True)["bf16_flops_per_s"] == 197e12


def test_new_cell_from_new_files_alone(tree):
    before = {p: p.read_bytes() for p in (tree / "chipbench").rglob("*") if p.is_file()}
    cell = add_smoke_cell(tree, "bert-large.train.b8x512", "smoke.train")
    assert all(p.read_bytes() == b for p, b in before.items())
    assert cell in run.list_cells(tree)
    out = measure(tree, cell)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-2:] == ["checks", "_where"]  # _where is dropped before printing
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
@pytest.mark.parametrize("like", ["bert-large.train.b8x512",
                                  "h2o-danube-1.8b-pp4.train.s4096"])
def test_broken_step_is_not_correct(tree, monkeypatch, fault, like):
    from repro.launch import steps
    from repro.models import transformer as tf

    real = steps.make_train_step

    def broken(cfg, policy, *a, **kw):
        step = real(cfg, policy, *a, **kw)
        if fault == "frozen":  # returns its state unchanged
            import jax
            return jax.jit(lambda p, o, b: (p, o, tf.loss_fn(p, b, cfg)))
        # half of the batch left out, the mean taken over the rest
        return lambda p, o, b: step(p, o, {"tokens": b["tokens"][: b["tokens"].shape[0] // 2]})

    monkeypatch.setattr(steps, "make_train_step", broken)
    out = measure(tree, add_smoke_cell(tree, like, "smoke.broken"))
    assert out["correct"] is False, out["checks"]


_DP4 = """
import sys, json
from pathlib import Path
root = Path(sys.argv[1]); sys.path.insert(0, str(root / 'chipbench'))
import run
from repro.optim import grad_comm
real = grad_comm.all_reduce_grads
variants = {
    'sound': real,
    'broken': lambda grads, *a, **k: (grads, None, []),  # exchange left out
    'summed': lambda grads, *a, **k: real(grads, *a, **dict(k, mean=False)),
}
args = run.parse(['--workload', sys.argv[2], '--seed', '9', '--seconds', '0.5', '--trace', '0'])
out = {}
for mode in sys.argv[3].split(','):
    grad_comm.all_reduce_grads = variants[mode]
    o = run.measure(args, root=root, require_tpu=False, compile_cache=False)
    out[mode] = {'correct': o['correct'], 'checks': o['checks']}
print(json.dumps(out))
"""


def run_dp4(tree, modes: str, optimizer: dict | None = None) -> dict:
    """Run a 2x2 smoke cell on four CPU devices once per mode; the
    exchange is sound, left out, or summed instead of averaged."""
    cell = add_smoke_cell(tree, "bert-large.train.dp4.lumorph4", "smoke.dp4", chips=4,
                          optimizer=optimizer)
    env = dict(CPU_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", _DP4, str(tree), cell, modes],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode,want", [("sound", True), ("broken", False)])
def test_exchange_left_out_is_not_correct(tree, mode, want):
    out = run_dp4(tree, mode)[mode]
    assert out["correct"] is want, out


@pytest.mark.parametrize("clip", ["on", "off"])
def test_gradient_summed_over_replicas(tree, clip):
    """An exchange that sums the replicas' gradients instead of averaging
    them (x4 on the 2x2).  With the cells' global-norm clipping, and a
    norm over the clip, the clipped gradient, Adam's moments and every
    update come out the same, so no reading can tell and none should;
    with clipping off the first gradient is four times the reference's."""
    out = run_dp4(tree, "sound,summed", {} if clip == "on" else {"grad_clip": 1e9})
    assert out["sound"]["correct"] is True, out
    if clip == "on":
        for k, c in out["sound"]["checks"].items():
            assert out["summed"]["checks"][k]["value"] == pytest.approx(c["value"], rel=1e-6), out
    else:
        assert out["summed"]["correct"] is False, out
        assert out["summed"]["checks"]["grad_gap"]["value"] > 1.0, out


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_follows_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in cells.values())
    for name, w in cells.items():
        assert NAME.match(name) and w["chips"] in (1, 4)
        assert (ROOT / "chipbench" / "traffic" / f"{name}.json").is_file()
        reported = run.end_to_end_names(BENCH, name)
        assert "setup_s" in reported and len(reported) >= 2
        assert run.per_layer_entries(BENCH, name)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        for cell in m["workloads"]:
            assert m["moves"] in run.end_to_end_names(BENCH, cell)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


def test_metric_readers_read_nothing_without_a_trace():
    ctx = {"trace": None, "counts": {"steps": 3, "tokens": 30, "window_s": 1.0},
           "model": {}, "traffic": {"kind": "serve"}, "chips": 1, "peak": None,
           "flops": None}
    for m in BENCH["per_layer"]:
        assert run.load_metric(m["name"]).read(ctx) is None
