"""The ``deepseek`` float32 reference against the program, at a smoke size
on the CPU: the cell's configuration with small widths, one chip's share of
the router's experts held (4 of 8), YaRN and the latent norm kept; the same
weights (drawn by the benchmark from the seed), the program run in float32
compute, logits and gradients compared."""

import json
import math
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "chipbench"))

import flops_mla_moe  # noqa: E402
from reference import deepseek  # noqa: E402
from reference.common import F32, FP8, init_tree, layout_shapes, seed_key  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

CONFIG = json.loads((ROOT / "chipbench" / "configs" / "deepseek-v2-lite-ep8.json").read_text())
SMOKE = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
             block_pattern=["mla_dense", "mla_moe", "mla_moe"], mla_kv_lora_rank=32,
             mla_qk_nope_dim=16, mla_qk_rope_dim=8, mla_v_dim=16, moe_experts=4,
             moe_router_experts=8, moe_top_k=3, moe_d_ff=32)


def smoke(**over):
    m = dict(CONFIG["model"], **SMOKE, **over)
    return m, ModelConfig(name="deepseek-smoke", **dict(m, compute_dtype="float32"))


def tokens(vocab, b=2, s=32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (b, s)), jnp.int32)


def test_layout_is_the_programs():
    m, cfg = smoke()
    want = jax.tree.map(lambda s: tuple(s.shape), tf.param_shapes(cfg))
    assert layout_shapes(deepseek.layout(m)) == want


def test_cell_layout_is_the_programs():
    """At the cell's own widths, by shapes alone (nothing is allocated)."""
    m = CONFIG["model"]
    cfg = ModelConfig(name=CONFIG["name"], **m)
    want = jax.tree.map(lambda s: tuple(s.shape), tf.param_shapes(cfg))
    assert layout_shapes(deepseek.layout(m)) == want
    assert [(k, n) for k, n in deepseek.runs(m["block_pattern"])] == [("mla_dense", 1),
                                                                      ("mla_moe", 5)]


def test_logits_match_program():
    m, cfg = smoke()
    params = init_tree(deepseek.layout(m), seed_key(3))
    toks = tokens(m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog, _ = tf.forward_logits(params, {"tokens": toks}, cfg)
        ref = deepseek.logits(params, toks, m, F32, q_block=8)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_gradients_match_program(norm_topk):
    """Loss (with the balance loss, weighted up so that it shows) and every
    gradient leaf, including the router's through the gates and the loss."""
    m, cfg = smoke(norm_topk_prob=norm_topk, aux_loss_weight=0.1)
    params = init_tree(deepseek.layout(m), seed_key(2**31 + 5))
    toks = tokens(m["vocab_size"], seed=1)
    n = toks.shape[0] * (toks.shape[1] - 1)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: tf.loss_fn(p, {"tokens": toks}, cfg))(params)
        lr, gr = jax.value_and_grad(lambda p: deepseek.loss_sum(p, toks, m, F32, 16) / n)(params)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-9)


def test_balance_loss_is_in_the_loss():
    """The reference's loss moves with ``aux_loss_weight`` by (S−1)·α·Σ of
    the rows' balance losses, each near 1 for an even router."""
    m, _ = smoke()
    params = init_tree(deepseek.layout(m), seed_key(4))
    toks = tokens(m["vocab_size"], b=1)
    a = deepseek.loss_sum(params, toks, dict(m, aux_loss_weight=0.0), F32, 16)
    b = deepseek.loss_sum(params, toks, dict(m, aux_loss_weight=1.0), F32, 16)
    per_layer = float(b - a) / (toks.shape[1] - 1) / 2  # two MoE layers
    assert 0.3 < per_layer < 3.0


def test_yarn_frequencies_and_scale_as_published():
    """DeepSeek-V2-Lite's YaRN over its 64 rope dims: pairs below dim 10
    keep RoPE's frequency, pairs from 23 take it ÷ 40, a linear ramp
    between; softmax scale (0.1·0.707·ln 40 + 1)² / √192."""
    m = CONFIG["model"]
    inv = np.asarray(deepseek.yarn_inv_freq(m))
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, base / 40 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    scale = (0.1 * 0.707 * math.log(40) + 1) ** 2 / math.sqrt(192)
    assert deepseek.softmax_scale(m) == pytest.approx(scale, rel=1e-12)
    assert scale == pytest.approx(0.1147, abs=1e-4)


def test_control_is_lower_precision():
    m, _ = smoke()
    params = init_tree(deepseek.layout(m), seed_key(1))
    toks = tokens(m["vocab_size"])
    ref = deepseek.logits(params, toks, m, F32, 16)
    ctl = deepseek.logits(params, toks, m, FP8, 16)
    assert float(jnp.max(jnp.abs(ctl - ref)) / jnp.max(jnp.abs(ref))) > 1e-2


@pytest.mark.parametrize("layers,gflop", [(6, 2.151), (5, 1.862)])
def test_flops_per_token(layers, gflop):
    """6 × (MLA projections, dense MLP, shared experts, router, 0.75 of a
    routed expert per MoE layer, head slice) + 6·L·H·(192+128)·2048.5."""
    m = dict(CONFIG["model"], n_layers=layers,
             block_pattern=CONFIG["model"]["block_pattern"][:layers])
    got = flops_mla_moe.train_flops_per_token(m, 4096)
    mla = 2048 * 16 * 192 + 2048 * 512 + 2048 * 64 + 512 * 16 * 256 + 16 * 128 * 2048
    moe = 0.75 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64
    hand = (6 * (layers * mla + 3 * 2048 * 10944 + (layers - 1) * moe + 12800 * 2048)
            + 6 * layers * 16 * 320 * 2048.5)
    assert got == pytest.approx(hand, rel=1e-12)
    assert got / 1e9 == pytest.approx(gflop, abs=5e-4)


def test_mfu_reader():
    import run

    ctx = {"trace": None, "counts": {"steps": 10, "tokens": 81920, "window_s": 4.0},
           "model": CONFIG["model"], "traffic": {"kind": "train", "seq_len": 4096},
           "chips": 1, "peak": {"bf16_flops_per_s": 197e12}, "flops": None}
    got = run.load_metric("train_step_mfu.mla_moe").read(ctx)
    want = 100 * flops_mla_moe.train_flops_per_token(CONFIG["model"], 4096) * 20480 / 197e12
    assert got == pytest.approx(want) and 0 < got < 105


def test_reference_imports_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, 'chipbench');"
            "import reference.deepseek, flops_mla_moe;"
            "bad = [k for k in sys.modules if k.split('.')[0] == 'repro'];"
            "print(bad); sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                       text=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.fixture
def smoke_cell(tmp_path):
    """A checkout with a smoke-size copy of the deepseek cell (float32
    compute), made of new files and new ``BENCHMARK.json`` entries only."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    like, name = "deepseek-v2-lite-ep8.train.b2x4096", "smoke.deepseek"
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == like)
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = dict(CONFIG, name=name + "-cfg",
                  model=dict(CONFIG["model"], **SMOKE, compute_dtype="float32"))
    cfile = f"chipbench/configs/{name}-cfg.json"
    (tmp_path / cfile).write_text(json.dumps(config))
    traffic = json.loads((tmp_path / "chipbench/traffic" / f"{like}.json").read_text())
    traffic.update(global_batch=2, seq_len=32)
    # float32 compute: the program's gaps are round-off, far under these
    traffic["check"].update(q_block=16, limits=dict.fromkeys(
        ("loss_gap", "grad_gap", "change_gap"), 1e-3))
    (tmp_path / "chipbench/traffic" / f"{name}.json").write_text(json.dumps(traffic))
    bench["configs"].append(dict(conf, name=name + "-cfg", file=cfile))
    bench["workloads"].append(dict(wl, name=name, config=name + "-cfg"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path, name


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_cell_runs_and_checks_on_the_cpu(smoke_cell, monkeypatch, fault):
    """The harness runs the cell's smoke copy through the program's train
    step and reads ``correct`` against the reference; leaving half of the
    batch out makes it false."""
    import run
    from repro.launch import steps

    root, cell = smoke_cell
    if fault:
        real = steps.make_train_step

        def broken(cfg, policy, *a, **kw):
            step = real(cfg, policy, *a, **kw)
            return lambda p, o, b: step(p, o, {"tokens": b["tokens"][:1]})

        monkeypatch.setattr(steps, "make_train_step", broken)
    args = run.parse(["--workload", cell, "--seed", str(2**33 + 7), "--seconds", "0.5",
                      "--trace", "0"])
    out = run.measure(args, root=root, require_tpu=False, compile_cache=False)
    assert out["correct"] is (fault is None), out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
