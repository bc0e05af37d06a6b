"""The benchmark's float32 references against the program, at smoke sizes
on the CPU: same weights (drawn by the benchmark from the seed), the
program run in float32 compute, logits and gradients compared."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "chipbench"))

from reference import bert, danube  # noqa: E402
from reference.common import F32, FP8, init_tree, layout_shapes, seed_key  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

SMOKE = {
    "bert": (bert, "bert-large", dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                                      head_dim=16, d_ff=128, vocab_size=256)),
    "danube": (danube, "h2o-danube-1.8b-pp4",
               dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                    d_ff=128, vocab_size=256, sliding_window=16)),
}


def smoke(family):
    mod, name, sizes = SMOKE[family]
    m = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())["model"]
    m = dict(m, **sizes)
    cfg = ModelConfig(name=family + "-smoke", **dict(m, compute_dtype="float32"))
    return mod, m, cfg


def tokens(vocab, b=2, s=32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (b, s)), jnp.int32)


@pytest.mark.parametrize("family", ["bert", "danube"])
def test_layout_is_the_programs(family):
    mod, m, cfg = smoke(family)
    want = jax.tree.map(lambda s: tuple(s.shape), tf.param_shapes(cfg))
    assert layout_shapes(mod.layout(m)) == want


@pytest.mark.parametrize("family", ["bert", "danube"])
def test_logits_match_program(family):
    mod, m, cfg = smoke(family)
    params = init_tree(mod.layout(m), seed_key(3))
    toks = tokens(m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog, _ = tf.forward_logits(params, {"tokens": toks}, cfg)
        ref = mod.logits(params, toks, m, F32, q_block=8)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("family", ["bert", "danube"])
def test_gradients_match_program(family):
    mod, m, cfg = smoke(family)
    params = init_tree(mod.layout(m), seed_key(2**31 + 5))
    toks = tokens(m["vocab_size"], seed=1)
    n = toks.shape[0] * (toks.shape[1] - 1)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: tf.loss_fn(p, {"tokens": toks}, cfg))(params)
        lr, gr = jax.value_and_grad(lambda p: mod.loss_sum(p, toks, m, F32, 16) / n)(params)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-9)


def test_window_masks_far_keys():
    """With the window shorter than the sequence, the danube reference's
    logit at the last position does not depend on the first token."""
    mod, m, _ = smoke("danube")
    m = dict(m, n_layers=1, sliding_window=4)
    params = init_tree(mod.layout(m), seed_key(0))
    a = tokens(m["vocab_size"], b=1, s=12)
    b = a.at[0, 0].set((a[0, 0] + 1) % m["vocab_size"])
    la, lb = (mod.logits(params, t, m, F32, q_block=4)[0, -1] for t in (a, b))
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=0, atol=0)


def test_control_is_lower_precision():
    """The float8 control departs from the float32 reference by more than
    bfloat16 rounding would."""
    mod, m, _ = smoke("bert")
    params = init_tree(mod.layout(m), seed_key(1))
    toks = tokens(m["vocab_size"])
    ref = mod.logits(params, toks, m, F32, 16)
    ctl = mod.logits(params, toks, m, FP8, 16)
    rel = float(jnp.max(jnp.abs(ctl - ref)) / jnp.max(jnp.abs(ref)))
    assert rel > 1e-2


def test_same_seed_same_weights_large_seeds_differ():
    mod, m, _ = smoke("bert")
    a = init_tree(mod.layout(m), seed_key(7))["embed"]
    b = init_tree(mod.layout(m), seed_key(7))["embed"]
    c = init_tree(mod.layout(m), seed_key(7 + 2**32))["embed"]
    assert bool(jnp.all(a == b)) and not bool(jnp.all(a == c))


def test_references_import_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, 'chipbench');"
            "import reference.bert, reference.danube;"
            "bad = [k for k in sys.modules if k.split('.')[0] == 'repro'];"
            "print(bad); sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                       text=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
