"""Optimizer + gradient-communication machinery."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from hypothesis import given, settings, strategies as st

from repro.optim import grad_comm
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state, lr_at


def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0, total_steps=200)
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    state = init_opt_state(params)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        params, state = adamw_update(params, g, state, cfg)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target), atol=1e-2)


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110, min_lr_ratio=0.1)
    assert float(lr_at(cfg, jnp.int32(0))) == pytest.approx(0.0)
    assert float(lr_at(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(lr_at(cfg, jnp.int32(110))) == pytest.approx(0.1, abs=1e-6)
    # monotone decay after warmup
    vals = [float(lr_at(cfg, jnp.int32(s))) for s in range(10, 111, 10)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


def test_grad_clip_applies():
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0,
                      warmup_steps=0, total_steps=10, eps=0.0, b1=0.0, b2=0.0)
    params = {"w": jnp.zeros(4)}
    state = init_opt_state(params)
    g = {"w": jnp.full(4, 100.0)}  # norm 200 → clipped 1.0
    p2, _ = adamw_update(params, g, state, cfg)
    # with b1=b2=0, update = lr·g_clipped/|g_clipped| elementwise = lr·sign
    assert float(jnp.abs(p2["w"]).max()) <= 1.0 + 1e-6


@given(st.integers(min_value=1, max_value=10_000_000),
       st.integers(min_value=1, max_value=1 << 26))
@settings(max_examples=100, deadline=None)
def test_bucketing_partition(total, bucket_bytes):
    buckets = grad_comm.make_buckets(total, bucket_bytes)
    # exact contiguous partition of [0, total)
    assert buckets[0].start == 0 and buckets[-1].end == total
    for a, b in zip(buckets, buckets[1:]):
        assert a.end == b.start
    target = max(1, bucket_bytes // 4)
    for b in buckets[:-1]:
        assert b.n_elems == target  # uniform except the tail


def test_quantize_error_bound():
    x = jnp.asarray(np.random.RandomState(0).randn(4096).astype(np.float32)) * 10
    q, s = grad_comm.quantize_int8(x)
    deq = grad_comm.dequantize_int8(q, s, 4096)
    per_block_max = jnp.abs(x.reshape(-1, 256)).max(axis=1)
    bound = per_block_max / 254 + 1e-6
    err = jnp.abs(deq - x).reshape(-1, 256).max(axis=1)
    assert bool(jnp.all(err <= bound))


def test_error_feedback_removes_bias():
    """EF property: accumulated compensated quantization tracks the true sum
    far better than naive quantization (bias → 0)."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(512).astype(np.float32) * 1e-3)
    steps = 50
    ef = jnp.zeros_like(x)
    acc_ef = jnp.zeros_like(x)
    acc_naive = jnp.zeros_like(x)
    for _ in range(steps):
        comp = x + ef
        q, s = grad_comm.quantize_int8(comp)
        deq = grad_comm.dequantize_int8(q, s, x.shape[0])
        ef = comp - deq
        acc_ef += deq
        qn, sn = grad_comm.quantize_int8(x)
        acc_naive += grad_comm.dequantize_int8(qn, sn, x.shape[0])
    true = x * steps
    err_ef = float(jnp.abs(acc_ef - true).max())
    err_naive = float(jnp.abs(acc_naive - true).max())
    assert err_ef <= err_naive * 0.9 + 1e-12


def test_all_reduce_grads_single_axis_identity():
    """On a 1-device mesh the bucketed LUMORPH allreduce must be exact."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    grads = {"a": jnp.arange(8.0), "b": jnp.ones((3, 3))}

    def body(g):
        out, _, log = grad_comm.all_reduce_grads(g, ("data",), algo="auto", mean=True)
        return out

    specs = jax.tree.map(lambda _: jax.sharding.PartitionSpec(), grads)
    f = jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(specs,),
                                 out_specs=specs,
                                 axis_names={"data"}, check_vma=False))
    out = f(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(grads[k]), rtol=1e-6)


def test_auto_selection_regimes():
    from repro.core.cost_model import LUMORPH_LINK, algorithm_cost, select_algorithm
    # small buffers: α-dominated → log-round algorithms
    assert select_algorithm(4 * 1024, 256, LUMORPH_LINK) in ("lumorph2", "lumorph4")
    # huge buffers: all three are β-parity (telescoping) — whatever auto
    # picks must be within 1% of the best candidate
    n = 8 << 30
    picked = algorithm_cost(select_algorithm(n, 256, LUMORPH_LINK), n, 256, LUMORPH_LINK)
    best = min(algorithm_cost(a, n, 256, LUMORPH_LINK)
               for a in ("ring", "lumorph2", "lumorph4"))
    assert picked <= best * 1.01
