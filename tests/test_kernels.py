"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


def _mkqkv(key, b, sq, skv, h, kv, d, dv, dt):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dt)
    k = jax.random.normal(ks[1], (b, skv, kv, d), dt)
    v = jax.random.normal(ks[2], (b, skv, kv, dv), dt)
    return q, k, v


def _ref_bshd(q, k, v, causal, window, scale):
    b, sq, h, d = q.shape
    _, skv, kv, dv = v.shape
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kv, skv, dv)
    out = ref.reference_attention(qr, kr, vr, causal=causal, window=window,
                                  scale=scale)
    return out.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)


ATTN_CASES = [
    # (b, sq, skv, h, kv, d, dv, causal, window, scale, dtype, block)
    (2, 128, 128, 4, 4, 64, 64, True, None, None, jnp.float32, None),
    (1, 256, 256, 8, 2, 64, 64, True, None, None, jnp.bfloat16, None),  # GQA 4:1, bf16
    (2, 100, 100, 4, 1, 32, 32, True, 48, None, jnp.float32, None),     # MQA + SWA + ragged
    (1, 64, 192, 2, 2, 128, 128, False, None, None, jnp.float32, None),  # bidirectional/cross
    (1, 160, 160, 2, 2, 80, 80, True, None, None, jnp.float32, None),    # danube head_dim=80
    (1, 96, 96, 3, 3, 64, 64, True, 17, None, jnp.bfloat16, None),       # odd heads + window
    (1, 200, 200, 4, 4, 192, 128, True, None, 0.1147, jnp.bfloat16, None),  # MLA: qk 192, v 128
    (1, 100, 300, 2, 1, 64, 64, False, None, None, jnp.float32, None),   # cross, keys padded
    (1, 320, 320, 4, 2, 64, 64, True, 100, None, jnp.float32, 128),      # 3×3 blocks, window
    (1, 384, 384, 2, 2, 80, 80, True, None, None, jnp.bfloat16, 128),    # 3×3 blocks, causal
]


@pytest.mark.parametrize("b,sq,skv,h,kv,d,dv,causal,window,scale,dt,block",
                         ATTN_CASES)
def test_flash_attention(rng, monkeypatch, b, sq, skv, h, kv, d, dv, causal,
                         window, scale, dt, block):
    """The kernel's output and its gradients with respect to q, k and v
    against the jnp oracle's; ``block`` shrinks the kernel's blocks so that
    short sequences span several, dead ones skipped."""
    if block:
        monkeypatch.setattr(fa, "BLOCK", block)
    q, k, v = _mkqkv(rng, b, sq, skv, h, kv, d, dv, dt)
    ct = jax.random.normal(jax.random.fold_in(rng, 1), (b, sq, h, dv), jnp.float32)

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * ct)

    kernel = lambda q, k, v: ops.flash_attention(q, k, v, causal=causal,
                                                 window=window, scale=scale)
    oracle = lambda q, k, v: _ref_bshd(q, k, v, causal, window, scale)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    out, expect = kernel(q, k, v), oracle(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect.astype(jnp.float32))))
    assert err < tol, err
    grads = jax.grad(functools.partial(loss, kernel), (0, 1, 2))(q, k, v)
    expects = jax.grad(functools.partial(loss, oracle), (0, 1, 2))(q, k, v)
    for name, g, e in zip("qkv", grads, expects):
        g, e = g.astype(jnp.float32), e.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(g - e)) / jnp.max(jnp.abs(e)))
        assert rel < tol, (name, rel)


@pytest.mark.parametrize("shape,dt", [
    ((4, 37, 512), jnp.float32),
    ((2, 130, 768), jnp.bfloat16),
    ((1, 1, 2048), jnp.float32),    # decode row
    ((512, 64), jnp.float32),       # 2-D input
])
def test_rmsnorm(rng, shape, dt):
    x = jax.random.normal(rng, shape, dt)
    w = jax.random.normal(jax.random.fold_in(rng, 1), (shape[-1],), jnp.float32) * 0.2
    out = ops.fused_rmsnorm(x, w)
    expect = ref.reference_rmsnorm(x, w)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 expect.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("n", [256, 1000, 65536, 12345])
def test_quant_roundtrip(rng, n):
    x = jax.random.normal(rng, (n,), jnp.float32) * 5
    q, s = ops.quantize_int8(x)
    qr, sr = ref.reference_quantize_int8(x)
    assert jnp.array_equal(q[:len(qr)], qr)
    assert jnp.allclose(s, sr)
    deq = ops.dequantize_int8(q, s, n)
    # per-block max error ≤ scale/2 = blockmax/254
    xf = jnp.pad(x, (0, (-n) % 256)).reshape(-1, 256)
    bound = (jnp.abs(xf).max(axis=1) / 254 + 1e-6)[:, None]
    err = jnp.abs(deq - x)
    errb = jnp.pad(err, (0, (-n) % 256)).reshape(-1, 256)
    assert bool(jnp.all(errb <= bound + 1e-7))


def _kernel_on_model_path(monkeypatch):
    """The models take the flash kernel as they do on TPU, with the kernel
    in the Pallas interpreter."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: True)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "deepseek-v2-lite-16b"])
def test_attention_matches_model_path(rng, monkeypatch, arch):
    """The model through the flash kernel agrees with its jnp attention
    (the path off TPU): logits, and the loss's gradient."""
    from repro.configs import get_smoke_config
    from repro.models import forward_logits, init_params, loss_fn
    cfg = get_smoke_config(arch).replace(compute_dtype="float32")
    params = init_params(rng, cfg)
    batch = {"tokens": jax.random.randint(rng, (2, 32), 0, cfg.vocab_size)}

    def run():
        logits, _ = forward_logits(params, batch, cfg)
        return logits, jax.grad(lambda p: loss_fn(p, batch, cfg))(params)

    base, base_g = run()
    _kernel_on_model_path(monkeypatch)
    flash, flash_g = run()
    err = float(jnp.abs(base - flash).max() / (jnp.abs(base).max() + 1e-9))
    assert err < 1e-4, err
    rel = jax.tree.map(lambda g, e: float(jnp.abs(g - e).max() / (jnp.abs(e).max() + 1e-9)),
                       flash_g, base_g)
    assert max(jax.tree.leaves(rel)) < 1e-3, rel
