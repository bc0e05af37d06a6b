"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference on CPU.

Wall-times on CPU are NOT the TPU story (interpret mode runs the kernel
body in Python); the 'derived' column therefore reports the structural
metric that matters for the TPU target: VMEM working set per grid step and
arithmetic intensity — plus an allclose check against the oracle.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


def _time(f, *args, iters=3):
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # µs


def run() -> list[str]:
    lines = ["name,us_per_call,derived"]
    rng = jax.random.PRNGKey(0)

    # flash attention: prefill-ish tile
    b, s, h, kv, d = 1, 512, 8, 2, 64
    q = jax.random.normal(rng, (b, s, h, d), jnp.float32)
    k = jax.random.normal(rng, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(rng, (b, s, kv, d), jnp.float32)
    t_ref = _time(lambda q, k, v: ref.reference_attention(
        q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        k.transpose(0, 2, 1, 3).reshape(b * kv, s, d),
        v.transpose(0, 2, 1, 3).reshape(b * kv, s, d)), q, k, v)
    blk = min(fa.BLOCK, s)  # the kernel's q and kv block at this length
    vmem_kb = (blk * d * 3 + blk * blk) * 4 / 1024  # q,k,v tiles + scores
    flops_per_byte = (2 * blk * blk * d * 2) / ((blk * d * 3 + blk * d) * 4)
    lines.append(f"bench_kernels/flash_attention/ref_jnp,{t_ref:.0f},")
    lines.append(f"bench_kernels/flash_attention/vmem_per_step_kb,,{vmem_kb:.0f}")
    lines.append(f"bench_kernels/flash_attention/arith_intensity,,{flops_per_byte:.1f}")
    out = ops.flash_attention(q, k, v, causal=True)
    expect = ref.reference_attention(
        q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        k.transpose(0, 2, 1, 3).reshape(b * kv, s, d),
        v.transpose(0, 2, 1, 3).reshape(b * kv, s, d)).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    ok = bool(jnp.abs(out - expect).max() < 2e-5)
    lines.append(f"bench_kernels/flash_attention/allclose,,{'PASS' if ok else 'FAIL'}")

    # rmsnorm
    x = jax.random.normal(rng, (256, 2048), jnp.float32)
    w = jnp.zeros((2048,))
    t_ref = _time(lambda x, w: ref.reference_rmsnorm(x, w), x, w)
    lines.append(f"bench_kernels/rmsnorm/ref_jnp,{t_ref:.0f},")
    ok = bool(jnp.abs(ops.fused_rmsnorm(x, w) - ref.reference_rmsnorm(x, w)).max() < 1e-5)
    lines.append(f"bench_kernels/rmsnorm/allclose,,{'PASS' if ok else 'FAIL'}")
    lines.append("bench_kernels/rmsnorm/hbm_passes,,1 (vs 2 unfused)")

    # int8 quant
    g = jax.random.normal(rng, (1 << 20,), jnp.float32)
    t_ref = _time(lambda g: ref.reference_quantize_int8(g), g)
    lines.append(f"bench_kernels/quant_int8/ref_jnp,{t_ref:.0f},")
    q8, sc = ops.quantize_int8(g)
    qr, sr = ref.reference_quantize_int8(g)
    ok = bool(jnp.array_equal(q8[:qr.shape[0]], qr))
    lines.append(f"bench_kernels/quant_int8/allclose,,{'PASS' if ok else 'FAIL'}")
    lines.append("bench_kernels/quant_int8/wire_compression,,3.76x (int8+1/64 scales vs fp32)")
    return lines
