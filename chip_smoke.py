"""Smoke test of the main path on TPU: does the program start and compute
the right thing on the chip?

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip: train, serve, kernels
    python chip_smoke.py --four-chips  # a 2x2 host: collectives, dp=4 train

One chip (the default) drives, at BERT-large's published widths:

* training through ``repro.launch.train.main`` with ``--comm xla`` and
  ``--comm lumorph4``: every loss finite, final losses within ``LOSS_REL``;
* serving through ``repro.launch.serve.main``: finite logits, the
  expected generated shape;
* every Pallas kernel through ``repro.kernels.ops``, compiled for the chip
  (``tpu_custom_call`` in the program) and compared with its
  ``repro.kernels.ref`` oracle.

``--four-chips`` runs only what exists across chips: the LUMORPH
all-reduces (monolithic and overlapped) against ``lax.psum`` with the
result on four distinct devices, and data-parallel training on four chips
with ``lumorph4`` against ``xla``.

Everything runs in this one process: a chip belongs to one process at a
time.  A backend other than TPU is a failure, never a fallback.  Each
phase raises when a check fails, so the script exits nonzero.  The times it
prints come from one cold run (the first train step includes compiling)
and are smoke output, not benchmark metrics.  The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    raise SystemExit(f"chip_smoke: {ROOT} is not a checkout of this repository "
                     "(no src/repro next to the script)")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.collectives import (make_all_reduce,  # noqa: E402
                                    make_overlapped_all_reduce)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.serve import metrics as serve_metrics  # noqa: E402

SEED = 0
#: final-loss agreement of two gradient backends over a bf16 wire (the
#: tolerance ``tests/test_train_integration.py`` holds LUMORPH-4 to)
LOSS_REL = 2e-2
#: max |kernel - oracle| / max |oracle| for bf16 outputs
#: (``tests/test_kernels.py`` bf16 tolerance)
BF16_REL = 2e-2
#: f32 all-reduce vs psum: summation order is the only difference
F32_REL = 1e-5

#: (name, (batch, seq, q heads, kv heads, qk head_dim, v head_dim),
#: sliding window, softmax scale or None for 1/√qk head_dim)
ATTN_CASES = (
    ("bert-large", (8, 512, 16, 16, 64, 64), None, None),
    ("h2o-danube-1.8b gqa+swa", (1, 4096, 32, 8, 80, 80), 1024, None),
    ("deepseek-v2-lite mla", (2, 4096, 16, 16, 192, 128), None, 0.1147),
)
RMSNORM_SHAPE = (4096, 2560)  # danube d_model
QUANT_N = 1 << 24  # a 64 MB fp32 gradient bucket
COLLECTIVE_BYTES = (256 << 10, 32 << 20)  # per rank: α regime, β regime
COLLECTIVE_ALGOS = ("ring", "lumorph2", "lumorph4")
OVERLAP_CHUNKS = 4


def check(ok: bool, what: str) -> None:
    """Raise (and so exit nonzero) when a smoke check fails."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_phase(count: int) -> jax.Device:
    """The run's first device; exit unless JAX sees ``count`` TPU chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); refusing to run elsewhere")
    if len(devs) != count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chip(s), found "
                         f"{len(devs)}")
    say(f"device {devs[0].device_kind} x{len(devs)}")
    return devs[0]


def train_phase(*, batch: int, seq: int, steps: int, data_parallel: int = 0,
                arch: str = "bert-large", smoke: bool = False,
                comms: tuple[str, ...] = ("xla", "lumorph4")) -> dict:
    """``repro.launch.train.main`` once per gradient backend; returns the
    results keyed by backend.  ``comms[0]`` is the reference."""
    argv = ["--arch", arch, "--batch", str(batch), "--seq", str(seq),
            "--steps", str(steps), "--seed", str(SEED),
            "--log-every", str(steps)]
    if data_parallel:
        argv += ["--data-parallel", str(data_parallel)]
    if smoke:
        argv.append("--smoke")
    runs = {}
    for comm in comms:
        r = runs[comm] = train.main(argv + ["--comm", comm])
        check(len(r["losses"]) == steps, f"train {comm}: {len(r['losses'])} "
              f"of {steps} steps ran")
        check(all(math.isfinite(x) for x in r["losses"]),
              f"train {comm}: non-finite loss in {r['losses']}")
        check(r["param_devices"] == jax.device_count(),
              f"train {comm}: parameters on {r['param_devices']} of "
              f"{jax.device_count()} devices")
        say(f"train {arch} comm={comm} dp={data_parallel or jax.device_count()} "
            f"batch={batch} seq={seq}: losses={r['losses']} "
            f"step_s={r['step_s']} (step 0 includes compile)")
    base = runs[comms[0]]["final_loss"]
    for comm in comms[1:]:
        rel = abs(runs[comm]["final_loss"] - base) / abs(base)
        check(rel <= LOSS_REL, f"train {comm} final loss "
              f"{runs[comm]['final_loss']} vs {comms[0]} {base}: rel {rel}")
        say(f"train final loss {comm} vs {comms[0]}: rel diff {rel}")
    return runs


def serve_phase(*, batch: int, prompt_len: int, gen: int,
                arch: str = "bert-large", smoke: bool = False) -> dict:
    """``repro.launch.serve.main``: prefill then greedy decode."""
    argv = ["--arch", arch, "--batch", str(batch), "--prompt-len",
            str(prompt_len), "--gen", str(gen), "--seed", str(SEED)]
    if smoke:
        argv.append("--smoke")
    r = serve.main(argv)
    check(r["finite"], "serve: non-finite logits")
    check(r["generated_shape"] == [batch, gen],
          f"serve: generated {r['generated_shape']}, expected {[batch, gen]}")
    say(f"serve {arch} batch={batch} prompt={prompt_len} gen={gen}: "
        f"ttft_s={r[serve_metrics.TTFT_S]} tpot_s={r[serve_metrics.TPOT_S]} "
        "(compile included)")
    return r


def _compile(fn, *args, require_mosaic: bool, name: str, **static):
    """``fn`` compiled for ``args``; on the chip the program must hold the
    Mosaic kernel, which shows it did not run in interpret mode."""
    compiled = fn.lower(*args, **static).compile()
    if require_mosaic:
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
    return compiled


def _rel_err(out, expect) -> float:
    out, expect = out.astype(jnp.float32), expect.astype(jnp.float32)
    return float(jnp.max(jnp.abs(out - expect)) / jnp.max(jnp.abs(expect)))


def _ref_attention_bshd(q, k, v, *, causal, window, scale):
    b, s, h, _ = q.shape
    dv = v.shape[-1]
    to_bh = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, s, t.shape[-1])
    with jax.default_matmul_precision("highest"):
        out = ref.reference_attention(to_bh(q), to_bh(k), to_bh(v), causal=causal,
                                      window=window, scale=scale)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3)


def _attention_grads(attn):
    """The gradients of Σ attn(q, k, v) · ct with respect to q, k and v."""
    def loss(q, k, v, ct, **static):
        return jnp.sum(attn(q, k, v, **static).astype(jnp.float32) * ct)
    return jax.jit(jax.grad(loss, (0, 1, 2)),
                   static_argnames=("causal", "window", "scale"))


def kernel_phase(*, attn_cases=ATTN_CASES, rmsnorm_shape=RMSNORM_SHAPE,
                 quant_n: int = QUANT_N, require_mosaic: bool = True) -> dict:
    """Each kernel through ``repro.kernels.ops`` against its oracle; returns
    the errors by kernel (flash attention: its output and its gradients).
    ``require_mosaic=False`` only for a CPU test."""
    key = jax.random.PRNGKey(SEED)
    errs = {}
    for i, (name, (b, s, h, kv, d, dv), window, scale) in enumerate(attn_cases):
        kq, kk, kvv, kct = jax.random.split(jax.random.fold_in(key, i), 4)
        q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16)
        v = jax.random.normal(kvv, (b, s, kv, dv), jnp.bfloat16)
        ct = jax.random.normal(kct, (b, s, h, dv), jnp.float32)
        static = dict(causal=True, window=window, scale=scale)
        label = f"flash_attention {name}"
        fa = _compile(jax.jit(ops.flash_attention, static_argnames=tuple(static)),
                      q, k, v, **static, require_mosaic=require_mosaic, name=label)
        oracle = jax.jit(_ref_attention_bshd, static_argnames=tuple(static))
        errs[label] = _rel_err(fa(q, k, v), oracle(q, k, v, **static))
        check(errs[label] <= BF16_REL, f"{label}: rel err {errs[label]}")
        grads = _compile(_attention_grads(ops.flash_attention), q, k, v, ct,
                         **static, require_mosaic=require_mosaic,
                         name=f"{label} grad")(q, k, v, ct)
        expects = _attention_grads(_ref_attention_bshd)(q, k, v, ct, **static)
        for part, g, e in zip(("dq", "dk", "dv"), grads, expects):
            errs[f"{label} {part}"] = err = _rel_err(g, e)
            check(err <= BF16_REL, f"{label} {part}: rel err {err}")

    kx, kw, kg = jax.random.split(jax.random.fold_in(key, 100), 3)
    x = jax.random.normal(kx, rmsnorm_shape, jnp.bfloat16)
    w = jax.random.normal(kw, rmsnorm_shape[-1:], jnp.float32) * 0.2
    rn = _compile(ops.fused_rmsnorm, x, w, require_mosaic=require_mosaic,
                  name="fused_rmsnorm")
    errs["fused_rmsnorm"] = _rel_err(rn(x, w), jax.jit(ref.reference_rmsnorm)(x, w))
    check(errs["fused_rmsnorm"] <= BF16_REL,
          f"fused_rmsnorm: rel err {errs['fused_rmsnorm']}")

    g = jax.random.normal(kg, (quant_n,), jnp.float32) * 5
    quant = _compile(ops.quantize_int8, g, require_mosaic=require_mosaic,
                     name="quantize_int8")
    q8, sc = quant(g)
    q8_ref, sc_ref = jax.jit(ref.reference_quantize_int8)(g)
    errs["quantize_int8 payload mismatches"] = int(jnp.sum(q8 != q8_ref))
    errs["quantize_int8 scale mismatches"] = int(jnp.sum(sc != sc_ref))
    dequant = _compile(ops.dequantize_int8, q8, sc, n=quant_n,
                       require_mosaic=require_mosaic, name="dequantize_int8")
    deq_ref = jax.jit(ref.reference_dequantize_int8, static_argnames="n")(
        q8_ref, sc_ref, n=quant_n)
    errs["dequantize_int8 mismatches"] = int(jnp.sum(dequant(q8, sc) != deq_ref))
    for name in ("quantize_int8 payload mismatches",
                 "quantize_int8 scale mismatches", "dequantize_int8 mismatches"):
        check(errs[name] == 0, f"{name}: {errs[name]} of {quant_n} "
              "differ from the oracle (must be bit-identical)")
    for name, err in errs.items():
        say(f"kernel {name}: {err}")
    return errs


def _timed_us(fn, x) -> tuple[jax.Array, float]:
    out = jax.block_until_ready(fn(x))  # compile + warm
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(x))
    return out, (time.perf_counter() - t0) * 1e6


def collective_phase(*, sizes=COLLECTIVE_BYTES, algos=COLLECTIVE_ALGOS,
                     n_chunks: int = OVERLAP_CHUNKS) -> dict:
    """The LUMORPH all-reduces over every device, against ``lax.psum``;
    returns the relative errors by (algorithm, bytes per rank)."""
    p = jax.device_count()
    mesh = jax.make_mesh((p,), ("d",), axis_types=(AxisType.Auto,))
    key = jax.random.PRNGKey(SEED)
    psum = make_all_reduce(mesh, "d", "psum")
    errs = {}
    for nbytes in sizes:
        x = jax.random.normal(jax.random.fold_in(key, nbytes), (p, nbytes // 4),
                              jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("d")))
        expect, us = _timed_us(psum, xs)
        say(f"collective psum {nbytes} B/rank: {us:.1f} us (one warm call)")
        for algo in algos:
            for label, fn in (
                    (algo, make_all_reduce(mesh, "d", algo)),
                    (f"{algo} overlap C={n_chunks}", make_overlapped_all_reduce(
                        mesh, "d", algo, n_chunks=n_chunks))):
                out, us = _timed_us(fn, xs)
                devs = {s.device for s in out.addressable_shards}
                check(len(out.addressable_shards) == p and len(devs) == p,
                      f"{label}: result shards on {len(devs)} distinct "
                      f"devices, expected {p}")
                err = errs[(label, nbytes)] = _rel_err(out, expect)
                check(err <= F32_REL, f"{label} {nbytes} B/rank: rel err {err}")
                say(f"collective {label} {nbytes} B/rank on {len(devs)} "
                    f"devices: rel err vs psum {err}, {us:.1f} us (one warm call)")
    return errs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip path, on a 2x2 host")
    args = ap.parse_args(argv)
    say(f"compile cache: {use_compile_cache()}")
    dev = device_phase(4 if args.four_chips else 1)
    say("smoke output from one cold run, not benchmark metrics")
    if args.four_chips:
        collective_phase()
        train_phase(batch=32, seq=512, steps=3, data_parallel=4)
    else:
        train_phase(batch=8, seq=512, steps=5)
        serve_phase(batch=4, prompt_len=64, gen=16)
        kernel_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
