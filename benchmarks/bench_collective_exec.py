"""Executable-collective benchmark: our shard_map ALLREDUCEs on 8 fake CPU
devices (numerics + wall time) — run in a subprocess pinned to
``JAX_PLATFORMS=cpu``, so the child never asks for an accelerator the
parent process may already hold.  A child that fails raises.

CPU wall-times don't transfer to TPU; the useful derived outputs are the
numerical max-error vs psum and the per-algorithm round counts (which ARE
the TPU-relevant α structure).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_cpu_child(script: str, bench: str) -> dict:
    """Run ``script`` on the CPU backend; return its ``RESULT`` payload."""
    r = subprocess.run([sys.executable, "-c", script.format(src=SRC)],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    for line in r.stdout.splitlines():
        if r.returncode == 0 and line.startswith("RESULT"):
            return json.loads(line[6:])
    raise RuntimeError(f"{bench}: CPU child failed (rc={r.returncode}):\n"
                       f"{r.stderr[-2000:]}")

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.core.collectives import make_all_reduce
from repro.core.scheduler import build_schedule

p = 8
mesh = jax.make_mesh((p,), ("d",), axis_types=(AxisType.Auto,))
rng = np.random.RandomState(0)
x = rng.randn(p, 1 << 16).astype(np.float32)
expect = x.sum(0)
xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("d", None)))
out = {{}}
for algo in ("ring", "lumorph2", "lumorph4", "psum"):
    f = make_all_reduce(mesh, "d", algo)
    r = f(xs); jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f(xs))
    dt = (time.perf_counter() - t0) / 5 * 1e6
    err = float(np.abs(np.asarray(r)[0] - expect).max() / np.abs(expect).max())
    rounds = len(build_schedule(algo, list(range(p)), 4 << 16).rounds) if algo != "psum" else 0
    out[algo] = {{"us": dt, "err": err, "rounds": rounds}}
print("RESULT" + json.dumps(out))
"""


def run() -> list[str]:
    lines = ["name,us_per_call,derived"]
    data = _run_cpu_child(SCRIPT, "bench_collective_exec")
    for algo, d in data.items():
        lines.append(f"bench_collective_exec/{algo}/8cpu_256KB,{d['us']:.0f},"
                     f"err={d['err']:.1e} rounds={d['rounds']}")
    return lines


# ---------------------------------------------------------------------------
# overlap mode (``benchmarks.run bench_overlap``): chunked waves hidden
# behind a Pallas compute kernel
# ---------------------------------------------------------------------------

OVERLAP_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.core.collectives import (compile_schedule, make_overlapped_all_reduce,
                                    schedule_for_execution)
from repro.kernels import ops

p = 8
D = 128
mesh = jax.make_mesh((p,), ("d",), axis_types=(AxisType.Auto,))
rng = np.random.RandomState(0)
x = rng.randn(p, 1 << 16).astype(np.float32)
xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("d", None)))
w = jnp.zeros((D,), jnp.float32)

def compute(y):
    # the per-chunk consumer: the Pallas rmsnorm over the reduced slice
    return ops.fused_rmsnorm(y.reshape(-1, D), w).reshape(y.shape)

def timed(f):
    r = f(xs); jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f(xs))
    return (time.perf_counter() - t0) / 5 * 1e6, np.asarray(r)

expect = np.asarray(compute(jnp.asarray(x.sum(0))))
out = {{}}
mono_fn = compile_schedule(schedule_for_execution("lumorph2", p), "d")
mono = jax.jit(jax.shard_map(
    lambda v: compute(mono_fn(v[0]))[None], mesh=mesh,
    in_specs=P("d", None), out_specs=P("d", None),
    axis_names={{"d"}}, check_vma=False))
us, r = timed(mono)
err = float(np.abs(r[0] - expect).max() / np.abs(expect).max())
out["mono"] = {{"us": us, "err": err}}
for C in (2, 4, 8):
    f = make_overlapped_all_reduce(mesh, "d", algo="lumorph2", n_chunks=C,
                                   compute=compute)
    us, r = timed(f)
    err = float(np.abs(r[0] - expect).max() / np.abs(expect).max())
    out[f"overlap_c{{C}}"] = {{"us": us, "err": err}}
print("RESULT" + json.dumps(out))
"""

#: the analytic operating point the overlap claim is gated at: paper-scale
#: width, a β-heavy bucket, compute sized to the collective (the balanced
#: regime every DDP bucket aims for) — 8-way chunking should hide most of
#: the wire time behind the compute stream
CLAIM_P, CLAIM_BYTES, CLAIM_CHUNKS, CLAIM_MIN = 256, 256e6, 8, 1.3


def run_overlap() -> list[str]:
    """``bench_overlap``: measured chunked-vs-monolithic wall times on the
    8-device fake mesh (numerics + interleaving overhead; CPU serializes
    the streams, so wall-clock parity is the bar there) plus the α–β
    pipelined model at the claim's operating point, which gates
    ``claim_overlap_speedup``."""
    from repro.core import cost_model as cm

    lines = ["name,us_per_call,derived"]
    data = _run_cpu_child(OVERLAP_SCRIPT, "bench_overlap")
    mono_us = data["mono"]["us"]
    for name, d in data.items():
        ratio = "" if name == "mono" else f" vs_mono={mono_us / d['us']:.2f}x"
        lines.append(f"bench_overlap/exec/{name}/8cpu_256KB,{d['us']:.0f},"
                     f"err={d['err']:.1e}{ratio}")

    link = cm.LUMORPH_LINK
    for p in (64, CLAIM_P):
        comm = cm.algorithm_cost("lumorph2", CLAIM_BYTES, p, link)
        t_mono = cm.overlapped_step_time("lumorph2", CLAIM_BYTES, p, link,
                                         1, comm)
        t_ovl = cm.overlapped_step_time("lumorph2", CLAIM_BYTES, p, link,
                                        CLAIM_CHUNKS, comm)
        lines.append(
            f"bench_overlap/model/p{p}_256MB_c{CLAIM_CHUNKS},,"
            f"t_mono={t_mono * 1e3:.2f}ms t_ovl={t_ovl * 1e3:.2f}ms "
            f"speedup={t_mono / t_ovl:.2f}x")
        if p == CLAIM_P:
            lines.append(f"bench_overlap/model/gate_speedup,,"
                         f"{t_mono / t_ovl:.2f}x (gate {CLAIM_MIN}x)")
            lines.append(f"bench_overlap/claim_overlap_speedup,,"
                         f"{'PASS' if t_mono / t_ovl >= CLAIM_MIN else 'FAIL'}")
    return lines
