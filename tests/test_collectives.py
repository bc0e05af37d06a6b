"""Executable collectives: exact ALLREDUCE vs psum (multi-device via
subprocess — the main test process keeps 1 device)."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

SRC = str(Path(__file__).resolve().parents[1] / "src")

CHECK = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.core.collectives import make_all_reduce
from repro.optim.grad_comm import compressed_all_reduce

p = 8
mesh = jax.make_mesh((p,), ("d",), axis_types=(AxisType.Auto,))
rng = np.random.RandomState(0)
x = rng.randn(p, 41).astype(np.float32)
expect = np.tile(x.sum(0, keepdims=True), (p, 1))
xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("d", None)))
for algo in ("ring", "lumorph2", "lumorph4", "tree", "psum"):
    out = np.asarray(make_all_reduce(mesh, "d", algo)(xs))
    assert np.allclose(out, expect, rtol=1e-5, atol=1e-5), algo
# compressed: lossy but bounded (int8 per-block ~ 1% of block max per hop)
f = jax.jit(jax.shard_map(lambda v: compressed_all_reduce(v[0], "d")[None],
            mesh=mesh, in_specs=P("d", None), out_specs=P("d", None),
            axis_names={{"d"}}, check_vma=False))
out = np.asarray(f(xs))
rel = np.abs(out - expect).max() / np.abs(expect).max()
assert rel < 0.05, f"compressed relerr {{rel}}"
print("SUBPROCESS_OK")
"""


@pytest.mark.slow
def test_collectives_multidevice():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", CHECK.format(src=SRC)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


def test_single_device_identity():
    """p=1: every algorithm must be the identity."""
    from repro.core.collectives import all_reduce
    mesh = jax.make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))
    x = jnp.arange(16.0)
    for algo in ("ring", "lumorph2", "lumorph4", "tree", "psum"):
        f = jax.jit(jax.shard_map(
            lambda v: all_reduce(v, "d", algo), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec(),
            out_specs=jax.sharding.PartitionSpec(),
            axis_names={"d"}, check_vma=False))
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x))


def test_partner_maps_match_scheduler():
    """The ppermute partner maps are exactly the scheduler's circuits —
    check LUMORPH-2 round 0 for p=8: partners at XOR distance 4."""
    from repro.core.scheduler import rhd_schedule
    s = rhd_schedule(list(range(8)), 1024.0)
    assert set(s.rounds[0].pairs) == {(i, i ^ 4) for i in range(8)}
    assert set(s.rounds[-1].pairs) == {(i, i ^ 4) for i in range(8)}
