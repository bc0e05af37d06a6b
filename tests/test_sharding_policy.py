"""Sharding policy: every spec divides on the production meshes, for every
full-size architecture — without compiling anything (AbstractMesh)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.launch import steps as steps_lib
from repro.models import transformer as tf
from repro.sharding.policy import make_policy

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_size(mesh, name):
    return dict(zip(mesh.axis_names, mesh.axis_sizes))[name]


def _check_divides(tree_shapes, tree_specs, mesh, what, arch):
    shapes = jax.tree.leaves(tree_shapes)
    flat_specs = jax.tree.leaves(tree_specs, is_leaf=lambda x: isinstance(x, P))
    assert len(shapes) == len(flat_specs)
    for leaf, spec in zip(shapes, flat_specs):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= _axis_size(mesh, a)
            assert dim % n == 0, \
                f"{arch} {what}: dim {dim} not divisible by {axes} ({n})"


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
def test_param_and_opt_specs_divide(arch, mesh):
    cfg = get_config(arch)
    policy = make_policy(cfg, mesh)
    pshapes = tf.param_shapes(cfg)
    _check_divides(pshapes, policy.param_specs(pshapes), mesh, "param", arch)
    oshapes = steps_lib.opt_shapes(cfg, pshapes)
    _check_divides(oshapes, policy.opt_specs(oshapes), mesh, "opt", arch)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_cache_specs_divide(arch):
    cfg = get_config(arch)
    policy = make_policy(cfg, SINGLE)
    cshapes = jax.eval_shape(lambda: tf.init_caches(cfg, 128, 2048))
    _check_divides(cshapes, policy.cache_specs(cshapes), SINGLE, "cache", arch)


def test_zero3_auto_enabled_for_dbrx_only():
    for arch in ASSIGNED:
        cfg = get_config(arch)
        policy = make_policy(cfg, SINGLE)
        if arch == "dbrx-132b":
            assert policy.zero3, "dbrx must ZeRO-3 (264GB bf16 / 16 TP > HBM)"
        else:
            assert not policy.zero3, f"{arch} unexpectedly zero3"


def test_batch_spec_handles_unshardable():
    cfg = get_config("zamba2-1.2b")
    policy = make_policy(cfg, SINGLE)
    assert policy.batch_spec("tokens", (256, 4096)) == P("data", None)
    assert policy.batch_spec("tokens", (1, 524288)) == P(None, None)  # long_500k


def test_kv_replication_rule():
    """glm4 kv=2 < tp=16 → K/V projections replicated, Q/O head-sharded."""
    cfg = get_config("glm4-9b")
    policy = make_policy(cfg, SINGLE)
    wq = policy.param_spec("segments/0/attn/wq", (40, 4096, 32, 128))
    wk = policy.param_spec("segments/0/attn/wk", (40, 4096, 2, 128))
    assert tuple(wq) == (None, None, "model", None)
    assert all(e is None for e in tuple(wk))


def test_moe_expert_parallel():
    cfg = get_config("dbrx-132b")
    policy = make_policy(cfg, SINGLE)
    spec = policy.param_spec("segments/0/moe/wi", (40, 16, 6144, 10752))
    assert tuple(spec)[1] == "model"  # experts on the model axis (EP)
