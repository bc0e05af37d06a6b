"""Checkpoint: atomic write, latest discovery, retention, elastic restore."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.checkpoint import checkpoint as ck


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (4, 8)),
                       "b": jnp.zeros(8)},
            "opt": {"m": jnp.ones((4, 8)), "step": jnp.int32(7)}}


def test_roundtrip(tmp_path):
    s = _state()
    ck.save(tmp_path, 7, s)
    restored, step = ck.restore(tmp_path, jax.tree.map(lambda x: x, s))
    assert step == 7
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_latest_and_retention(tmp_path):
    s = _state()
    for step in (10, 20, 30, 40):
        ck.save(tmp_path, step, s, keep=2)
    assert ck.latest_step(tmp_path) == 40
    kept = sorted(d.name for d in Path(tmp_path).iterdir())
    assert kept == ["step_0000000030", "step_0000000040"]


def test_incomplete_checkpoint_ignored(tmp_path):
    """A crash mid-write leaves a .tmp dir — it must never be 'latest'."""
    s = _state()
    ck.save(tmp_path, 5, s)
    bad = Path(tmp_path) / "step_0000000009.tmp"
    bad.mkdir()
    (bad / "leaf_00000.npy").write_bytes(b"junk")
    assert ck.latest_step(tmp_path) == 5
    # also: a dir without manifest is ignored
    nomanifest = Path(tmp_path) / "step_0000000011"
    nomanifest.mkdir()
    assert ck.latest_step(tmp_path) == 5


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ck.restore(tmp_path, _state())


def test_shape_mismatch_rejected(tmp_path):
    s = _state()
    ck.save(tmp_path, 1, s)
    wrong = {"params": {"w": jnp.zeros((5, 8)), "b": jnp.zeros(8)},
             "opt": {"m": jnp.ones((4, 8)), "step": jnp.int32(0)}}
    with pytest.raises(ValueError):
        ck.restore(tmp_path, wrong)


def test_elastic_restore_resharded(tmp_path):
    """Restore onto explicit shardings (elastic mesh change semantics)."""
    s = _state()
    ck.save(tmp_path, 3, s)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = jax.tree.map(
        lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()), s)
    restored, step = ck.restore(tmp_path, s, shardings=sh)
    assert step == 3
    assert restored["params"]["w"].sharding.mesh.shape["data"] == 1


def test_train_restart_continues(tmp_path):
    """Integration: a killed-and-restarted trainer resumes from the
    checkpoint and the data stream position (determinism)."""
    from repro.launch.train import main
    args = ["--arch", "bert-large", "--smoke", "--steps", "6", "--batch", "2",
            "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
            "--log-every", "100"]
    main(args)  # runs 0..5, checkpoints at 3 and 6
    assert ck.latest_step(tmp_path) == 6
    r2 = main(["--arch", "bert-large", "--smoke", "--steps", "8", "--batch", "2",
               "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
               "--log-every", "100"])
    assert r2["steps"] == 2  # resumed at 6, ran 6..7
