"""End-to-end training integration: LUMORPH comm == XLA comm, loss sanity."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_train(extra, timeout=900):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train"] + extra,
        capture_output=True, text=True, timeout=timeout,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": "/root",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_lumorph_comm_matches_xla():
    """Gradient path equivalence: the LUMORPH collectives must produce the
    same training trajectory as XLA's all-reduce (4 fake devices, dp=4)."""
    common = ["--arch", "bert-large", "--smoke", "--steps", "4", "--batch", "4",
              "--seq", "32", "--data-parallel", "4", "--log-every", "100",
              "--wire-dtype", "float32"]
    base = _run_train(common + ["--comm", "xla"])
    for comm in ("ring", "lumorph2", "lumorph4"):
        out = _run_train(common + ["--comm", comm])
        assert out["final_loss"] == pytest.approx(base["final_loss"], rel=1e-4), comm
    # production wire dtype (bf16): stays within mixed-precision tolerance
    bf = _run_train(common[:-2] + ["--comm", "lumorph4"])
    assert bf["final_loss"] == pytest.approx(base["final_loss"], rel=2e-2)


@pytest.mark.slow
def test_compressed_training_tracks():
    """int8+EF training stays close to exact-comm training."""
    common = ["--arch", "bert-large", "--smoke", "--steps", "6", "--batch", "4",
              "--seq", "32", "--data-parallel", "4", "--log-every", "100"]
    base = _run_train(common + ["--comm", "lumorph2"])
    comp = _run_train(common + ["--comm", "lumorph2", "--compress"])
    assert comp["final_loss"] == pytest.approx(base["final_loss"], rel=0.05)


@pytest.mark.slow
def test_loss_decreases_short_run():
    out = _run_train(["--arch", "bert-large", "--smoke", "--steps", "30",
                      "--batch", "4", "--seq", "32", "--lr", "1e-3",
                      "--comm", "lumorph4", "--data-parallel", "2",
                      "--log-every", "100"], timeout=1200)
    assert out["final_loss"] < out["first_loss"]
