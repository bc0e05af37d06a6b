"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases pass at BERT-large smoke sizes (kernels in interpret mode).  Also
the compile-cache helper the entry points call."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_exits_nonzero_without_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "script_alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.device_phase(1)


def test_check_raises(smoke):
    smoke.check(True, "fine")
    with pytest.raises(RuntimeError, match="broken"):
        smoke.check(False, "broken")


def test_train_phase_smoke(smoke, capsys):
    runs = smoke.train_phase(batch=2, seq=16, steps=2, smoke=True)
    assert set(runs) == {"xla", "lumorph4"}
    for r in runs.values():
        assert len(r["losses"]) == len(r["step_s"]) == 2
        assert r["param_devices"] == 1
    assert "[smoke] train bert-large comm=lumorph4" in capsys.readouterr().out


def test_serve_phase_smoke(smoke):
    r = smoke.serve_phase(batch=2, prompt_len=4, gen=3, smoke=True)
    assert r["generated_shape"] == [2, 3]


def test_kernel_phase_small(smoke):
    errs = smoke.kernel_phase(
        attn_cases=(("gqa+swa", (1, 256, 4, 2, 80), 100),),
        rmsnorm_shape=(64, 256), quant_n=300_000, require_mosaic=False)
    assert errs["flash_attention gqa+swa"] <= smoke.BF16_REL
    assert errs["quantize_int8 payload mismatches"] == 0
    assert errs["dequantize_int8 mismatches"] == 0


def test_collective_phase_one_device(smoke):
    errs = smoke.collective_phase(sizes=(4096,), n_chunks=2)
    assert len(errs) == 2 * len(smoke.COLLECTIVE_ALGOS)
    assert max(errs.values()) == 0.0  # one rank: every algorithm is exact


def test_compile_cache_keeps_env_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
