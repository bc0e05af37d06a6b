"""The main-path Pallas kernels compile for TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a v5e that is described,
not attached.  That catches what interpret mode cannot — a block shape
Mosaic refuses, too much VMEM — and shows the kernel is lowered to Mosaic
(``tpu_custom_call``), not interpreted.  Nothing runs, so these say
nothing about results or speed.

The topology is described only inside the ``topo`` fixture: only one
process at a time may load the TPU library, so no module of the suite may
do it while it is imported.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import grad_compress as gc
from repro.kernels import rmsnorm as rn

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8
QUANT_N = 1 << 24  # a 64 MB fp32 gradient bucket


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: a
    compile for a described chip is written there but cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe one with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel,args,kw", [
    pytest.param(fa.flash_attention_bhsd,
                 [((8, 16, 512, 64), BF16)] * 3,
                 dict(causal=True, window=None, scale=0.125),
                 id="flash_attention-bert-large"),
    pytest.param(fa.flash_attention_bhsd,
                 [((1, 32, 4096, 80), BF16), ((1, 8, 4096, 80), BF16),
                  ((1, 8, 4096, 80), BF16)],
                 dict(causal=True, window=1024, scale=80 ** -0.5),
                 id="flash_attention-danube-gqa-swa"),
    pytest.param(fa.flash_attention_bhsd,
                 [((2, 16, 4096, 192), BF16), ((2, 16, 4096, 192), BF16),
                  ((2, 16, 4096, 128), BF16)],
                 dict(causal=True, window=None, scale=0.1147),
                 id="flash_attention-deepseek-mla"),
    pytest.param(fa.flash_attention_bhsd,
                 [((4, 6, 448, 64), BF16), ((4, 6, 1500, 64), BF16),
                  ((4, 6, 1500, 64), BF16)],
                 dict(causal=False, window=None, scale=0.125),
                 id="flash_attention-whisper-cross"),
    pytest.param(rn.rmsnorm_pallas, [((4096, 2560), BF16), ((2560,), F32)],
                 {}, id="rmsnorm-d2560"),
    pytest.param(gc.quantize_int8_pallas, [((QUANT_N,), F32)], {},
                 id="quantize_int8-16M"),
    pytest.param(gc.dequantize_int8_pallas,
                 [((QUANT_N,), I8), ((QUANT_N // gc.QUANT_BLOCK,), F32)],
                 dict(n=QUANT_N), id="dequantize_int8-16M"),
])
def test_kernel_compiles_for_v5e(one_chip, kernel, args, kw):
    f = jax.jit(functools.partial(kernel, interpret=False, **kw))
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    text = f.lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _cut(arch: str, layers: int, **share):
    from repro.configs import get_config
    cfg = get_config(arch)
    return cfg.replace(n_layers=layers, block_pattern=cfg.block_pattern[:layers],
                       **share)


#: one chip's share of DeepSeek-V2-Lite under 8-way expert parallelism
EP8 = dict(moe_experts=8, moe_router_experts=64, vocab_size=12800)


#: a tpu_custom_call instruction's own op_name (its metadata follows the
#: kernel's multi-line attributes)
_KERNEL_OP = re.compile(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
                        re.DOTALL)


@pytest.mark.parametrize("arch,layers,share,batch,seq,chips,comm", [
    pytest.param("bert-large", 1, {}, 8, 512, 1, "xla", id="bert-large-b8x512"),
    pytest.param("bert-large", 1, {}, 32, 512, 4, "lumorph4", id="bert-large-dp4-lumorph4"),
    pytest.param("bert-large", 1, {}, 32, 512, 4, "xla", id="bert-large-dp4-xla"),
    pytest.param("h2o-danube-1.8b", 1, {}, 1, 4096, 1, "xla", id="danube-s4096"),
    pytest.param("deepseek-v2-lite-16b", 2, EP8, 2, 4096, 1, "xla",
                 id="deepseek-mla-b2x4096"),
])
def test_train_step_takes_flash_attention(topo, monkeypatch, arch, layers, share,
                                          batch, seq, chips, comm):
    """The train step compiled for described v5e chips runs attention
    through the flash kernel, forward and backward, under the ``flash``
    scope, and holds no float32 [.., S, S] score buffer.  Over four chips
    (data parallel, by LUMORPH or by GSPMD) each chip runs the kernel on
    its own rows: no q, k or v is gathered.  ``ops.on_tpu`` is steered:
    here the process's backend is the CPU, but the program is compiled for
    the chip."""
    from jax.sharding import AxisType, Mesh

    from repro.kernels import ops
    from repro.launch import steps
    from repro.models import transformer as tf
    from repro.sharding.policy import make_policy

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = _cut(arch, layers, **share)
    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(chips, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    policy = make_policy(cfg, mesh)
    shapes = tf.param_shapes(cfg)
    params = steps.sharded_struct(shapes, policy.param_specs(shapes), policy)
    o_shapes = steps.opt_shapes(cfg, shapes)
    opt = steps.sharded_struct(o_shapes, policy.opt_specs(o_shapes), policy)
    tokens, _ = steps.input_specs(cfg, policy, seq, batch)
    step = steps.make_train_step(cfg, policy, comm=comm, wire_dtype=BF16)
    text = step.lower(params, opt, tokens).compile().as_text()
    flash = [n for n in _KERNEL_OP.findall(text) if "/attention/flash/" in n]
    # forward, its recomputation under remat, and the dq and dkv kernels
    assert len(flash) >= 4, flash
    assert not re.search(rf"f32\[[0-9,]*{seq},{seq}\]", text)
    assert not re.search(rf"= bf16\[{batch},(\d+,){{2}}\d+\][^\n]* all-gather\(", text)
