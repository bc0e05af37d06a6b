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

import jax
import jax.numpy as jnp
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
                 [((8 * 16, 512, 64), BF16)] * 3, dict(window=None),
                 id="flash_attention-bert-large"),
    pytest.param(fa.flash_attention_bhsd,
                 [((32, 4096, 80), BF16), ((8, 4096, 80), BF16),
                  ((8, 4096, 80), BF16)], dict(window=4096),
                 id="flash_attention-danube-gqa-swa"),
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
