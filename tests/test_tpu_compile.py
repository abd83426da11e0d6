"""Compile the main path for a described TPU v5e chip; nothing runs.

What Mosaic or the TPU compiler refuses here (a block layout, too much VMEM,
a program that does not fit HBM) would fail on the chip, and interpret-mode
tests cannot see it. The topology is described inside a fixture, never at
import, so every pytest worker collects the same tests; the compilation
cache is off around these compiles, whose entries could not be read back
without a chip.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.cim_linear import CiMConfig
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ops import adc_quant_op, cim_matmul_op
from repro.models import build_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [128, 16])
@pytest.mark.parametrize("mode", ["fake_quant", "bitplane"])
def test_cim_matmul_kernel_compiles(one_chip, mode, rows):
    # smollm-135m's up projection over one prefill batch (4 x 128 tokens)
    x = _spec((512, 576), jnp.float32, one_chip)
    w = _spec((576, 1536), jnp.float32, one_chip)
    compiled = cim_matmul_op.lower(x, w, rows=rows, mode=mode, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_adc_quant_kernel_compiles(one_chip):
    v = _spec((512, 1536), jnp.float32, one_chip)
    compiled = adc_quant_op.lower(v, bits=5, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    b, h, kv, s, hd = 1, 9, 3, 2048, 64  # smollm-135m heads at a 2k prefill
    q = _spec((b, h, s, hd), jnp.bfloat16, one_chip)
    k = _spec((b, kv, s, hd), jnp.bfloat16, one_chip)
    compiled = flash_attention_pallas.lower(q, k, k, causal=True, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cim", [None, "fake_quant"])
@pytest.mark.parametrize("step", ["prefill", "decode_step"])
def test_smollm_serving_step_compiles(one_chip, step, cim):
    """Full-width smollm-135m, as ``launch.serve`` jits it."""
    cfg = get_config("smollm-135m")
    if cim:
        cfg = dataclasses.replace(cfg, cim=CiMConfig(mode=cim, ste=False))
    model = build_model(cfg)
    batch, prompt, total = 4, 128, 160
    place = lambda tree: jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.make_cache(batch, total)))
    if step == "prefill":
        args = (params, _spec((batch, prompt), jnp.int32, one_chip), cache)
    else:
        token = _spec((batch,), jnp.int32, one_chip)
        args = (params, token, _spec((), jnp.int32, one_chip), cache)
    compiled = jax.jit(getattr(model, step)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 10**9
