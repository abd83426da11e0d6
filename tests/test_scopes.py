"""The device scopes (``repro.obs.scopes``) reach the compiled programs: every
model scope is in the op_name metadata of a tiny CiM transformer's prefill,
decode step and train step, and the fused fabric graph carries its node and
ADC scopes. Scopes are metadata only: that they leave the optimized HLO
unchanged once op_name is stripped was checked against the tree without
them (PERF.md), not here."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.core.cim_linear import CiMConfig
from repro.models import build_model
from repro.models import layers as Lmod
from repro.obs import scopes

CFG = ModelConfig(
    name="scopes", family="dense", n_layers=1, d_model=64, vocab=256, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, pad_vocab_multiple=16,
    param_dtype="float32", compute_dtype="float32",
    cim=CiMConfig(mode="fake_quant", adc_bits=12, ste=False),
)
MODEL_SCOPES = {
    scopes.EMBED, scopes.NORM, scopes.ATTENTION, scopes.KV_CACHE, scopes.MLP,
    scopes.CIM_LINEAR, scopes.CIM_QUANTIZE, scopes.CIM_TILES, scopes.LM_HEAD,
    scopes.LAYER_SCAN,
}
_WRAPPER = re.compile(r"^(?:\w+\()+|\)+$")


@pytest.fixture(autouse=True)
def _no_act_rules():
    Lmod.set_act_rules(None)
    yield
    Lmod.set_act_rules(None)


def scopes_in(compiled) -> set:
    """Vocabulary names on the op_name paths of a compiled program."""
    paths = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    parts = {_WRAPPER.sub("", p) for path in paths for p in path.split("/")}
    return parts & set(scopes.SCOPES)


def serve_programs(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.make_cache(2, 24)
    toks = jnp.zeros((2, 16), jnp.int32)
    prefill = jax.jit(model.prefill).lower(params, toks, cache).compile()
    decode = jax.jit(model.decode_step).lower(params, toks[:, 0], jnp.int32(16), cache).compile()
    return prefill, decode


def test_serve_programs_carry_every_model_scope():
    prefill, decode = serve_programs(CFG)
    assert scopes_in(prefill) == MODEL_SCOPES
    assert scopes_in(decode) == MODEL_SCOPES


def test_linear_off_the_cim_path():
    _, decode = serve_programs(dataclasses.replace(CFG, cim=None))
    assert scopes.LINEAR in scopes_in(decode)
    assert not scopes_in(decode) & {scopes.CIM_LINEAR, scopes.CIM_QUANTIZE}


def test_train_step_carries_the_ste_and_optimizer():
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import TrainSettings, _build_step

    cfg = dataclasses.replace(CFG, cim=dataclasses.replace(CFG.cim, ste=True))
    model = build_model(cfg)
    mesh = make_local_mesh()
    opt_init, step_fn = _build_step(model, cfg, TrainSettings(steps=4, batch=2, seq=32), mesh)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        batch = {"inputs": jnp.zeros((2, 32), jnp.int32), "labels": jnp.zeros((2, 32), jnp.int32)}
        compiled = step_fn.lower(params, opt_init(params), batch, jnp.int32(0)).compile()
    want = MODEL_SCOPES - {scopes.KV_CACHE} | {scopes.CIM_STE, scopes.OPTIMIZER}
    assert scopes_in(compiled) == want


def test_fused_fabric_graph_carries_node_and_adc_scopes():
    from repro.fabric import ChipMeshConfig, FabricConfig, compile_graph_forward

    fb = FabricConfig(mode="hybrid", n_arrays=64)
    cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=fb.adc_bits, rows=fb.rows,
                    ste=False)
    prog = compile_graph_forward(dataclasses.replace(CFG, cim=None, vocab=64),
                                 ChipMeshConfig(fabric=fb), cim=cim, backend="shard_map",
                                 tokens=4, scan_layers=True)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 4, prog.d_in))
    flat = prog._prepare(x, prog.random_weights(jax.random.PRNGKey(3)), None)
    got = scopes_in(prog._fused(False).lower(x, *flat).compile())
    assert {scopes.fabric_op("matmul"), scopes.CIM_ADC, scopes.CIM_MAC,
            scopes.FABRIC_REQUANT} <= got
    assert {scopes.fabric_op(op) for op in ("norm", "attention", "silu_gate", "residual")} <= got


def test_fabric_op_scope_names_are_closed():
    assert scopes.fabric_op("residual") == "fabric.residual"
    with pytest.raises(ValueError, match="unknown fabric op"):
        scopes.fabric_op("conv")
