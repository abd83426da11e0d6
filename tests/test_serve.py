"""Serving: batched prefill+decode, sliding-window ring cache, CiM mode."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.cim_linear import CiMConfig
from repro.launch.serve import ServeSettings, parse_fabric_mesh, serve_batch
from repro.models import build_model
from repro.models import layers as L


def test_parse_fabric_mesh():
    """--fabric-mesh DxM: any mesh make_chip_mesh accepts, loud errors else."""
    assert parse_fabric_mesh("2x4") == (2, 4)
    assert parse_fabric_mesh("1x1") == (1, 1)
    assert parse_fabric_mesh("4X2") == (4, 2)  # case-insensitive
    for bad in ("2x", "axb", "2x2x2", ""):
        with pytest.raises(ValueError, match="fabric-mesh"):
            parse_fabric_mesh(bad)
    with pytest.raises(ValueError, match="axes must be >= 1"):
        parse_fabric_mesh("0x2")


def test_serve_batch_runs():
    cfg = reduced(ARCHS["smollm-135m"], n_layers=2)
    out = serve_batch(cfg, ServeSettings(batch=3, prompt_len=16, gen_len=8))
    assert out["generated"].shape == (3, 8)
    assert out["decode_tok_s"] > 0


def test_serve_with_cim_quantization():
    """The paper's technique as a serving feature (fake_quant inference)."""
    cfg = dataclasses.replace(
        reduced(ARCHS["smollm-135m"], n_layers=2),
        cim=CiMConfig(mode="fake_quant", adc_bits=8, rows=64, ste=False),
    )
    out = serve_batch(cfg, ServeSettings(batch=2, prompt_len=8, gen_len=4))
    assert out["generated"].shape == (2, 4)


def test_window_ring_cache_equals_full_cache_within_window():
    """Windowed decode == full-cache decode when context fits the window."""
    base = reduced(ARCHS["smollm-135m"], n_layers=2)
    b, s = 2, 48
    x = jax.random.randint(jax.random.PRNGKey(0), (b, s), 0, base.vocab)

    cfg_full = base
    cfg_win = dataclasses.replace(base, sliding_window=64)  # window > context
    logits = {}
    for tag, cfg in (("full", cfg_full), ("win", cfg_win)):
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(1))
        cache = m.make_cache(b, 64)
        _, cache = m.prefill(p, x[:, :-1], cache)
        ld, _ = m.decode_step(p, x[:, -1], jnp.asarray(s - 1), cache)
        logits[tag] = ld
    np.testing.assert_allclose(
        np.asarray(logits["full"]), np.asarray(logits["win"]), atol=2e-4
    )


def test_decode_beyond_window_truncates_attention():
    """With a small window, early tokens stop influencing decode logits."""
    cfg = dataclasses.replace(
        reduced(ARCHS["smollm-135m"], n_layers=2), sliding_window=16
    )
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(1))
    b, s = 1, 48
    x1 = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab)
    x2 = x1.at[:, :8].set((x1[:, :8] + 7) % cfg.vocab)  # differ only outside window
    outs = []
    for x in (x1, x2):
        cache = m.make_cache(b, s)
        _, cache = m.prefill(p, x[:, :-1], cache)
        ld, _ = m.decode_step(p, x[:, -1], jnp.asarray(s - 1), cache)
        outs.append(np.asarray(ld))
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-4)


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing is set; else the fixed
    checkout path."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    repo = compile_cache.CHECKOUT_CACHE.parent
    assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (repo / "src" / "repro").is_dir()
