"""Dense / GQA / MoE decoder stack with scan-over-layers.

All per-layer parameters are stacked on a leading L dim and consumed via
``lax.scan`` — the lowered HLO contains ONE block body regardless of depth,
which keeps the 512-device SPMD dry-run compile tractable and is the layout
pipeline-parallelism would slice at >1k-chip scale.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import init_moe, moe_ffn, moe_ffn_dense
from repro.obs import scopes

__all__ = [
    "init_transformer",
    "transformer_forward",
    "transformer_prefill",
    "transformer_decode",
]


def init_transformer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    nl = cfg.n_layers
    p = {
        "embed": L.init_embedding(ks[0], cfg),
        "attn": L.init_attention(ks[1], cfg, nl),
        "ln1": jnp.zeros((nl, cfg.d_model), L.pdtype(cfg)),
        "ln2": jnp.zeros((nl, cfg.d_model), L.pdtype(cfg)),
        "ln_f": jnp.zeros((cfg.d_model,), L.pdtype(cfg)),
    }
    if cfg.n_experts:
        p["moe"] = init_moe(ks[2], cfg, nl)
    else:
        p["mlp"] = L.init_mlp(ks[2], cfg, nl)
    return p


def _block_train(x, lp, cfg: ModelConfig, positions):
    h, _ = L.attention(lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps), cfg, positions)
    x = x + h
    hn = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        ffn = moe_ffn_dense if cfg.moe_impl == "dense" else moe_ffn
        y, aux = ffn(lp["moe"], hn, cfg)
    else:
        y, aux = L.mlp(lp["mlp"], hn, cfg), jnp.zeros((), jnp.float32)
    return L.constrain(x + y, ("dp", None, None)), aux


def _layer_params(p: dict, cfg: ModelConfig):
    lp = {"attn": p["attn"], "ln1": p["ln1"], "ln2": p["ln2"]}
    lp["moe" if cfg.n_experts else "mlp"] = p["moe" if cfg.n_experts else "mlp"]
    return lp


def transformer_forward(p: dict, x_in: jnp.ndarray, cfg: ModelConfig):
    """Training forward: (B, S) tokens or (B, S, D) embeddings -> (h, aux)."""
    x = L.embed(p["embed"], x_in, cfg)
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(carry, lp):
        x, aux = carry
        x, a = _block_train(x, lp, cfg, positions)
        return (x, aux + a), None

    if cfg.remat != "none":
        body = jax.checkpoint(body)
    with jax.named_scope(scopes.LAYER_SCAN):
        (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), _layer_params(p, cfg))
    h = L.rms_norm(x, p["ln_f"], cfg.norm_eps)
    return h, aux / max(cfg.n_layers, 1)


def transformer_prefill(p: dict, x_in: jnp.ndarray, cfg: ModelConfig, cache: dict):
    """Prefill: fills the per-layer KV cache, returns (h_last, cache)."""
    x = L.embed(p["embed"], x_in, cfg)
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(x, xs):
        lp, cache_l = xs
        hn = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, new_cache = L.attention(lp["attn"], hn, cfg, positions, cache=cache_l)
        x = x + h
        hn = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            ffn = moe_ffn_dense if cfg.moe_impl == "dense" else moe_ffn
            y, _ = ffn(lp["moe"], hn, cfg)
        else:
            y = L.mlp(lp["mlp"], hn, cfg)
        return x + y, new_cache

    if cfg.remat != "none":
        body = jax.checkpoint(body)
    with jax.named_scope(scopes.LAYER_SCAN):
        x, new_cache = lax.scan(body, x, (_layer_params(p, cfg), cache))
    h = L.rms_norm(x, p["ln_f"], cfg.norm_eps)
    return h, new_cache


def transformer_decode(p: dict, token, cfg: ModelConfig, pos, cache: dict):
    """One decode step: token (B,) or embedding (B, D) -> (logits, cache)."""
    if cfg.input_kind == "embeddings":
        x = token[:, None, :].astype(L.cdtype(cfg))
    else:
        x = L.embed(p["embed"], token[:, None], cfg)

    def body(x, xs):
        lp, cache_l = xs
        hn = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, new_cache = L.decode_attention(lp["attn"], hn, cfg, pos, cache_l)
        x = x + h
        hn = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            ffn = moe_ffn_dense if cfg.moe_impl == "dense" else moe_ffn
            y, _ = ffn(lp["moe"], hn, cfg)
        else:
            y = L.mlp(lp["mlp"], hn, cfg)
        return x + y, new_cache

    with jax.named_scope(scopes.LAYER_SCAN):
        x, new_cache = lax.scan(body, x, (_layer_params(p, cfg), cache))
    h = L.rms_norm(x, p["ln_f"], cfg.norm_eps)
    logits = L.logits_step(p["embed"], h, cfg)
    return logits, new_cache
