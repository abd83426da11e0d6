"""Shared neural-net layers (pure JAX, functional, scan-friendly).

Conventions:
  * params are nested dicts of arrays; layer-stacked params carry a leading
    ``L`` dim and are consumed via ``lax.scan`` (compact HLO for the 512-device
    dry-run).
  * every matmul goes through ``dense()`` which routes to the CiM-quantized op
    when the config enables the paper's technique.
  * attention is blocked (online softmax over KV chunks) so 32k-token prefill
    never materializes an S×S score matrix; decode (Sq == 1) uses direct
    attention so a sequence-sharded KV cache reduces via SPMD collectives
    (flash-decoding-style sequence parallelism).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.cim_linear import CiMConfig, cim_matmul
from repro.obs import scopes

_NEG = -1e30

# ---------------------------------------------------------------------------
# Activation sharding constraints
#
# Set by launch/steps.py (and the train/serve drivers) before tracing:
#   ACT_RULES = {"dp": (("data",), 16), "tp": (("model",), 16)}
# Without rules (smoke tests, single device) constraints are no-ops.
# ---------------------------------------------------------------------------

ACT_RULES: Optional[dict] = None


def set_act_rules(rules: Optional[dict]) -> None:
    global ACT_RULES
    ACT_RULES = rules


def axis_size(logical: str) -> int:
    if ACT_RULES is None or logical not in ACT_RULES:
        return 1
    return ACT_RULES[logical][1]


def constrain(x: jnp.ndarray, logical: tuple) -> jnp.ndarray:
    """with_sharding_constraint with divisibility fallback per dim."""
    if ACT_RULES is None:
        return x
    from jax.sharding import PartitionSpec as P

    spec = []
    for dim, ax in zip(x.shape, logical):
        if ax is None or ax not in ACT_RULES:
            spec.append(None)
            continue
        axes, size = ACT_RULES[ax]
        spec.append((axes if len(axes) > 1 else axes[0]) if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))

__all__ = [
    "dense",
    "rms_norm",
    "apply_rope",
    "init_attention",
    "attention",
    "decode_attention",
    "init_mlp",
    "mlp",
    "init_embedding",
    "embed",
    "chunked_xent",
]


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def dense(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    cim: Optional[CiMConfig] = None,
):
    """Linear layer; routes through the CiM pipeline when enabled."""
    on_cim = cim is not None and cim.mode != "exact"
    with jax.named_scope(scopes.CIM_LINEAR if on_cim else scopes.LINEAR):
        if on_cim:
            y = cim_matmul(x, w.astype(jnp.float32), cim).astype(x.dtype)
        else:
            y = x @ w.astype(x.dtype)
        if bias is not None:
            y = y + bias.astype(y.dtype)
    return y


import functools
import os

# REPRO_LEGACY_NORM=1 restores the v1 (f32-materializing) norm/attention
# numerics — used to reproduce the paper-faithful BASELINE roofline numbers
# (EXPERIMENTS.md §Perf records both).
LEGACY_NORM = os.environ.get("REPRO_LEGACY_NORM", "0") == "1"


def _rms_norm_legacy(x, scale, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_fused(x, scale, eps):
    y, _ = _rms_norm_fwd(x, scale, eps)
    return y


def _rms_norm_fwd(x, scale, eps):
    """f32 statistics, x.dtype-materialized tensors (fwd AND bwd) — the
    hand-fused VJP keeps the full-hidden cotangents in the compute dtype,
    which the autodiff of an f32-upcast norm cannot (perf iteration A1,
    EXPERIMENTS.md §Perf)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = lax.rsqrt(var + eps)
    y = x * inv.astype(x.dtype) * (1.0 + scale.astype(x.dtype))
    return y, (x, scale, inv)


def _rms_norm_bwd(eps, res, dy):
    x, scale, inv = res
    inv_x = inv.astype(x.dtype)
    xhat = x * inv_x
    g = dy * (1.0 + scale.astype(dy.dtype))
    # dx = inv * (g - xhat * mean(g * xhat));  reductions in f32, tensors in x.dtype
    mgx = jnp.mean(
        (g * xhat).astype(jnp.float32), axis=-1, keepdims=True
    ).astype(x.dtype)
    dx = inv_x * (g - xhat * mgx)
    dscale = jnp.sum(
        (dy * xhat).astype(jnp.float32), axis=tuple(range(dy.ndim - 1))
    ).astype(scale.dtype)
    return dx, dscale


_rms_norm_fused.defvjp(_rms_norm_fwd, _rms_norm_bwd)


@jax.named_scope(scopes.NORM)
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    if LEGACY_NORM:
        return _rms_norm_legacy(x, scale, eps)
    # Perf iteration A1b/A1c (the A1 custom-vjp variant was REFUTED — its
    # residuals defeat the scan-level remat; see EXPERIMENTS.md §Perf):
    # variance as a self-dot with f32 OUTPUT but bf16 operands — the dot
    # transpose rule keeps the backward cotangent in the compute dtype, so
    # neither pass materializes an f32 copy of the residual stream.
    var = (
        jnp.einsum("...d,...d->...", x, x, preferred_element_type=jnp.float32)
        / x.shape[-1]
    )[..., None]
    inv = lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + scale.astype(x.dtype))


def _rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(
    x: jnp.ndarray,  # (B, S, n, head_dim)
    positions: jnp.ndarray,  # (S,) or scalar-broadcastable int32
    theta: float,
) -> jnp.ndarray:
    hd = x.shape[-1]
    freqs = jnp.asarray(_rope_freqs(hd, theta), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (S, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, blocked prefill + cached decode)
# ---------------------------------------------------------------------------


def _flash_sharded(q, k, v, cfg: ModelConfig):
    """Fused flash-attention (perf iteration D): batch over dp, QUERY sequence
    over tp (each model-axis rank owns S/tp query rows against the full K/V,
    with absolute positions keeping causality exact). Score tiles never leave
    VMEM; causal KV blocks are skipped in-kernel. Forward-only — used on the
    prefill path. q arrives pre-scaled (sm_scale=1)."""
    from repro.kernels.flash_attention import flash_attention_pallas

    b, s, kv, g, hd = q.shape
    qh = q.reshape(b, s, kv * g, hd).transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    interpret = jax.default_backend() != "tpu"
    call = functools.partial(
        flash_attention_pallas, causal=True, sm_scale=1.0, interpret=interpret
    )

    if ACT_RULES is not None and "mesh" in ACT_RULES:
        from jax.sharding import PartitionSpec as P

        mesh = ACT_RULES["mesh"]
        dp_axes, dp_size = ACT_RULES["dp"]
        tp_axes, tp_size = ACT_RULES["tp"]
        bspec = (dp_axes if len(dp_axes) > 1 else dp_axes[0]) if b % dp_size == 0 else None
        sspec = (tp_axes if len(tp_axes) > 1 else tp_axes[0]) if s % (tp_size * 128) == 0 else None

        def fn(qs, ks, vs):
            s_loc = qs.shape[2]
            if sspec is not None:
                off = lax.axis_index(tp_axes if len(tp_axes) > 1 else tp_axes[0]) * s_loc
            else:
                off = 0
            pos = off + jnp.arange(s_loc, dtype=jnp.int32)
            return call(qs, ks, vs, pos)

        out = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(
                P(bspec, None, sspec, None),
                P(bspec, None, None, None),
                P(bspec, None, None, None),
            ),
            out_specs=P(bspec, None, sspec, None),
            check_vma=False,
        )(qh, kh, vh)
    else:
        out = call(qh, kh, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, s, kv, g, hd)


def init_attention(key, cfg: ModelConfig, n_layers: int):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = pdtype(cfg)
    s = lambda fan_in: 1.0 / np.sqrt(fan_in)
    p = {
        "wq": jax.random.normal(ks[0], (n_layers, d, h * hd), dt) * s(d),
        "wk": jax.random.normal(ks[1], (n_layers, d, kv * hd), dt) * s(d),
        "wv": jax.random.normal(ks[2], (n_layers, d, kv * hd), dt) * s(d),
        "wo": jax.random.normal(ks[3], (n_layers, h * hd, d), dt) * s(h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_layers, h * hd), dt)
        p["bk"] = jnp.zeros((n_layers, kv * hd), dt)
        p["bv"] = jnp.zeros((n_layers, kv * hd), dt)
    return p


def _blocked_sdpa(
    q: jnp.ndarray,  # (B, Sq, K, G, hd) f32-scaled
    k: jnp.ndarray,  # (B, Sk, K, hd)
    v: jnp.ndarray,  # (B, Sk, K, hd)
    q_pos: jnp.ndarray,  # (Sq,) absolute positions of queries
    k_pos: jnp.ndarray,  # (Sk,) absolute positions of keys
    chunk: int,
    window: Optional[int],
) -> jnp.ndarray:
    b, sq, kh, g, hd = q.shape
    sk = k.shape[1]
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:  # pad keys; sentinel positions never pass the causal mask
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.concatenate(
            [k_pos, jnp.full((pad,), 1 << 30, k_pos.dtype)]
        )
        sk += pad
    n_chunks = sk // chunk

    kc = k.reshape(b, n_chunks, chunk, kh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kh, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)

    # scores/probabilities materialize in the compute dtype (bf16 on TPU);
    # the online-softmax statistics (m, l) and output accumulator stay f32
    # (perf iteration A2, EXPERIMENTS.md §Perf); REPRO_LEGACY_NORM=1 restores
    # the v1 f32 score path for baseline measurement
    sdt = jnp.float32 if LEGACY_NORM else q.dtype

    def step(carry, xs):
        m, l, acc = carry
        kci, vci, pci = xs
        s = jnp.einsum(
            "bqkgd,bckd->bqkgc", q, kci, preferred_element_type=jnp.float32
        )
        mask = pci[None, None, None, None, :] <= q_pos[None, :, None, None, None]
        if window is not None:
            mask &= pci[None, None, None, None, :] > (
                q_pos[None, :, None, None, None] - window
            )
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = (jnp.exp(s - m_new[..., None]) * mask.astype(jnp.float32)).astype(sdt)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, dtype=jnp.float32)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vci, preferred_element_type=jnp.float32
        )
        return (m_new, l, acc), None

    m0 = jnp.full((b, sq, kh, g), _NEG, jnp.float32)
    l0 = jnp.zeros((b, sq, kh, g), jnp.float32)
    a0 = jnp.zeros((b, sq, kh, g, hd), jnp.float32)
    # Perf iteration A3: remat each KV-chunk step — the backward pass
    # recomputes the (B,Sq,K,G,chunk) score tile instead of saving a stacked
    # copy per chunk (flash-attention-style memory behavior in pure XLA)
    step_fn = step if LEGACY_NORM else jax.checkpoint(step)
    (m, l, acc), _ = lax.scan(step_fn, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out


@jax.named_scope(scopes.ATTENTION)
def attention(
    p: dict,
    x: jnp.ndarray,  # (B, S, D)
    cfg: ModelConfig,
    positions: jnp.ndarray,  # (S,)
    cache: Optional[dict] = None,  # populated by prefill when serving
):
    """Full-sequence (training / prefill) GQA attention. Returns (out, cache)."""
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    cim = cfg.cim

    q = constrain(dense(x, p["wq"], p.get("bq"), cim), ("dp", None, "tp")).reshape(b, s, h, hd)
    k = constrain(dense(x, p["wk"], p.get("bk"), cim), ("dp", None, "tp")).reshape(b, s, kv, hd)
    v = constrain(dense(x, p["wv"], p.get("bv"), cim), ("dp", None, "tp")).reshape(b, s, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = q.reshape(b, s, kv, g, hd) / np.sqrt(hd)

    if cfg.attn_impl == "flash" and cfg.sliding_window is None:
        out = _flash_sharded(q, k, v, cfg)  # perf iteration D (fwd-only path)
    else:
        out = _blocked_sdpa(
            q, k, v, positions, positions, cfg.attn_chunk, cfg.sliding_window
        )
    out = out.astype(x.dtype).reshape(b, s, h * hd)
    out = constrain(out, ("dp", None, "tp"))
    y = constrain(dense(out, p["wo"], None, cim), ("dp", None, None))
    if cache is None:
        return y, None
    return y, _write_prefill_cache(cache, k, v, positions)


@jax.named_scope(scopes.KV_CACHE)
def _write_prefill_cache(cache: dict, k, v, positions) -> dict:
    s, sc = k.shape[1], cache["k"].shape[1]
    if cache["k"].dtype == jnp.int8:
        # int8 KV cache: per-kv-head symmetric scales computed at prefill
        k_scale = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=(0, 1, 3)) / 127.0
        v_scale = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=(0, 1, 3)) / 127.0
        k_scale = jnp.maximum(k_scale, 1e-8)
        v_scale = jnp.maximum(v_scale, 1e-8)
        kq = jnp.clip(jnp.round(k.astype(jnp.float32) / k_scale[None, None, :, None]), -127, 127)
        vq = jnp.clip(jnp.round(v.astype(jnp.float32) / v_scale[None, None, :, None]), -127, 127)
        k, v = kq.astype(jnp.int8), vq.astype(jnp.int8)
        scales = {"k_scale": k_scale, "v_scale": v_scale}
    else:
        scales = {}
    if s <= sc:  # prefix fits: write at the front
        return {
            "k": lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
            ),
            "v": lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
            ),
            "pos": lax.dynamic_update_slice(
                cache["pos"], positions.astype(jnp.int32), (0,)
            ),
            **scales,
        }
    # window cache: keep last sc keys, ring-rotated (slot = pos % sc)
    shift = (s - sc) % sc
    return {
        "k": jnp.roll(k[:, -sc:].astype(cache["k"].dtype), shift, axis=1),
        "v": jnp.roll(v[:, -sc:].astype(cache["v"].dtype), shift, axis=1),
        "pos": jnp.roll(positions[-sc:].astype(jnp.int32), shift),
        **scales,
    }


@jax.named_scope(scopes.ATTENTION)
def decode_attention(
    p: dict,
    x: jnp.ndarray,  # (B, 1, D)
    cfg: ModelConfig,
    pos: jnp.ndarray,  # scalar int32 — current absolute position
    cache: dict,  # {"k": (B, Sc, KV, hd), "v": ..., "pos": (Sc,)}
):
    """Single-token cached decode. The KV cache seq dim may be sharded
    (sequence parallelism); scores reduce via SPMD-inserted collectives."""
    b, _, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    cim = cfg.cim

    q = dense(x, p["wq"], p.get("bq"), cim).reshape(b, 1, h, hd)
    k = dense(x, p["wk"], p.get("bk"), cim).reshape(b, 1, kv, hd)
    v = dense(x, p["wv"], p.get("bv"), cim).reshape(b, 1, kv, hd)
    q = apply_rope(q, pos[None], cfg.rope_theta)
    k = apply_rope(k, pos[None], cfg.rope_theta)

    sc = cache["k"].shape[1]
    slot = pos % sc  # ring buffer when window-capped, linear otherwise
    int8_kv = cache["k"].dtype == jnp.int8
    if int8_kv:
        ks, vs = cache["k_scale"], cache["v_scale"]  # (KV,)
    with jax.named_scope(scopes.KV_CACHE):
        if int8_kv:
            k_w = jnp.clip(
                jnp.round(k.astype(jnp.float32) / jnp.maximum(ks, 1e-8)[None, None, :, None]),
                -127, 127,
            ).astype(jnp.int8)
            v_w = jnp.clip(
                jnp.round(v.astype(jnp.float32) / jnp.maximum(vs, 1e-8)[None, None, :, None]),
                -127, 127,
            ).astype(jnp.int8)
        else:
            k_w, v_w = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        ck = lax.dynamic_update_slice(cache["k"], k_w, (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v_w, (0, slot, 0, 0))
        cpos = lax.dynamic_update_slice(cache["pos"], pos[None].astype(jnp.int32), (slot,))

    valid = (cpos <= pos) & (cpos >= 0)
    if cfg.sliding_window is not None:
        valid &= cpos > pos - cfg.sliding_window

    if int8_kv:
        # integer score dot: q dynamically quantized per kv-head; the cache is
        # read at s8 — this is the MXU analogue of the paper's in-memory
        # integer product-sum (perf iteration C2)
        qh = q.reshape(b, 1, kv, g, hd).astype(jnp.float32) / np.sqrt(hd)
        sq = jnp.max(jnp.abs(qh), axis=(0, 1, 3, 4)) / 127.0  # (KV,)
        sq = jnp.maximum(sq, 1e-8)
        q_i8 = jnp.clip(
            jnp.round(qh / sq[None, None, :, None, None]), -127, 127
        ).astype(jnp.int8)
        s_i32 = jnp.einsum(
            "bqkgd,bckd->bqkgc", q_i8, ck, preferred_element_type=jnp.int32
        )
        s = s_i32.astype(jnp.float32) * (sq * ks)[None, None, :, None, None]
        s = jnp.where(valid[None, None, None, None, :], s, _NEG)
        m = s.max(axis=-1, keepdims=True)
        pattn = jnp.exp(s - m) * valid[None, None, None, None, :].astype(jnp.float32)
        # probabilities quantized to u8-equivalent s8 so the V read stays s8
        p_i8 = jnp.clip(jnp.round(pattn * 127.0), 0, 127).astype(jnp.int8)
        o_i32 = jnp.einsum(
            "bqkgc,bckd->bqkgd", p_i8, cv, preferred_element_type=jnp.int32
        )
        out = o_i32.astype(jnp.float32) * (vs / 127.0)[None, None, :, None, None]
        out = out / jnp.maximum(pattn.sum(-1)[..., None], 1e-30)
    else:
        qf = q.reshape(b, 1, kv, g, hd).astype(jnp.float32) / np.sqrt(hd)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf, ck.astype(jnp.float32))
        s = jnp.where(valid[None, None, None, None, :], s, _NEG)
        m = s.max(axis=-1, keepdims=True)
        pattn = jnp.exp(s - m)
        pattn = pattn * valid[None, None, None, None, :].astype(jnp.float32)
        out = jnp.einsum("bqkgc,bckd->bqkgd", pattn, cv.astype(jnp.float32))
        out = out / jnp.maximum(pattn.sum(-1)[..., None], 1e-30)
    out = out.astype(x.dtype).reshape(b, 1, h * hd)
    y = constrain(dense(out, p["wo"], None, cim), ("dp", None, None))
    new_cache = {"k": ck, "v": cv, "pos": cpos}
    if int8_kv:
        new_cache["k_scale"] = ks
        new_cache["v_scale"] = vs
    return y, new_cache


@jax.named_scope(scopes.KV_CACHE)
def make_attn_cache(cfg: ModelConfig, batch: int, seq_len: int, n_layers: int):
    """Preallocated KV cache (seq capped to the sliding window if set).

    ``cfg.kv_quant_int8`` stores K/V as int8 with per-(layer, kv-head) scales
    — the paper's low-precision-digitization insight applied to the serving
    cache (perf iteration C2): HBM cache traffic halves vs bf16."""
    sc = seq_len if cfg.sliding_window is None else min(seq_len, cfg.sliding_window)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    dt = jnp.int8 if cfg.kv_quant_int8 else cdtype(cfg)
    cache = {
        "k": jnp.zeros((n_layers, batch, sc, kv, hd), dt),
        "v": jnp.zeros((n_layers, batch, sc, kv, hd), dt),
        "pos": jnp.full((n_layers, sc), -1, jnp.int32),
    }
    if cfg.kv_quant_int8:
        cache["k_scale"] = jnp.full((n_layers, kv), 1e-2, jnp.float32)
        cache["v_scale"] = jnp.full((n_layers, kv), 1e-2, jnp.float32)
    return cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, n_layers: int, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = pdtype(cfg)
    return {
        "w_gate": jax.random.normal(ks[0], (n_layers, d, f), dt) / np.sqrt(d),
        "w_up": jax.random.normal(ks[1], (n_layers, d, f), dt) / np.sqrt(d),
        "w_down": jax.random.normal(ks[2], (n_layers, f, d), dt) / np.sqrt(f),
    }


@jax.named_scope(scopes.MLP)
def mlp(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    cim = cfg.cim
    sh = ("dp", None, "tp") if x.ndim == 3 else ("dp", "tp")
    gate = constrain(dense(x, p["w_gate"], None, cim), sh)
    up = constrain(dense(x, p["w_up"], None, cim), sh)
    out = dense(jax.nn.silu(gate) * up, p["w_down"], None, cim)
    return constrain(out, ("dp",) + (None,) * (x.ndim - 1))


# ---------------------------------------------------------------------------
# Embedding + chunked softmax cross-entropy
# ---------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig):
    v, d = cfg.padded_vocab, cfg.d_model
    k1, k2 = jax.random.split(key)
    dt = pdtype(cfg)
    p = {"tok": jax.random.normal(k1, (v, d), dt) * 0.02}
    if not cfg.tie_embeddings:
        p["unembed"] = jax.random.normal(k2, (d, v), dt) / np.sqrt(d)
    return p


@jax.named_scope(scopes.EMBED)
def embed(p: dict, tokens_or_x: jnp.ndarray, cfg: ModelConfig):
    if cfg.input_kind == "embeddings":
        return constrain(tokens_or_x.astype(cdtype(cfg)), ("dp", None, None))
    out = p["tok"][tokens_or_x].astype(cdtype(cfg))
    return constrain(out, ("dp", None, None))


def unembed_weight(p: dict, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return p["tok"].T
    return p["unembed"]


@jax.named_scope(scopes.LM_HEAD)
def chunked_xent(
    p: dict,
    h: jnp.ndarray,  # (B, S, D) final hidden states
    labels: jnp.ndarray,  # (B, S) int32, -1 = ignore
    cfg: ModelConfig,
) -> jnp.ndarray:
    """Mean next-token cross-entropy without materializing (B, S, V) logits.

    Scans the sequence in ``cfg.loss_chunk`` slices; each slice's logits are
    rematerialized in the backward pass (jax.checkpoint)."""
    w = unembed_weight(p, cfg)
    b, s, d = h.shape
    c = min(cfg.loss_chunk, s)
    assert s % c == 0, "pad sequence to a loss_chunk multiple"
    n = s // c
    hc = h.reshape(b, n, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, c).transpose(1, 0, 2)
    vmask = (jnp.arange(cfg.padded_vocab) < cfg.vocab).astype(jnp.float32)

    @jax.checkpoint
    def chunk_loss(hi, li):
        logits = (hi @ w.astype(hi.dtype)).astype(jnp.float32)
        logits = constrain(logits, ("dp", None, "tp"))
        logits = logits + (vmask - 1.0) * 1e9  # mask padded vocab
        lse = jax.nn.logsumexp(logits, axis=-1)
        li_safe = jnp.maximum(li, 0)
        picked = jnp.take_along_axis(logits, li_safe[..., None], axis=-1)[..., 0]
        valid = (li >= 0).astype(jnp.float32)
        return ((lse - picked) * valid).sum(), valid.sum()

    def step(carry, xs):
        tot, cnt = carry
        l, v = chunk_loss(*xs)
        return (tot + l, cnt + v), None

    (tot, cnt), _ = lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


@jax.named_scope(scopes.LM_HEAD)
def logits_step(p: dict, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Decode-step logits (B, 1, V): direct matmul, vocab sharded over TP."""
    w = unembed_weight(p, cfg)
    logits = (h @ w.astype(h.dtype)).astype(jnp.float32)
    logits = constrain(logits, ("dp", None, "tp"))
    vmask = (jnp.arange(cfg.padded_vocab) < cfg.vocab).astype(jnp.float32)
    return logits + (vmask - 1.0) * 1e9
