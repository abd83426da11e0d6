"""Fused causal flash-attention Pallas TPU kernel (GQA-aware).

The §Perf analysis shows dense-LM train/prefill cells are bound by attention
score tiles round-tripping HBM (EXPERIMENTS.md). This kernel keeps the whole
online-softmax pipeline in VMEM — q tiles stream against a VMEM-resident K/V
(per (batch, head) grid cell), score/probability tiles never materialize in
HBM, and causal masking SKIPS fully-masked KV blocks (the dynamic
``fori_loop`` bound), halving attention FLOPs vs the masked-dense scan.

Sequence parallelism: ``q_positions`` carries ABSOLUTE query positions, so a
q-sequence shard (inside shard_map, each tp rank owning S/tp query rows
against the full K/V) masks correctly — this is how launch-time prefill uses
it (models/layers._flash_sharded, perf iteration D).

Scope: Sk·hd·bf16 K/V per (batch, head) must fit VMEM (32k×128 = 8 MiB ✓).
Validated against ``ref.flash_attention_ref`` in interpret mode (tests) and
on a TPU v5e (``chip_smoke.py``); ``tests/test_tpu_compile.py`` compiles it
for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, qpos_ref, o_ref, *, sm_scale, block_q, block_k, causal
):
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (bq, hd)
    sk = k_ref.shape[2]
    n_kv = sk // block_k
    q_pos = qpos_ref[...]  # (bq, 1) absolute positions

    if causal:
        # highest kv block intersecting this q tile's causal triangle
        upper = jnp.minimum(jnp.max(q_pos) // block_k + 1, n_kv)
    else:
        upper = n_kv

    hd = k_ref.shape[3]

    def body(j, carry):
        m, l, acc = carry
        # every index a Slice: bare ints in the tuple break interpret-mode
        # discharge (jax state_discharge expects .shape on non-Slice indices)
        idx = (
            pl.dslice(0, 1),
            pl.dslice(0, 1),
            pl.dslice(j * block_k, block_k),
            pl.dslice(0, hd),
        )
        k = k_ref[idx][0, 0].astype(jnp.float32)
        v = v_ref[idx][0, 0].astype(jnp.float32)
        s = q @ k.T  # (bq, bk)
        if causal:
            k_pos = j * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            mask = k_pos <= q_pos
            s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = p * mask.astype(jnp.float32)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + p @ v
        return m_new, l, acc

    m0 = jnp.full((block_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(0, upper, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (B, H, Sq, hd)
    k: jnp.ndarray,  # (B, KV, Sk, hd)  KV divides H (GQA)
    v: jnp.ndarray,  # (B, KV, Sk, hd)
    q_positions: jnp.ndarray | None = None,  # (Sq,) absolute; default arange
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, sq, hd = q.shape
    kv, sk = k.shape[1], k.shape[2]
    if h % kv:
        raise ValueError("n_heads must be a multiple of n_kv_heads")
    if sq % block_q or sk % block_k:
        raise ValueError("pad Sq/Sk to block multiples")
    g = h // kv
    if sm_scale is None:
        sm_scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.arange(sq, dtype=jnp.int32)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bb, hh, ii: (bb, hh, ii, 0)),
            pl.BlockSpec((1, 1, sk, hd), lambda bb, hh, ii: (bb, hh // g, 0, 0)),
            pl.BlockSpec((1, 1, sk, hd), lambda bb, hh, ii: (bb, hh // g, 0, 0)),
            # positions as an (Sq, 1) column: a 1-D block has no layout
            # Mosaic accepts, and the column needs no in-kernel relayout
            pl.BlockSpec((block_q, 1), lambda bb, hh, ii: (ii, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda bb, hh, ii: (bb, hh, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v, q_positions.astype(jnp.int32).reshape(sq, 1))
