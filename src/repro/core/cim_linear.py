"""CiM-quantized matmul / linear layer — the paper's technique as a framework op.

A matmul ``y = x @ w`` is mapped onto bit-plane compute-in-SRAM arrays:
the reduction dimension K is split into tiles of ``rows`` (one CiM array's
word lines each); activations/weights are quantized to ``a_bits``/``w_bits``
and bit-sliced; every (input-plane × weight-plane) product-sum is computed in
the charge domain as an analog MAV and digitized by the *memory-immersed ADC*
of a proximal array (core.adc); the B-bit codes are recombined digitally with
signed powers of two and the per-tile partial sums are accumulated.

Three fidelity modes:

  * ``exact``      — plain matmul (no CiM). Baseline / training default.
  * ``bitplane``   — faithful per-plane simulation (A·W plane pairs, per-plane
                     ADC with the full noise model). Exactly equals the integer
                     matmul when the ADC resolves the row count
                     (2^adc_bits >= 2·rows, as on the 16-row, 5-bit chip).
  * ``fake_quant`` — fast vectorized surrogate: integer per-tile partial sums
                     passed through an RMS-equivalent composite quantizer
                     (single matmul; used for large-model inference and QAT).

``ste=True`` wraps the quantized output in a straight-through estimator so the
op is trainable (QAT).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search_tree as st
from repro.core.adc import (
    ADCConfig,
    ADCResult,
    convert,
    dequantize,
    make_reference_ladder,
)
from repro.core.cim_array import bit_planes, plane_weights
from repro.core.mav_stats import analytic_code_pmf
from repro.obs import scopes

__all__ = ["CiMConfig", "CimStats", "cim_matmul", "cim_linear", "quantize_symmetric"]


@dataclasses.dataclass(frozen=True)
class CiMConfig:
    """Static configuration of the CiM mapping for one linear layer."""

    mode: str = "fake_quant"  # exact | fake_quant | bitplane
    a_bits: int = 8
    w_bits: int = 8
    adc_bits: int = 5
    rows: int = 16  # word lines per CiM array (reduction-tile size)
    a_signed: bool = True  # post-ReLU activations may use unsigned planes
    w_signed: bool = True
    search: str = "sar"  # sar | sar_asym — affects cost accounting (+codes under noise)
    comparator_sigma: float = 0.0
    ref_mismatch_sigma: float = 0.0
    ste: bool = True  # straight-through estimator (QAT)
    exact_counts: bool = False  # round reconstructed counts to integers

    def __post_init__(self):
        if self.mode not in ("exact", "fake_quant", "bitplane", "int8_dot"):
            raise ValueError(f"unknown CiM mode {self.mode!r}")

    def adc_config(self) -> ADCConfig:
        return ADCConfig(
            bits=self.adc_bits,
            n_ref_columns=max(32, 1 << self.adc_bits),
            comparator_sigma=self.comparator_sigma,
            ref_mismatch_sigma=self.ref_mismatch_sigma,
            mode="sar_asym" if self.search == "sar_asym" else "sar",
        )

    def search_tree(self) -> st.TreeTables:
        if self.search == "sar_asym":
            pmf = analytic_code_pmf(self.rows, self.adc_bits)
            return st.optimal_tree(pmf)
        return st.symmetric_tree(self.adc_bits)


class CimStats(NamedTuple):
    conversions: jnp.ndarray  # total ADC conversions performed
    comparisons: jnp.ndarray  # total comparator firings (energy proxy)


def quantize_symmetric(
    x: jnp.ndarray, bits: int, signed: bool, per_axis: Optional[int] = None
):
    """Uniform symmetric quantization. Returns (x_int float32, scale)."""
    if per_axis is not None:
        red = tuple(i for i in range(x.ndim) if i != per_axis % x.ndim)
        absmax = jnp.max(jnp.abs(x) if signed else jnp.maximum(x, 0), axis=red, keepdims=True)
    else:
        absmax = jnp.max(jnp.abs(x) if signed else jnp.maximum(x, 0))
    qmax = (1 << (bits - 1)) - 1 if signed else (1 << bits) - 1
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    lo = -qmax - 1 if signed else 0
    x_int = jnp.clip(jnp.round(x / scale), lo, qmax)
    return x_int, scale


# ---------------------------------------------------------------------------
# Faithful bit-plane path
# ---------------------------------------------------------------------------


def _pad_reduction(x_int, w_int, rows):
    k = x_int.shape[-1]
    pad = (-k) % rows
    if pad:
        x_int = jnp.pad(x_int, ((0, 0), (0, pad)))
        w_int = jnp.pad(w_int, ((0, pad), (0, 0)))
    return x_int, w_int, (k + pad) // rows


def _bitplane_matmul(x_int, w_int, cfg: CiMConfig, key, row_offset=0):
    """x_int (M,K) @ w_int (K,N) through per-plane CiM arrays + in-memory ADC.

    ``row_offset`` is the global index of ``x_int``'s first row. With a key,
    comparator noise is drawn PER ROW from ``fold_in(cmp_key, row_offset+i)``
    (the mismatch ladder stays shared — the reference DAC is one physical
    array), so a row's draws depend only on its global row index: never on
    the total batch shape, and never on which data shard executes it. That
    row-shape invariance is what lets a zero-padded bucketed batch
    (``fabric.autotune``) stay bit-exact to the unpadded run row by row.

    Returns (y_int float32 (M,N), CimStats).
    """
    m, _ = x_int.shape
    n = w_int.shape[1]
    r = cfg.rows
    x_int, w_int, t = _pad_reduction(x_int, w_int, r)

    xb = bit_planes(x_int, cfg.a_bits, cfg.a_signed)  # (A, M, K)
    wb = bit_planes(w_int, cfg.w_bits, cfg.w_signed)  # (W, K, N)
    xb = xb.reshape(cfg.a_bits, m, t, r).astype(jnp.float32)
    wb = wb.reshape(cfg.w_bits, t, r, n).astype(jnp.float32)

    # analog MAV of every (plane_a, plane_w, tile): (A, W, M, T, N) in [0,1]
    with jax.named_scope(scopes.CIM_MAC):
        mav = jnp.einsum("amtr,btrn->abmtn", xb, wb) / r
    # half-LSB bias (standard comparator/DAC offset) so the discrete MAV
    # levels k/R sit mid-bin instead of exactly on code boundaries — without
    # it, arbitrarily small comparator noise flips boundary codes at p=0.5
    mav = mav + 0.5 / (1 << cfg.adc_bits)

    adc_cfg = cfg.adc_config()
    tree = cfg.search_tree()
    with jax.named_scope(scopes.CIM_ADC):
        if key is None:
            res: ADCResult = convert(mav, adc_cfg, key=None, tree=tree)
        else:
            mismatch_key, cmp_key = jax.random.split(key)
            ladder = make_reference_ladder(adc_cfg, mismatch_key)
            row_ids = jnp.asarray(row_offset, jnp.int32) + jnp.arange(m, dtype=jnp.int32)
            row_keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(cmp_key, row_ids)
            res = jax.vmap(
                lambda v_row, k_row: convert(
                    v_row, adc_cfg, key=k_row, tree=tree, ladder=ladder
                ),
                in_axes=(2, 0),
                out_axes=2,
            )(mav, row_keys)
    # floor reconstruction: digital output is the raw code scaled by one LSB,
    # zero-bias on empty tiles and exact whenever 2^adc_bits >= 2*rows
    v_hat = res.codes.astype(jnp.float32) / (1 << cfg.adc_bits) * adc_cfg.vdd
    counts = v_hat * r  # reconstructed per-array discharge counts
    if cfg.exact_counts:
        counts = jnp.round(counts)

    wa = jnp.asarray(plane_weights(cfg.a_bits, cfg.a_signed), jnp.float32)
    ww = jnp.asarray(plane_weights(cfg.w_bits, cfg.w_signed), jnp.float32)
    with jax.named_scope(scopes.CIM_MAC):
        y_int = jnp.einsum("abmtn,a,b->mn", counts, wa, ww)
    stats = CimStats(
        conversions=jnp.asarray(mav.size, jnp.int32),
        comparisons=res.comparisons.astype(jnp.float32).sum().astype(jnp.int32),
    )
    return y_int, stats


# ---------------------------------------------------------------------------
# Fast fake-quant surrogate
# ---------------------------------------------------------------------------


def _fake_quant_matmul(x_int, w_int, cfg: CiMConfig):
    """Integer per-tile partial sums + RMS-equivalent composite quantizer.

    Each plane-pair's count is independently quantized with step R/2^B; the
    equivalent single quantizer on the composite tile partial sum uses the
    RMS combination of the plane recombination weights.
    """
    m, _ = x_int.shape
    n = w_int.shape[1]
    r = cfg.rows
    x_int, w_int, t = _pad_reduction(x_int, w_int, r)
    xt = x_int.reshape(m, t, r)
    wt = w_int.reshape(t, r, n)
    wa = plane_weights(cfg.a_bits, cfg.a_signed)
    ww = plane_weights(cfg.w_bits, cfg.w_signed)
    rms = float(np.sqrt((wa**2).sum()) * np.sqrt((ww**2).sum()))
    step = (r / (1 << cfg.adc_bits)) * rms
    with jax.named_scope(scopes.CIM_TILES):
        partial = jnp.einsum("mtr,trn->mtn", xt, wt)  # (M, T, N) integer-valued
        q = jnp.round(partial / step) * step
        return q.sum(axis=1), step


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------


def cim_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    cfg: CiMConfig,
    key: Optional[jax.Array] = None,
    return_stats: bool = False,
):
    """``y = x @ w`` through the CiM + memory-immersed-ADC pipeline.

    ``x``: (..., K); ``w``: (K, N). Leading dims of x are flattened.
    """
    if cfg.mode == "exact":
        y = x @ w
        if return_stats:
            z = jnp.zeros((), jnp.int32)
            return y, CimStats(z, z)
        return y

    if cfg.mode == "int8_dot":
        # TPU-native adaptation of the paper's low-precision digitization:
        # integer product-sums on the MXU (s8 x s8 -> s32), per-channel
        # weight scales — the serving path's HBM reads are int8 end-to-end
        # (perf iteration C1, EXPERIMENTS.md §Perf).
        batch_shape = x.shape[:-1]
        xm = x.reshape(-1, x.shape[-1])
        x_int, sx = quantize_symmetric(xm, 8, True)
        w_int, sw = quantize_symmetric(w, 8, True, per_axis=-1)
        y_i32 = jax.lax.dot_general(
            x_int.astype(jnp.int8),
            w_int.astype(jnp.int8),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        y_q = y_i32.astype(jnp.float32) * sx * sw
        if cfg.ste:
            y_lin = xm @ w
            y_q = y_lin + jax.lax.stop_gradient(y_q.astype(y_lin.dtype) - y_lin)
        y = y_q.reshape(*batch_shape, w.shape[1]).astype(x.dtype)
        if return_stats:
            z = jnp.zeros((), jnp.int32)
            return y, CimStats(z, z)
        return y

    batch_shape = x.shape[:-1]
    k = x.shape[-1]
    xm = x.reshape(-1, k)

    with jax.named_scope(scopes.CIM_QUANTIZE):
        x_int, sx = quantize_symmetric(xm, cfg.a_bits, cfg.a_signed)
        w_int, sw = quantize_symmetric(w, cfg.w_bits, cfg.w_signed, per_axis=-1)

    stats = None
    if cfg.mode == "bitplane":
        y_int, stats = _bitplane_matmul(x_int, w_int, cfg, key)
    else:
        y_int, _ = _fake_quant_matmul(x_int, w_int, cfg)
    y_q = y_int * sx * sw  # sw broadcasts (1, N)

    if cfg.ste:
        with jax.named_scope(scopes.CIM_STE):
            y_lin = xm @ w
            y_q = y_lin + jax.lax.stop_gradient(y_q - y_lin)

    y = y_q.reshape(*batch_shape, w.shape[1])
    if return_stats:
        if stats is None:
            z = jnp.zeros((), jnp.int32)
            stats = CimStats(z, z)
        return y, stats
    return y


def cim_linear(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    cfg: Optional[CiMConfig] = None,
    key: Optional[jax.Array] = None,
):
    """Linear layer front-end used by the model zoo."""
    if cfg is None or cfg.mode == "exact":
        y = x @ w
    else:
        y = cim_matmul(x, w, cfg, key=key)
    if bias is not None:
        y = y + bias
    return y


def digitization_stats(cfg: CiMConfig, m: int, k: int, n: int) -> dict:
    """Analytic per-matmul digitization cost (conversions, expected
    comparisons) for the configured search under the Binomial MAV model."""
    t = -(-k // cfg.rows)
    conversions = cfg.a_bits * cfg.w_bits * m * t * n
    pmf = analytic_code_pmf(cfg.rows, cfg.adc_bits)
    tree = cfg.search_tree()
    e_cmp = tree.expected_depth(pmf)
    return {
        "conversions": conversions,
        "expected_comparisons_per_conversion": e_cmp,
        "total_comparisons": conversions * e_cmp,
    }
