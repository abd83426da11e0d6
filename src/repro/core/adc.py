"""Memory-immersed ADC transfer functions (JAX).

Behavioral models of the paper's SRAM-immersed digitization modes:

  * ``sar``      — successive approximation via the neighbor array's capacitive
                   DAC (symmetric balanced search, ``bits`` comparisons).
  * ``sar_asym`` — SAR driven by an asymmetric search tree matched to the MAV
                   distribution (paper Fig. 4; ~3.7 comparisons @ 5 bits).
  * ``flash``    — one-to-many coupling: 2^bits - 1 references generated in
                   parallel by proximal arrays (1 cycle).
  * ``hybrid``   — ``flash_bits`` MSBs in one Flash cycle, remaining bits in
                   SAR (optionally asymmetric per-segment trees), paper Fig. 3.
  * ``ideal``    — noiseless quantizer (oracle).

Non-idealities modeled: input-referred comparator noise (rms volts, fresh per
comparison), unit-capacitor mismatch of the memory-immersed capacitive DAC
(relative sigma; produces DNL/INL as in paper Fig. 6), and frequency/voltage
dependent noise injected via ``core.noise``.

All converters return ``ADCResult(codes, comparisons, cycles)`` where
``comparisons`` counts comparator firings (energy) and ``cycles`` counts
sequential comparison cycles (latency). Noiseless converters are resolved by
one compare against every ladder boundary (``takes_direct_path``); the
search-tree walk runs where comparator noise makes the path matter.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import search_tree as st

__all__ = [
    "ADCConfig",
    "ADCResult",
    "make_reference_ladder",
    "convert",
    "quantize_ideal",
    "dequantize",
    "measure_transfer",
    "dnl_inl",
    "stack_trees",
    "takes_direct_path",
]


@dataclasses.dataclass(frozen=True)
class ADCConfig:
    """Static configuration of one memory-immersed ADC instance."""

    bits: int = 5
    vdd: float = 1.0
    n_ref_columns: int = 32  # unit caps (columns) in the reference array
    comparator_sigma: float = 0.0  # input-referred rms noise [V]
    ref_mismatch_sigma: float = 0.0  # relative unit-cap mismatch sigma
    mode: str = "sar"  # sar | sar_asym | flash | hybrid | ideal
    flash_bits: int = 2  # MSBs resolved in the flash phase of hybrid mode

    def __post_init__(self):
        if self.mode not in ("sar", "sar_asym", "flash", "hybrid", "ideal"):
            raise ValueError(f"unknown ADC mode {self.mode!r}")
        if self.n_ref_columns < (1 << self.bits):
            raise ValueError(
                "reference array must have >= 2^bits columns to generate all "
                f"thresholds (got {self.n_ref_columns} < {1 << self.bits})"
            )
        if self.mode == "hybrid" and not (0 < self.flash_bits < self.bits):
            raise ValueError("hybrid mode needs 0 < flash_bits < bits")

    @property
    def n_codes(self) -> int:
        return 1 << self.bits

    @property
    def lsb(self) -> float:
        return self.vdd / self.n_codes


class ADCResult(NamedTuple):
    codes: jnp.ndarray  # int32, same shape as input voltage
    comparisons: jnp.ndarray  # int32, comparator firings per conversion
    cycles: jnp.ndarray  # int32, sequential cycles per conversion


# ---------------------------------------------------------------------------
# Reference generation (memory-immersed capacitive DAC)
# ---------------------------------------------------------------------------


def make_reference_ladder(
    cfg: ADCConfig, key: Optional[jax.Array] = None
) -> jnp.ndarray:
    """Boundary voltages (2^bits + 1,) produced by the neighbor CiM array.

    Boundary ``t`` precharges ``m = round(t * n_cols / 2^bits)`` of the
    neighbor array's column lines to VDD (rest to GND) and charge-shares:
    ``V = VDD * sum(C_precharged) / sum(C_all)``. Unit-cap mismatch makes the
    ladder non-uniform — the source of DNL/INL in paper Fig. 6.
    """
    n = cfg.n_ref_columns
    if key is not None and cfg.ref_mismatch_sigma > 0.0:
        caps = 1.0 + cfg.ref_mismatch_sigma * jax.random.normal(key, (n,))
        caps = jnp.maximum(caps, 1e-3)
    else:
        caps = jnp.ones((n,))
    csum = jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(caps)])
    if n % cfg.n_codes == 0:
        # m = t * n / 2^bits: a strided slice, which lowers to no gather
        tapped = csum[:: n // cfg.n_codes]
    else:
        m = np.round(np.arange(cfg.n_codes + 1) * n / cfg.n_codes).astype(np.int32)
        tapped = csum[m]
    return cfg.vdd * tapped / csum[n]


# ---------------------------------------------------------------------------
# Ideal quantizer (oracle) and dequantization
# ---------------------------------------------------------------------------


def quantize_ideal(v: jnp.ndarray, bits: int, vdd: float = 1.0) -> jnp.ndarray:
    """Ideal mid-tread staircase: code k covers [k*LSB, (k+1)*LSB)."""
    n = 1 << bits
    return jnp.clip(jnp.floor(v / vdd * n), 0, n - 1).astype(jnp.int32)


def dequantize(codes: jnp.ndarray, bits: int, vdd: float = 1.0) -> jnp.ndarray:
    """Mid-point reconstruction of the code's voltage bin."""
    n = 1 << bits
    return (codes.astype(jnp.float32) + 0.5) * (vdd / n)


# ---------------------------------------------------------------------------
# Tree table helpers
# ---------------------------------------------------------------------------


def _tree_to_jnp(tree: st.TreeTables):
    return (
        jnp.asarray(tree.threshold),
        jnp.asarray(tree.left),
        jnp.asarray(tree.right),
        int(tree.max_depth),
    )


def stack_trees(trees: Sequence[st.TreeTables]):
    """Pad + stack per-segment trees (hybrid fine phase) into (S, n) tables."""
    n_int = max(max(t.threshold.size, 1) for t in trees)
    thr = np.zeros((len(trees), n_int), np.int32)
    left = np.full((len(trees), n_int), -1, np.int32)
    right = np.full((len(trees), n_int), -1, np.int32)
    for s, t in enumerate(trees):
        k = t.threshold.size
        thr[s, :k] = t.threshold
        left[s, :k] = t.left
        right[s, :k] = t.right
    max_depth = max(t.max_depth for t in trees)
    return jnp.asarray(thr), jnp.asarray(left), jnp.asarray(right), max_depth


# ---------------------------------------------------------------------------
# Traversal engine (vectorized, jit-friendly)
# ---------------------------------------------------------------------------


def _traverse(
    v: jnp.ndarray,
    ladder: jnp.ndarray,
    thr: jnp.ndarray,
    left: jnp.ndarray,
    right: jnp.ndarray,
    max_depth: int,
    sigma: float,
    key: Optional[jax.Array],
    boundary_offset: Optional[jnp.ndarray] = None,
    seg: Optional[jnp.ndarray] = None,
):
    """Walk an alphabetic search tree for every element of ``v`` in lockstep,
    comparison ``i`` adding its own draw of comparator noise (``sigma``).

    ``thr/left/right`` are flat ``(n,)`` tables, or ``(S, n)`` segmented tables
    indexed by ``seg`` (hybrid fine phase). ``boundary_offset`` shifts the
    code-boundary index (per element) before the ladder lookup.
    """
    if max_depth == 0:
        z = jnp.zeros(v.shape, jnp.int32)
        return z, z

    ref = jnp.zeros(v.shape, jnp.int32)
    ncmp = jnp.zeros(v.shape, jnp.int32)
    noise = sigma * jax.random.normal(key, (max_depth,) + v.shape)

    segmented = thr.ndim == 2

    def lookup(table, node):
        if segmented:
            return table[seg, node]
        return table[node]

    def body(i, state):
        ref, ncmp = state
        is_internal = ref >= 0
        node = jnp.maximum(ref, 0)
        t = lookup(thr, node)
        if boundary_offset is not None:
            t = t + boundary_offset
        go_right = (v + noise[i]) >= ladder[t]
        nxt = jnp.where(go_right, lookup(right, node), lookup(left, node))
        ref = jnp.where(is_internal, nxt, ref)
        ncmp = ncmp + is_internal.astype(jnp.int32)
        return ref, ncmp

    ref, ncmp = lax.fori_loop(0, max_depth, body, (ref, ncmp))
    codes = -ref - 1
    return codes, ncmp


# ---------------------------------------------------------------------------
# Direct path (noiseless comparators)
# ---------------------------------------------------------------------------


def takes_direct_path(cfg: ADCConfig) -> bool:
    """Whether :func:`convert` resolves ``cfg``'s conversions by counting
    the ladder boundaries each voltage reaches instead of walking the search
    tree: every mode but ``ideal`` with noiseless comparators.

    Noiseless, every comparison is ``v >= ladder[t]`` against a strictly
    increasing ladder, and every search tree is alphabetic, so the leaf any
    walk reaches is the number of inner boundaries ``v`` reaches, and the
    comparisons it took are that leaf's depth.
    """
    return cfg.mode != "ideal" and not cfg.comparator_sigma > 0.0


def _leaf_depths(depth: np.ndarray, fired: jnp.ndarray) -> jnp.ndarray:
    """``depth[code]`` per element, with no gather. ``fired[t-1]`` is
    ``v >= ladder[t]``; the boundaries a voltage reaches are a prefix, so
    ``depth[code] = depth[0] + sum_t fired[t-1] * (depth[t] - depth[t-1])``."""
    depth = np.asarray(depth, np.int32)
    if (depth == depth[0]).all():
        return jnp.full(fired.shape[1:], depth[0], jnp.int32)
    step = np.diff(depth).reshape((-1,) + (1,) * (fired.ndim - 1))
    return depth[0] + jnp.sum(jnp.where(fired, step, 0), axis=0, dtype=jnp.int32)


def _convert_direct(v, cfg: ADCConfig, ladder, tree, fine_trees) -> ADCResult:
    n = cfg.n_codes
    fired = v[None] >= ladder[1:n].reshape((n - 1,) + (1,) * v.ndim)
    codes = jnp.sum(fired, axis=0, dtype=jnp.int32)
    if cfg.mode == "flash":
        cmp = jnp.full(v.shape, n - 1, jnp.int32)
        return ADCResult(codes, cmp, jnp.ones(v.shape, jnp.int32))
    if cfg.mode in ("sar", "sar_asym"):
        ncmp = _leaf_depths(_sar_tree(cfg, tree).depth, fired)
        return ADCResult(codes, ncmp, ncmp)
    # hybrid: every flash comparator fires, then the segment's SAR search
    n_seg = 1 << cfg.flash_bits
    depth = np.concatenate([t.depth for t in _fine_trees(cfg, fine_trees)])
    fine_cmp = _leaf_depths(depth, fired)
    return ADCResult(codes, (n_seg - 1) + fine_cmp, 1 + fine_cmp)


# ---------------------------------------------------------------------------
# Conversion front-ends
# ---------------------------------------------------------------------------


def _sar_tree(cfg: ADCConfig, tree: Optional[st.TreeTables]) -> st.TreeTables:
    return tree if tree is not None else st.symmetric_tree(cfg.bits)


def _fine_trees(cfg: ADCConfig, fine_trees) -> list[st.TreeTables]:
    """The hybrid fine phase's per-segment trees (symmetric by default)."""
    n_seg = 1 << cfg.flash_bits
    if fine_trees is None:
        return [st.symmetric_tree(cfg.bits - cfg.flash_bits)] * n_seg
    if len(fine_trees) != n_seg:
        raise ValueError(f"need {n_seg} fine trees, got {len(fine_trees)}")
    return list(fine_trees)


def convert(
    v: jnp.ndarray,
    cfg: ADCConfig,
    key: Optional[jax.Array] = None,
    tree: Optional[st.TreeTables] = None,
    ladder: Optional[jnp.ndarray] = None,
    fine_trees: Optional[Sequence[st.TreeTables]] = None,
) -> ADCResult:
    """Digitize analog MAV voltages ``v`` under the configured mode.

    ``tree`` supplies the asymmetric search tree for ``sar_asym``;
    ``fine_trees`` optionally supplies 2^flash_bits per-segment asymmetric
    trees for the hybrid fine phase. ``ladder`` overrides reference
    generation (e.g. to reuse one mismatch draw across conversions); the
    ladder must strictly increase, as every generated one does.
    """
    v = jnp.asarray(v)
    mismatch_key = cmp_key = None
    if key is not None:
        mismatch_key, cmp_key = jax.random.split(key)
    if ladder is None:
        ladder = make_reference_ladder(cfg, mismatch_key)

    if cfg.mode == "ideal":
        codes = quantize_ideal(v, cfg.bits, cfg.vdd)
        z = jnp.zeros(v.shape, jnp.int32)
        return ADCResult(codes, z, z)

    if takes_direct_path(cfg):
        return _convert_direct(v, cfg, ladder, tree, fine_trees)
    if cmp_key is None:
        raise ValueError("comparator noise requires a PRNG key")

    if cfg.mode == "flash":
        n = cfg.n_codes
        noise = cfg.comparator_sigma * jax.random.normal(cmp_key, (n - 1,) + v.shape)
        thrs = ladder[1:n]  # boundaries 1..n-1
        fired = (v[None] + noise) >= thrs.reshape((n - 1,) + (1,) * v.ndim)
        codes = fired.sum(axis=0).astype(jnp.int32)
        cmp = jnp.full(v.shape, n - 1, jnp.int32)
        cyc = jnp.ones(v.shape, jnp.int32)
        return ADCResult(codes, cmp, cyc)

    if cfg.mode in ("sar", "sar_asym"):
        thr, left, right, max_depth = _tree_to_jnp(_sar_tree(cfg, tree))
        codes, ncmp = _traverse(
            v, ladder, thr, left, right, max_depth, cfg.comparator_sigma, cmp_key
        )
        return ADCResult(codes, ncmp, ncmp)

    # hybrid: flash on the top flash_bits, then SAR within the segment
    f = cfg.flash_bits
    n_seg = 1 << f
    seg_size = 1 << (cfg.bits - f)
    coarse_boundaries = np.arange(1, n_seg) * seg_size  # ladder indices
    k1, k2 = jax.random.split(cmp_key)
    noise = cfg.comparator_sigma * jax.random.normal(k1, (n_seg - 1,) + v.shape)
    cthr = ladder[jnp.asarray(coarse_boundaries)]
    fired = (v[None] + noise) >= cthr.reshape((n_seg - 1,) + (1,) * v.ndim)
    seg = fired.sum(axis=0).astype(jnp.int32)

    if fine_trees is not None:
        thr, left, right, max_depth = stack_trees(_fine_trees(cfg, fine_trees))
    else:
        t = st.symmetric_tree(cfg.bits - f)
        thr, left, right, max_depth = _tree_to_jnp(t)
    fine_codes, fine_cmp = _traverse(
        v,
        ladder,
        thr,
        left,
        right,
        max_depth,
        cfg.comparator_sigma,
        k2,
        boundary_offset=seg * seg_size,
        seg=seg if fine_trees is not None else None,
    )
    codes = seg * seg_size + fine_codes
    comparisons = (n_seg - 1) + fine_cmp  # every flash comparator fires
    cycles = 1 + fine_cmp  # flash phase is one cycle
    return ADCResult(codes, comparisons, cycles)


# ---------------------------------------------------------------------------
# Static characterization (paper Fig. 6): staircase, DNL, INL
# ---------------------------------------------------------------------------


def measure_transfer(
    cfg: ADCConfig,
    key: Optional[jax.Array] = None,
    n_points: int = 8192,
    tree: Optional[st.TreeTables] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Sweep a voltage ramp; return (ramp voltages, output codes)."""
    ramp = jnp.linspace(0.0, cfg.vdd * (1 - 1e-6), n_points)
    res = convert(ramp, cfg, key=key, tree=tree)
    return np.asarray(ramp), np.asarray(res.codes)


def dnl_inl(
    ramp: np.ndarray, codes: np.ndarray, cfg: ADCConfig
) -> tuple[np.ndarray, np.ndarray]:
    """Differential/integral non-linearity in LSB from a measured staircase."""
    n = cfg.n_codes
    lsb = cfg.lsb
    edges = np.full(n, np.nan)
    for c in range(1, n):
        idx = np.argmax(codes >= c)
        if codes[idx] >= c:
            edges[c] = ramp[idx]
    widths = np.diff(edges[1:])  # widths of codes 1..n-2
    dnl = widths / lsb - 1.0
    ideal_edges = np.arange(1, n) * lsb
    inl = (edges[1:] - ideal_edges) / lsb
    return dnl, inl
