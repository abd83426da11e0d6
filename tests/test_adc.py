"""Memory-immersed ADC: mode equivalence, staircase, DNL/INL (paper Fig. 6)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adc
from repro.core import search_tree as st
from repro.core.mav_stats import analytic_code_pmf


@pytest.fixture(scope="module")
def ramp():
    return jnp.linspace(0.0, 0.999, 4096)


@pytest.mark.parametrize("bits", [3, 5, 6])
@pytest.mark.parametrize("mode", ["sar", "flash"])
def test_modes_match_ideal(ramp, bits, mode):
    cfg = adc.ADCConfig(bits=bits, mode=mode, n_ref_columns=max(32, 1 << bits))
    res = adc.convert(ramp, cfg)
    ideal = adc.quantize_ideal(ramp, bits)
    np.testing.assert_array_equal(np.asarray(res.codes), np.asarray(ideal))


def test_asym_tree_same_codes(ramp):
    """The asymmetric search changes the comparison COUNT, not the codes."""
    pmf = analytic_code_pmf(16, 5)
    tree = st.optimal_tree(pmf)
    cfg = adc.ADCConfig(bits=5, mode="sar_asym")
    res = adc.convert(ramp, cfg, tree=tree)
    ideal = adc.quantize_ideal(ramp, 5)
    np.testing.assert_array_equal(np.asarray(res.codes), np.asarray(ideal))
    # comparisons vary per code and average below 5 under the skewed pmf
    mav_like = jnp.asarray(
        np.random.default_rng(0).binomial(16, 0.25, 20000) / 16.0
    )
    r2 = adc.convert(mav_like, cfg, tree=tree)
    assert float(r2.comparisons.mean()) < 4.0


@pytest.mark.parametrize("flash_bits", [1, 2, 3])
def test_hybrid_codes_and_cycles(ramp, flash_bits):
    cfg = adc.ADCConfig(bits=5, mode="hybrid", flash_bits=flash_bits)
    res = adc.convert(ramp, cfg)
    ideal = adc.quantize_ideal(ramp, 5)
    np.testing.assert_array_equal(np.asarray(res.codes), np.asarray(ideal))
    # latency: 1 flash cycle + (bits - flash_bits) SAR cycles
    assert int(res.cycles.max()) == 1 + (5 - flash_bits)
    # energy: all 2^f - 1 flash comparators fire + SAR comparisons
    assert int(res.comparisons.max()) == (1 << flash_bits) - 1 + (5 - flash_bits)


def test_hybrid_with_asymmetric_fine_trees(ramp):
    """Hybrid + per-segment asymmetric trees (paper §II-C composition)."""
    pmf = analytic_code_pmf(16, 5)
    seg = 1 << 3  # 2 flash bits -> segments of 8 codes
    fine = []
    for s in range(4):
        p = pmf[s * seg : (s + 1) * seg]
        fine.append(st.optimal_tree(p / max(p.sum(), 1e-12)))
    cfg = adc.ADCConfig(bits=5, mode="hybrid", flash_bits=2)
    res = adc.convert(ramp, cfg, fine_trees=fine)
    ideal = adc.quantize_ideal(ramp, 5)
    np.testing.assert_array_equal(np.asarray(res.codes), np.asarray(ideal))


def test_staircase_monotonic_under_mismatch():
    cfg = adc.ADCConfig(bits=5, mode="sar", ref_mismatch_sigma=0.02)
    r, codes = adc.measure_transfer(cfg, key=jax.random.PRNGKey(0))
    assert (np.diff(codes) >= 0).all(), "staircase must stay monotonic"


def test_dnl_inl_zero_without_mismatch():
    cfg = adc.ADCConfig(bits=5, mode="sar")
    r, codes = adc.measure_transfer(cfg, n_points=1 << 14)
    dnl, inl = adc.dnl_inl(r, codes, cfg)
    assert np.nanmax(np.abs(dnl)) < 0.05
    assert np.nanmax(np.abs(inl)) < 0.05


def test_dnl_inl_paper_band():
    """Fig. 6: with the chip's cap matching, DNL/INL stay below 0.5 LSB."""
    cfg = adc.ADCConfig(bits=5, mode="sar", ref_mismatch_sigma=0.01)
    worst_dnl = worst_inl = 0.0
    for seed in range(5):
        r, codes = adc.measure_transfer(
            cfg, key=jax.random.PRNGKey(seed), n_points=1 << 14
        )
        dnl, inl = adc.dnl_inl(r, codes, cfg)
        worst_dnl = max(worst_dnl, np.nanmax(np.abs(dnl)))
        worst_inl = max(worst_inl, np.nanmax(np.abs(inl)))
    assert worst_dnl < 0.5 and worst_inl < 0.5


def test_comparator_noise_degrades_gracefully():
    cfg_clean = adc.ADCConfig(bits=5, mode="sar")
    cfg_noisy = adc.ADCConfig(bits=5, mode="sar", comparator_sigma=0.02)
    v = jax.random.uniform(jax.random.PRNGKey(1), (20000,))
    c0 = adc.convert(v, cfg_clean).codes
    c1 = adc.convert(v, cfg_noisy, key=jax.random.PRNGKey(2)).codes
    err = np.abs(np.asarray(c0) - np.asarray(c1))
    assert err.mean() < 1.5  # noise shifts codes by ~sigma/LSB, not wildly
    assert (err > 0).any()  # but it does perturb


def test_reference_ladder_monotonic():
    for seed in range(4):
        cfg = adc.ADCConfig(bits=5, ref_mismatch_sigma=0.05)
        lad = adc.make_reference_ladder(cfg, jax.random.PRNGKey(seed))
        assert (jnp.diff(lad) > 0).all()
        assert float(lad[0]) == 0.0
        assert float(lad[-1]) == pytest.approx(cfg.vdd)


def test_invalid_configs_raise():
    with pytest.raises(ValueError):
        adc.ADCConfig(bits=6, n_ref_columns=32)  # needs 64 columns
    with pytest.raises(ValueError):
        adc.ADCConfig(mode="nope")
    with pytest.raises(ValueError):
        adc.ADCConfig(mode="hybrid", flash_bits=5, bits=5)


# ---------------------------------------------------------------------------
# noiseless direct path vs a plain walk of the tree tables
# ---------------------------------------------------------------------------


def _numpy_walk(v, ladder, tree, noise=None, offset=0):
    """Walk ``tree``'s flat tables for every element of ``v``, one level at a
    time: comparison ``i`` is ``v + noise[i] >= ladder[threshold[node] +
    offset]``. Returns (codes, comparisons)."""
    v = np.asarray(v, np.float32)
    ladder = np.asarray(ladder, np.float32)
    ref = np.zeros(v.shape, np.int64)
    ncmp = np.zeros(v.shape, np.int64)
    for i in range(int(tree.depth.max())):
        inner = ref >= 0
        node = np.maximum(ref, 0)
        vi = v if noise is None else (v + noise[i]).astype(np.float32)
        right = vi >= ladder[tree.threshold[node] + offset]
        nxt = np.where(right, tree.right[node], tree.left[node])
        ref = np.where(inner, nxt, ref)
        ncmp += inner
    return -ref - 1, ncmp


def _numpy_convert(v, ladder, cfg, tree=None, fine_trees=None, noise=(None, None)):
    """(codes, comparisons, cycles) of a plain conversion: flash counts every
    boundary in one cycle; SAR walks its tree; hybrid counts the coarse
    boundaries ``ladder[s * seg_size]`` (under ``noise[0]``), then walks that
    segment's tree over its own boundaries (under ``noise[1]``)."""
    v = np.asarray(v, np.float32)
    ladder = np.asarray(ladder, np.float32)
    if cfg.mode == "flash":
        n = cfg.n_codes
        codes = (v[None] >= ladder[1:n].reshape((n - 1,) + (1,) * v.ndim)).sum(0)
        return codes, np.full(v.shape, n - 1), np.ones(v.shape)
    if cfg.mode in ("sar", "sar_asym"):
        codes, ncmp = _numpy_walk(v, ladder, tree or st.symmetric_tree(cfg.bits), noise[1])
        return codes, ncmp, ncmp
    n_seg = 1 << cfg.flash_bits
    seg_size = cfg.n_codes // n_seg
    trees = fine_trees or [st.symmetric_tree(cfg.bits - cfg.flash_bits)] * n_seg
    seg = np.zeros(v.shape, np.int64)
    for s in range(1, n_seg):
        vs = v if noise[0] is None else (v + noise[0][s - 1]).astype(np.float32)
        seg += vs >= ladder[s * seg_size]
    codes = np.zeros(v.shape, np.int64)
    ncmp = np.zeros(v.shape, np.int64)
    for s, t in enumerate(trees):
        m = seg == s
        fine_noise = None if noise[1] is None else noise[1][:, m]
        c, k = _numpy_walk(v[m], ladder, t, fine_noise, offset=s * seg_size)
        codes[m] = s * seg_size + c
        ncmp[m] = k
    return codes, (n_seg - 1) + ncmp, 1 + ncmp


def _tree(mode, bits):
    if mode == "sar_asym":
        return st.optimal_tree(analytic_code_pmf(16, bits))
    return st.symmetric_tree(bits)


def _segment_trees(bits, flash_bits):
    """Per-segment optimal trees for the hybrid fine phase."""
    pmf = analytic_code_pmf(16, bits)
    size = 1 << (bits - flash_bits)
    segs = [pmf[s * size : (s + 1) * size] for s in range(1 << flash_bits)]
    return [st.optimal_tree(p / max(p.sum(), 1e-12)) for p in segs]


def _check_noiseless(cfg, mismatch, seed, **trees):
    """Noiseless ``convert`` gives the plain conversion's codes, comparisons
    and cycles exactly: at every discrete MAV level of a 16-row array (with
    the half-LSB bias), on every ladder boundary, below 0 and above vdd."""
    if mismatch:
        cfg = dataclasses.replace(cfg, ref_mismatch_sigma=0.05)
    key = jax.random.PRNGKey(seed) if mismatch else None
    ladder = adc.make_reference_ladder(
        cfg, None if key is None else jax.random.split(key)[0]
    )
    v = np.concatenate([
        np.arange(17) / 16 + 0.5 / cfg.n_codes,
        np.asarray(ladder),
        [-0.25, -1e-6, cfg.vdd, cfg.vdd + cfg.lsb, 1.5 * cfg.vdd],
    ]).astype(np.float32)
    res = adc.convert(jnp.asarray(v), cfg, key=key, **trees)
    codes, ncmp, cycles = _numpy_convert(v, ladder, cfg, **trees)
    assert res.codes.dtype == res.comparisons.dtype == res.cycles.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(res.codes), codes)
    np.testing.assert_array_equal(np.asarray(res.comparisons), ncmp)
    np.testing.assert_array_equal(np.asarray(res.cycles), cycles)


@pytest.mark.parametrize("mismatch", [False, True])
@pytest.mark.parametrize("bits", [4, 5, 6])
@pytest.mark.parametrize("mode", ["sar", "sar_asym", "flash"])
def test_noiseless_convert_matches_tree_walk(mode, bits, mismatch):
    cfg = adc.ADCConfig(bits=bits, mode=mode, n_ref_columns=max(32, 1 << bits))
    trees = {"tree": _tree(mode, bits)} if mode != "flash" else {}
    _check_noiseless(cfg, mismatch, 11 + bits, **trees)


@pytest.mark.parametrize("mismatch", [False, True])
@pytest.mark.parametrize("fine", [False, True])
@pytest.mark.parametrize("flash_bits", [1, 2, 3])
@pytest.mark.parametrize("bits", [4, 5, 6])
def test_noiseless_hybrid_matches_tree_walk(bits, flash_bits, fine, mismatch):
    """The hybrid converter, with symmetric or per-segment optimal fine trees."""
    cfg = adc.ADCConfig(
        bits=bits, mode="hybrid", flash_bits=flash_bits,
        n_ref_columns=max(32, 1 << bits),
    )
    trees = {"fine_trees": _segment_trees(bits, flash_bits)} if fine else {}
    _check_noiseless(cfg, mismatch, 7 * bits + flash_bits, **trees)


@pytest.mark.parametrize("mode", ["sar", "sar_asym", "hybrid"])
def test_noiseless_convert_lowers_without_gather_or_while(mode):
    """At the fabric's tile shape (a_bits, w_bits, M, K/16, 32 columns) the
    noiseless conversion is compares and sums; with comparator noise the
    walk stays (segmented per flash segment for hybrid), and its codes are
    the plain walk's under the same draws."""
    cfg = adc.ADCConfig(bits=5, mode=mode)
    if mode == "hybrid":
        trees = {"fine_trees": _segment_trees(5, cfg.flash_bits)}
        depth = max(t.max_depth for t in trees["fine_trees"])
    else:
        trees = {"tree": _tree(mode, 5)}
        depth = trees["tree"].max_depth
    shape = (4, 4, 4, 36, 32)
    rng = np.random.default_rng(0)
    v = (rng.binomial(16, 0.25, shape) / 16 + 0.5 / 32).astype(np.float32)

    hlo = jax.jit(lambda x: adc.convert(x, cfg, **trees)).lower(v).as_text()
    assert "gather" not in hlo and "while" not in hlo

    noisy = dataclasses.replace(cfg, comparator_sigma=0.02)
    key = jax.random.PRNGKey(5)
    fn = jax.jit(lambda x: adc.convert(x, noisy, key=key, **trees))
    assert "while" in fn.lower(v).as_text()
    res = fn(v)
    cmp_key = jax.random.split(key)[1]
    if mode == "hybrid":
        k1, k2 = jax.random.split(cmp_key)
        n_seg = 1 << cfg.flash_bits
        noise = (
            np.asarray(noisy.comparator_sigma * jax.random.normal(k1, (n_seg - 1,) + shape)),
            np.asarray(noisy.comparator_sigma * jax.random.normal(k2, (depth,) + shape)),
        )
    else:
        noise = (None, np.asarray(
            noisy.comparator_sigma * jax.random.normal(cmp_key, (depth,) + shape)
        ))
    codes, ncmp, cycles = _numpy_convert(
        v, adc.make_reference_ladder(noisy), noisy, noise=noise, **trees
    )
    np.testing.assert_array_equal(np.asarray(res.codes), codes)
    np.testing.assert_array_equal(np.asarray(res.comparisons), ncmp)
    np.testing.assert_array_equal(np.asarray(res.cycles), cycles)
    assert (codes != np.asarray(adc.convert(v, cfg, **trees).codes)).any()
