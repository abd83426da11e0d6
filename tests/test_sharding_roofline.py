"""Sharding rules + loop-aware HLO analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch import shardings as sh
from repro.roofline.hlo_stats import analyze


@pytest.fixture(scope="module")
def mesh():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


def test_spec_divisible(mesh):
    with sh.record_fallbacks() as fb:
        spec = sh.spec_for(mesh, (16, 32), ("dp", "tp"), "t")
    assert spec == P("data", "model")
    assert not fb


def test_spec_fallback_records(mesh):
    # a 2-way model axis with an odd dim must record the replication fallback
    dev2 = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh2 = Mesh(dev2, ("data", "model"))
    with sh.record_fallbacks() as fb:
        assert sh.spec_for(mesh2, (7,), ("tp",), "odd") == P(None)
    assert len(fb) == 1 and "odd" in fb[0]
    with sh.record_fallbacks() as fb2:
        assert sh.spec_for(mesh, (16,), ("tp",), "x") == P("model")
    assert not fb2


def test_fallback_recording_is_scoped():
    """Records don't leak across scopes (the old module-global bug) and
    nested recorders both observe inner fallbacks."""
    dev2 = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh2 = Mesh(dev2, ("data", "model"))
    # outside any recorder: nothing to leak into, and no error
    sh.spec_for(mesh2, (7,), ("tp",), "unscoped")
    with sh.record_fallbacks() as outer:
        sh.spec_for(mesh2, (5,), ("tp",), "outer-only")
        with sh.record_fallbacks() as inner:
            sh.spec_for(mesh2, (3,), ("tp",), "both")
        sh.spec_for(mesh2, (9,), ("tp",), "outer-again")
    assert [m.split(":")[0] for m in inner] == ["both"]
    assert [m.split(":")[0] for m in outer] == ["outer-only", "both", "outer-again"]
    # a fresh recorder starts empty — nothing leaked from the calls above
    with sh.record_fallbacks() as fresh:
        pass
    assert fresh == []


def test_param_rules_cover_all_archs(mesh):
    from repro.configs import ARCHS, reduced
    from repro.models import build_model

    for name in sorted(ARCHS):
        cfg = reduced(ARCHS[name])
        model = build_model(cfg)
        sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        shd = sh.param_shardings(mesh, sds, cfg)
        assert len(jax.tree.leaves(shd, is_leaf=lambda x: hasattr(x, "spec"))) == len(
            jax.tree.leaves(sds)
        )


def test_hlo_analyzer_scan_trip_counts():
    def f(w, x):
        def body(c, wl):
            return c @ wl, None
        out, _ = lax.scan(body, x, w)
        return out.sum()

    for L in (3, 9):
        w = jnp.ones((L, 64, 64))
        x = jnp.ones((4, 64))
        hlo = jax.jit(f).lower(w, x).compile().as_text()
        st = analyze(hlo, 1)
        assert st.dot_flops == pytest.approx(2 * 4 * 64 * 64 * L, rel=1e-6)


def test_hlo_analyzer_counts_collectives():
    from repro.roofline.hlo_stats import HloStats

    fake_hlo = """ENTRY %main (p: f32[16]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  ROOT %ar = f32[16]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    st = analyze(fake_hlo, 4)
    # all-reduce: 2*(4-1)/4 * 64 bytes = 96
    assert st.collective_total == pytest.approx(96.0)


def test_cache_shardings_seq_parallel_fallback(mesh):
    """kv heads not divisible -> sequence dim takes the tp axis."""
    from repro.configs import ARCHS
    cfg = ARCHS["command-r-plus-104b"]
    cache_sds = {
        "k": jax.ShapeDtypeStruct((2, 4, 64, 8, 16), jnp.bfloat16),
        "pos": jax.ShapeDtypeStruct((64,), jnp.int32),
    }
    # single-device mesh: everything divides; just check it runs
    shd = sh.cache_shardings(mesh, cache_sds, cfg)
    assert hasattr(shd["k"], "spec")


def test_peaks_are_keyed_by_device_kind():
    from repro.roofline import hw

    assert hw.peaks("TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        hw.peaks("cpu")
