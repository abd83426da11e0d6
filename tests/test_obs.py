"""Observability (``repro.obs``): span/tracer scoping, the metrics
registry and its Prometheus exposition, JSONL sinks, the pinned fallback
reason taxonomy, and — the load-bearing part — the neutrality guarantees:
fused collective censuses and bit-exact outputs must be identical with
observability on or off. ``tests/conftest.py`` forces 8 host devices."""

import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.cim_linear import CiMConfig
from repro.fabric import (
    ChipMeshConfig,
    FabricConfig,
    compile_forward,
    compile_graph_forward,
    link_validation,
    map_matmul,
    resolve_backend,
    shard_placement,
    transformer_graph_weights,
)
from repro.obs import trace as obs_trace

FB = FabricConfig(mode="pair_sar", rows=16, cols=32, n_arrays=8)
NOISY = CiMConfig(
    mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False,
    comparator_sigma=0.05,
)
SHAPES = [("l0", 4, 64, 64), ("l1", 4, 64, 96), ("l2", 4, 96, 32)]


def chain(cm, cim=NOISY, shapes=SHAPES):
    return [
        shard_placement(map_matmul(name, m, k, n, cm.fabric, cim=cim), cm)
        for name, m, k, n in shapes
    ]


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_span_disabled_returns_shared_noop_singleton():
    """Outside any tracing block, span() is the zero-allocation null path."""
    assert not obs.enabled()
    s1 = obs.span("anything", layer="q")
    s2 = obs.span("else")
    assert s1 is s2  # one shared singleton, no per-call allocation
    with s1 as sp:
        sp.set(tiles=4)  # all methods are no-ops
    obs.event("dropped.event", x=1)  # silently dropped


def test_tracing_records_spans_events_and_nesting_composes():
    with obs.tracing() as outer:
        with obs.tracing() as inner:
            with obs.span("fabric.demo", layer="l0") as sp:
                sp.set(backend="sequential")
            obs.event("fabric.fallback", reason="ragged_batch")
        # after the inner block closes, only the outer tracer listens
        obs.event("outer.only")
    for tr in (outer, inner):
        (rec,) = tr.spans
        assert rec["kind"] == "span" and rec["name"] == "fabric.demo"
        assert rec["attrs"] == {"layer": "l0", "backend": "sequential"}
        assert rec["duration_s"] >= 0
    assert [e["name"] for e in inner.events] == ["fabric.fallback"]
    assert [e["name"] for e in outer.events] == ["fabric.fallback", "outer.only"]
    assert not obs.enabled()


def test_span_opens_a_profiler_annotation_only_under_a_tracer(monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with obs.span("serve.fetch"):
        pass
    assert opened == []
    with obs.tracing():
        with obs.span("serve.decode"):
            with obs.span("serve.decode_step", step=0):
                pass
    assert opened == [("enter", "serve.decode"), ("enter", "serve.decode_step"),
                      ("exit", "serve.decode_step"), ("exit", "serve.decode")]


def test_span_records_parent_and_id():
    with obs.tracing() as tr:
        with obs.span("serve.prefill", batch=3):
            with obs.span("serve.make_cache", batch=3):
                pass
            obs.event("inside")
        with obs.span("serve.fetch", batch=3):
            pass
    make, prefill, fetch = tr.spans
    assert prefill["parent"] is None and fetch["parent"] is None
    assert make["parent"] == prefill["id"]
    assert len({make["id"], prefill["id"], fetch["id"]}) == 3
    assert tr.events[0]["parent"] == prefill["id"]
    assert {s["attrs"]["batch"] for s in tr.spans} == {3}


def test_serve_batch_spans_share_the_batch_id():
    from repro.configs import ARCHS, reduced
    from repro.launch.serve import ServeSettings, serve_batch

    cfg = reduced(ARCHS["smollm-135m"], n_layers=1)
    with obs.tracing() as tr:
        serve_batch(cfg, ServeSettings(batch=2, prompt_len=8, gen_len=4))
    names = [s["name"] for s in tr.spans]
    assert names == ["serve.make_cache", "serve.prefill", *["serve.decode_step"] * 3,
                     "serve.decode", "serve.fetch"]
    assert len({s["attrs"]["batch"] for s in tr.spans}) == 1
    by = {s["name"]: s for s in tr.spans}
    assert by["serve.make_cache"]["parent"] == by["serve.prefill"]["id"]
    steps = [s for s in tr.spans if s["name"] == "serve.decode_step"]
    assert [s["attrs"]["step"] for s in steps] == [0, 1, 2]
    assert {s["parent"] for s in steps} == {by["serve.decode"]["id"]}


def test_train_step_spans_hold_the_data_batch(tmp_path):
    from repro.configs import ARCHS, reduced
    from repro.launch.train import TrainSettings, train
    from repro.models import layers

    cfg = reduced(ARCHS["smollm-135m"], n_layers=1, d_model=32, vocab=64, n_heads=2,
                  n_kv_heads=1, d_ff=64, head_dim=16)
    st = TrainSettings(steps=2, batch=2, seq=16, warmup=1, ckpt_dir=str(tmp_path),
                       ckpt_every=100, log_every=100)
    try:
        with obs.tracing() as tr:
            train(cfg, st)
    finally:
        layers.set_act_rules(None)
    steps = [s for s in tr.spans if s["name"] == "train.step"]
    feeds = [s for s in tr.spans if s["name"] == "data.batch"]
    assert [s["attrs"]["step"] for s in steps] == [0, 1]
    assert [f["parent"] for f in feeds] == [s["id"] for s in steps]
    assert [f["attrs"]["step"] for f in feeds] == [0, 1]


def test_disabled_span_overhead_is_bounded():
    """The disabled path must stay cheap enough to leave in hot loops."""
    t0 = time.perf_counter()
    for _ in range(10_000):
        with obs.span("hot.loop", i=0):
            pass
    elapsed = time.perf_counter() - t0
    # generous absolute bound: 10k disabled spans in well under a second
    assert elapsed < 1.0, f"10k disabled spans took {elapsed:.3f}s"


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_metrics_registry_counters_gauges_histograms():
    with obs.collecting() as reg:
        obs.inc("fabric_requests_total", path="fused")
        obs.inc("fabric_requests_total", 2, path="fallback")
        obs.set_gauge("fabric_link_clock_calibration", 2.9e4)
        obs.observe("serve_prefill_seconds", 0.05)
        obs.observe("serve_prefill_seconds", 0.5)
        assert obs.active()
        assert obs.get_value("fabric_requests_total", path="fused") == 1.0
        assert obs.get_value("fabric_requests_total", path="fallback") == 2.0
        assert obs.get_value("fabric_link_clock_calibration") == 2.9e4
        assert obs.get_value("never_registered") == 0.0
    assert not obs.active()
    assert obs.get_value("fabric_requests_total", path="fused") == 0.0  # off
    assert reg.names() == [
        "fabric_link_clock_calibration",
        "fabric_requests_total",
        "serve_prefill_seconds",
    ]
    assert reg.histogram("serve_prefill_seconds").count() == 2
    assert reg.histogram("serve_prefill_seconds").sum() == pytest.approx(0.55)


def test_metrics_registry_rejects_misuse():
    reg = obs.MetricsRegistry()
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.counter("c").inc(-1)
    reg.counter("taken")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("taken")


def test_prometheus_text_exposition_format():
    reg = obs.MetricsRegistry()
    reg.counter("fabric_fallback_total", help="Fallbacks.").inc(
        reason="ragged_batch"
    )
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
    text = reg.prometheus_text()
    assert "# HELP fabric_fallback_total Fallbacks." in text
    assert "# TYPE fabric_fallback_total counter" in text
    assert 'fabric_fallback_total{reason="ragged_batch"} 1' in text
    assert "# TYPE lat_seconds histogram" in text
    # cumulative buckets with an auto-appended +Inf bound
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------


def test_jsonl_sink_streams_parse_clean(tmp_path):
    path = tmp_path / "trace.jsonl"
    with obs.tracing(jsonl=str(path)) as tr:
        with obs.span("fabric.demo", m=4):
            pass
        obs.event("fabric.fallback", reason="ragged_batch")
    records = obs.read_jsonl(str(path))
    assert len(records) == len(tr.spans) + len(tr.events) == 2
    assert {r["name"] for r in records} == {"fabric.demo", "fabric.fallback"}
    path.write_text(json.dumps(records[0]) + "\nnot json\n")
    with pytest.raises(ValueError):
        obs.read_jsonl(str(path))


def test_write_prometheus_sink(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("fabric_matmuls_total").inc(3)
    out = tmp_path / "metrics.prom"
    obs.write_prometheus(reg, str(out))
    assert "fabric_matmuls_total 3" in out.read_text()


# ---------------------------------------------------------------------------
# fallback taxonomy (wire format — strings are pinned, not prose)
# ---------------------------------------------------------------------------


def test_fallback_reason_strings_are_pinned():
    assert obs.REASON_RAGGED_BATCH == "ragged_batch"
    assert obs.REASON_INSUFFICIENT_DEVICES == "insufficient_devices"
    assert obs.REASON_REPLICATION_FALLBACK == "replication_fallback"
    assert obs.REASON_REQUESTED_SEQUENTIAL == "requested_sequential"
    assert obs.REASON_INELIGIBLE == "ineligible"
    assert obs.REASON_NO_BUCKET == "no_bucket"
    assert obs.FALLBACK_REASONS == (
        "ragged_batch", "insufficient_devices", "replication_fallback",
        "requested_sequential", "ineligible", "no_bucket",
    )
    assert obs.classify_fallback(["host has 1 jax device(s) < 4 chips"]) \
        == "insufficient_devices"
    assert obs.classify_fallback(["replication fallbacks leave realized "
                                  "splits 1x1 != mesh 2x2"]) \
        == "replication_fallback"
    assert obs.classify_fallback(["anything else"]) == "ineligible"


def test_insufficient_devices_fallback_recorded():
    """A 4x4 mesh (16 chips) on the 8-device host must auto-fall back with
    the canonical insufficient_devices reason and a device-count detail."""
    cm = ChipMeshConfig(data=4, model=4, fabric=FB)
    sp = shard_placement(map_matmul("l", 16, 256, 64, FB, cim=NOISY), cm)
    with obs.tracing() as tr, obs.collecting():
        assert resolve_backend(sp, "auto") == "sequential"
        assert obs.get_value(
            "fabric_fallback_total", reason="insufficient_devices"
        ) == 1.0
    (ev,) = [e for e in tr.events if e["name"] == "fabric.fallback"]
    assert ev["attrs"]["reason"] == "insufficient_devices"
    assert "jax device" in ev["attrs"]["detail"]


def test_explicit_sequential_request_records_no_fallback():
    """backend="sequential" is a request, not a degradation."""
    cm = ChipMeshConfig(data=2, model=2, fabric=FB)
    sp = shard_placement(map_matmul("l", 4, 64, 64, FB, cim=NOISY), cm)
    with obs.collecting():
        assert resolve_backend(sp, "sequential") == "sequential"
        assert obs.get_value("fabric_fallback_total") == 0.0
        for reason in obs.FALLBACK_REASONS:
            assert obs.get_value("fabric_fallback_total", reason=reason) == 0.0


def test_ragged_batch_fallback_counted_exactly_once():
    """The CI gate's exact semantics: an aligned fused request records 0
    ragged_batch fallbacks, a ragged one records exactly 1 (at the program
    level — the inner per-layer loop must not double-count)."""
    cm = ChipMeshConfig(data=2, model=2, fabric=FB)
    prog = compile_forward(chain(cm), cm, NOISY)
    assert prog.backend == "shard_map"
    ws = prog.random_weights(jax.random.PRNGKey(1))
    nk = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    with obs.tracing() as tr, obs.collecting():
        prog(x, ws, key=nk)  # aligned: fused path
        assert obs.get_value("fabric_fallback_total",
                             reason="ragged_batch") == 0.0
        assert obs.get_value("fabric_requests_total", path="fused") == 1.0
        prog(x[:3], ws, key=nk)  # 3 rows % data axis 2 != 0
        assert obs.get_value("fabric_fallback_total",
                             reason="ragged_batch") == 1.0
        assert obs.get_value("fabric_requests_total", path="fallback") == 1.0
    (ev,) = [e for e in tr.events if e["name"] == "fabric.fallback"]
    assert ev["attrs"]["reason"] == "ragged_batch"
    assert "batch rows 3" in ev["attrs"]["detail"]


def test_sharding_replication_fallback_emits_obs_records():
    from jax.sharding import Mesh
    from repro.launch.shardings import spec_for

    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh = Mesh(devs, ("data", "model"))
    with obs.tracing() as tr, obs.collecting():
        spec_for(mesh, (16, 33), ("fsdp", "tp"), "wq")  # 33 % 2 != 0
        assert obs.get_value("sharding_fallback_total") == 1.0
    (ev,) = [e for e in tr.events if e["name"] == "sharding.fallback"]
    assert "wq" in ev["attrs"]["detail"]


# ---------------------------------------------------------------------------
# neutrality: observability provably does not perturb compiled programs
# ---------------------------------------------------------------------------


def test_obs_does_not_change_fused_chain_census_or_outputs():
    cm = ChipMeshConfig(data=2, model=2, fabric=FB)
    prog = compile_forward(chain(cm), cm, NOISY)
    assert prog.backend == "shard_map"
    ws = prog.random_weights(jax.random.PRNGKey(1))
    nk = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    census_off = prog.collective_counts(x, ws, key=nk)
    y_off = np.asarray(prog(x, ws, key=nk))
    with obs.tracing(), obs.collecting():
        census_on = prog.collective_counts(x, ws, key=nk)
        y_on = np.asarray(prog(x, ws, key=nk))
    assert census_on == census_off
    assert (y_on == y_off).all()


def test_obs_does_not_change_fused_graph_logits_1x1_noisy():
    from repro.configs.base import ModelConfig
    from repro.models.transformer import init_transformer

    cfg = ModelConfig(
        name="obs-neutrality", family="dense", n_layers=1, d_model=64,
        vocab=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        pad_vocab_multiple=16, param_dtype="float32",
        compute_dtype="float32",
    )
    cm1 = ChipMeshConfig(fabric=FB)
    prog = compile_graph_forward(cfg, cm1, NOISY, tokens=8)
    assert prog.backend == "shard_map"  # the graph fuses even on 1x1
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    ws = transformer_graph_weights(params, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, cfg.d_model))
    nk = jax.random.PRNGKey(7)
    census_off = prog.collective_counts(key=nk)
    y_off = np.asarray(prog(x, ws, key=nk))
    with obs.tracing() as tr, obs.collecting():
        census_on = prog.collective_counts(key=nk)
        y_on = np.asarray(prog(x, ws, key=nk))
    assert census_on == census_off
    assert (y_on == y_off).all()
    names = [s["name"] for s in tr.spans]
    assert names == ["fabric.graph.prepare", "fabric.graph.forward"]


@pytest.mark.parametrize("sigma", [0.0, 0.05])
def test_direct_conversions_counted_on_the_fused_graph(sigma):
    """A noiseless fused call resolves every conversion on the ADC's direct
    path; a noisy one walks the search tree for all of them."""
    from repro.configs.base import ModelConfig
    from repro.models.transformer import init_transformer

    cfg = ModelConfig(
        name="obs-direct", family="dense", n_layers=1, d_model=64,
        vocab=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        pad_vocab_multiple=16, param_dtype="float32",
        compute_dtype="float32",
    )
    cim = dataclasses.replace(NOISY, comparator_sigma=sigma)
    prog = compile_graph_forward(cfg, ChipMeshConfig(fabric=FB), cim, tokens=8)
    ws = transformer_graph_weights(init_transformer(jax.random.PRNGKey(0), cfg), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, cfg.d_model))
    with obs.collecting():
        prog(x, ws, key=jax.random.PRNGKey(7))
        assert obs.get_value("fabric_requests_total", path="fused") == 1.0
        total = obs.get_value("fabric_conversions_total")
        direct = obs.get_value("fabric_direct_conversions_total")
    assert total > 0
    assert direct == (total if sigma == 0.0 else 0.0)


# ---------------------------------------------------------------------------
# calibration constant + serve summary line
# ---------------------------------------------------------------------------


def test_link_validation_names_the_calibration_constant():
    cm = ChipMeshConfig(data=2, model=2, fabric=FB)
    sps = chain(cm)
    with obs.collecting():
        v = link_validation(sps, measured_collective_s=1e-3)
        assert v["link_clock_calibration"] == v["measured_over_modeled"]
        assert v["link_clock_calibration"] == pytest.approx(
            1e-3 / v["modeled_link_s"]
        )
        # raw seconds always reported next to the ratio, and as gauges
        assert v["modeled_link_s"] > 0
        assert v["measured_collective_s"] == 1e-3
        assert obs.get_value("fabric_modeled_link_seconds") == \
            v["modeled_link_s"]
        assert obs.get_value("fabric_link_clock_calibration") == \
            v["link_clock_calibration"]
    # without a measurement the ratio is None, raw modeled time still there
    v0 = link_validation(sps, None)
    assert v0["link_clock_calibration"] is None
    assert v0["modeled_link_s"] > 0


def test_serve_obs_summary_line(capsys):
    from repro.configs import ARCHS, reduced
    from repro.launch.serve import ServeSettings, serve_batch

    cfg = reduced(ARCHS["smollm-135m"], n_layers=1)
    rollup = {
        "totals": {
            "latency_s": 1e-3, "digitization_energy_pj": 1e6,
            "ema_energy_pj": 0.0, "ema_bits_per_pass": 128.0,
            "crosschip_bits_per_pass": 0, "model_resident": True,
        },
        "mesh": {"n_chips": 4},
        "exec_backend": "shard_map",
    }
    st = ServeSettings(batch=2, prompt_len=8, gen_len=4)
    with obs.collecting() as reg:
        serve_batch(cfg, st, fabric_rollup=rollup)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[serve] obs")]
    assert len(line) == 1
    assert "fused" in line[0] and "link_clock_calibration" in line[0]
    assert "direct" in line[0]
    assert reg.counter("serve_requests_total").value() == 2.0
    assert reg.histogram("serve_prefill_seconds").count() == 1
    assert reg.counter("fabric_ema_bits_total").value() > 0
    # metrics off -> the original batching line comes back
    serve_batch(cfg, st, fabric_rollup=rollup)
    out = capsys.readouterr().out
    assert "[serve] batch" in out and "[serve] obs" not in out
