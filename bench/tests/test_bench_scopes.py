"""Device time by named scope and idle time by program span, on hand-built
traces and a hand-built ``.xplane.pb``."""

import random

import pytest

from bench.harness import scopes
from bench.harness.scopes import METRICS, OTHER, ScopedTrace, label_gaps, metric, reduce, scope_of
from bench.harness.trace import Event, label


@pytest.mark.parametrize("path, scope", [
    ("jit(prefill)/layer_scan/while/body/closed_call/checkpoint/attention/mul", "attention"),
    ("jit(prefill)/layer_scan/while/body/attention/cim_linear/cim.quantize/div", "cim.quantize"),
    ("jit(train_step)/transpose(jvp(cim_linear))/cim.tiles/dot_general", "cim.tiles"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/cim_linear/mul", "cim_linear"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/dynamic_slice", "layer_scan"),
    ("jit(decode_step)/layer_scan/while/body/attention/kv_cache/dynamic_update_slice", "kv_cache"),
    ("jit(chip_fn)/while/body/fabric.matmul/cim.adc/while/body/gather", "cim.adc"),
    ("jit(chip_fn)/while/body/fabric.matmul/fabric.requant/round", "fabric.requant"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(prefill)/while/body/dynamic_slice", OTHER),
    ("", OTHER),
    ("jit(f)/attention_like/cim_linearx/mul", OTHER),
])
def test_scope_of_is_innermost_vocabulary_name(path, scope):
    assert scope_of(path) == scope


def test_cim_subscopes_count_under_cim_linear_only():
    assert scopes.within("cim.adc", "cim_linear") and scopes.within("cim_linear", "cim_linear")
    assert not scopes.within("cim.adc", "attention")
    assert scopes.within("cim.adc", "cim.adc") and not scopes.within("cim_linear", "cim.adc")


def test_scopes_vocabulary_is_the_programs():
    from repro.obs import scopes as program_scopes

    assert scopes.SCOPES == set(program_scopes.SCOPES)
    assert set(scopes.CIM_SUBSCOPES) == {s for s in program_scopes.SCOPES if s.startswith("cim.")}


def _trace():
    # window [0, 100); one chip. Batch spans [0, 60) and [60, 100); in the
    # first, decode steps [0, 30) and a fetch [40, 60); in the second a fetch
    # [80, 100). Ops: a while op [0, 30) holding a cim_linear op [5, 15) and a
    # cim.adc op [15, 20); an attention op [30, 35); an op with no scope
    # [45, 50); a layer_scan op [60, 80).
    ops = [("layer_scan", 0, 30), ("cim_linear", 5, 10), ("cim.adc", 15, 5),
           ("attention", 30, 5), (OTHER, 45, 5), ("layer_scan", 60, 20)]
    spans = [Event("bench.window", 0, 100),
             Event("bench.serve_batch", 0, 60), Event("bench.serve_batch", 60, 40),
             Event("serve.decode", 0, 30), Event("serve.fetch", 40, 20),
             Event("serve.fetch", 80, 20)]
    device = [[Event(s, t, d) for s, t, d in ops]]
    named = [[Event(f"jit_prefill/%op.{i}", t, d) for i, (_, t, d) in enumerate(ops)]]
    paths = {f"jit_prefill/%op.{i}": "" if s == OTHER else f"jit(prefill)/{s}/x"
             for i, (s, _, _) in enumerate(ops)}
    return ScopedTrace(device, spans, paths, named)


def test_reduce_attributes_self_time_and_the_other_bucket():
    s = reduce(_trace())
    got = dict(s.scopes)
    # the while op's 30 less the 15 of the ops it holds, plus the second 20
    assert got["layer_scan"] == pytest.approx(35e-9)
    assert got["cim_linear"] == pytest.approx(10e-9)
    assert got["cim.adc"] == pytest.approx(5e-9)
    assert got["attention"] == pytest.approx(5e-9)
    assert got[OTHER] == pytest.approx(5e-9)
    assert s.other_share == pytest.approx(5 / 60)
    assert s.other_ops == [("jit_prefill/%op.4", "", pytest.approx(5e-9))]
    assert s.busy_s == pytest.approx(60e-9) and s.window_s == pytest.approx(100e-9)
    assert s.units == {"bench.serve_batch": 2}


def test_idle_inside_program_spans():
    s = reduce(_trace())
    # fetch [40, 60) holds the op [45, 50): 15 idle; fetch [80, 100): 20 idle
    assert s.span_idle["serve.fetch"] == pytest.approx(35e-9)
    assert s.span_idle["serve.decode"] == pytest.approx(0.0)
    assert "bench.serve_batch" not in s.span_idle


def test_gaps_are_labelled_by_program_span_inside_a_bench_span():
    s = reduce(_trace())
    # gaps: [35, 45) mid 40 -> the fetch (shorter than the batch span);
    # [50, 60) -> the fetch; [80, 100) -> the second fetch
    assert s.idle_gaps == [("serve.fetch", pytest.approx(20e-9)),
                           ("serve.fetch", pytest.approx(10e-9)),
                           ("serve.fetch", pytest.approx(10e-9))]


def test_label_gaps_sweep_matches_the_scan_of_all_spans():
    rnd = random.Random(7)
    spans = []
    for _ in range(200):
        t = rnd.uniform(0, 1000)
        spans.append(Event(f"s{len(spans)}", t, rnd.uniform(0.5, 200)))
    gap_list = [(t, t + rnd.uniform(0, 5)) for t in (rnd.uniform(0, 1100) for _ in range(300))]
    want = [label((s + e) / 2, spans) for s, e in gap_list]
    assert label_gaps(gap_list, spans) == want


@pytest.mark.parametrize("name, want", [
    ("cim_linear_ms.serve", (10 + 5) * 1e-9 * 1e3 / 2),
    ("attention_ms.serve", 5e-9 * 1e3 / 2),
    ("layer_scan_ms.serve", 35e-9 * 1e3 / 2),
    ("fetch_idle_ms.serve", 35e-9 * 1e3 / 2),
])
def test_metric_per_unit(name, want):
    assert metric(reduce(_trace()), name) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_is_none_without_its_trace(name):
    kind, what, unit = METRICS[name]
    empty = reduce(ScopedTrace([], [Event("bench.window", 0, 100), Event(unit, 0, 50)], {}, []))
    assert metric(empty, name) is None
    if unit != "bench.serve_batch":  # the hand-built trace's unit: no unit, no number
        assert metric(reduce(_trace()), name) is None


def test_metric_is_none_where_the_scope_or_span_is_missing():
    t = _trace()
    t.device_ops = [[Event(OTHER if e.name == "attention" else e.name, e.start_ns, e.dur_ns)
                     for e in t.device_ops[0]]]
    t.spans = [sp for sp in t.spans if sp.name != "serve.fetch"]
    s = reduce(t)
    assert metric(s, "attention_ms.serve") is None
    assert metric(s, "fetch_idle_ms.serve") is None
    assert metric(s, "cim_linear_ms.serve") is not None


# -- the .xplane.pb reader -----------------------------------------------------


def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(n: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(n << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(n << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, ops: dict) -> bytes:
    """An XPlane with a ``tf_op`` stat (id 7), a fixed-width stat on every op
    (id 3) and one line that the reader skips."""
    body = _field(2, name) + _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1)))
    body += _field(5, _field(1, 7) + _field(2, _field(1, 7) + _field(2, "tf_op")))
    body += _field(5, _field(1, 3) + _field(2, _field(1, 3) + _field(2, "flops")))
    for i, (ev, path) in enumerate(ops.items(), start=1):
        stats = _field(5, _field(1, 3) + b"\x11" + bytes(8))  # a double: wire type 1
        if path is not None:
            stats += _field(5, _field(1, 7) + _field(5, path + ":"))
        md = _field(1, i) + _field(2, ev) + _field(4, ev.split(" ")[0]) + stats
        body += _field(4, _field(1, i) + _field(2, md))
    return _field(1, body)


def test_op_names_reads_tf_op_of_device_planes(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        _plane("/device:TPU:0", {"%fusion.1 = f32[8] fusion(...)": "jit(f)/mlp/cim_linear/dot",
                                 "%copy.2 = f32[8] copy(...)": None})
        + _plane("/host:CPU", {"ignored": "jit(f)/norm"})
        + _field(2, "an error string"))
    assert scopes.op_names(str(path)) == {
        "/device:TPU:0": {"%fusion.1 = f32[8] fusion(...)": "jit(f)/mlp/cim_linear/dot"}}
