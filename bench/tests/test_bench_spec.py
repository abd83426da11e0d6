"""The benchmark's declaration and its yardstick, without running a cell:
BENCHMARK.json against the contract's shape, files found by name, FLOP
counts and the table of peaks."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import flops, peaks, runner

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_one_line_texts():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[key]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and c["source"].startswith("https://")


def test_metrics_follow_the_rules():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for cell in CELLS:
        reported = [n for n, m in e2e.items() if runner._reports(m, cell)]
        assert "setup_s" in reported and len(reported) >= 2, cell
        layer = [m for m in SPEC["per_layer"] if runner._reports(m, cell)]
        assert layer, cell
        for m in layer:
            assert m["moves"] in reported, (cell, m["name"])
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = runner.load_cell(cell)
    assert c.chips in (1, 4)
    assert (ROOT / "bench" / "drivers" / f"{c.traffic['driver']}.py").exists()
    assert set(c.traffic["limits"]) and all(v > 0 for v in c.traffic["limits"].values())
    for m in c.per_layer:
        assert callable(runner.reader(m["name"]))
    conf = next(x for x in SPEC["configs"] if x["name"] == next(
        w for w in SPEC["workloads"] if w["name"] == cell)["config"])
    assert conf["file"].startswith("bench/") and conf["source"] == c.config["source"]


def test_added_files_need_no_edit(tmp_path):
    """A later PR adds a configuration, a traffic mix, a cell and a metric by
    adding files and entries; the harness finds them without a code edit."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/smollm-135m-cim.json").read_text())
    (tmp_path / "bench/configs/new-model.json").write_text(
        json.dumps(dict(conf, num_hidden_layers=4)))
    (tmp_path / "bench/traffic/new_mix.json").write_text(json.dumps(
        {"driver": "serve", "batch": 2, "prompt_len": 64, "gen_len": 8, "check_batches": 1,
         "trace_seconds": 2, "limits": {"logit_gap": 1.0}}))
    (tmp_path / "bench/metrics/new_metric.py").write_text("def read(r):\n    return 1.0\n")
    spec["configs"].append({"name": "new-model", "source": conf["source"],
                            "file": "bench/configs/new-model.json",
                            "reduced": ["num_hidden_layers"], "why": "a test"})
    spec["workloads"].append({"name": "new_mix.new-model", "config": "new-model",
                              "traffic": "new_mix", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms", "better": "lower",
                              "source": "program_span", "layer": "serve engine",
                              "moves": "serve_tok_s", "workloads": ["new_mix.new-model"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and m["name"] in ("serve_tok_s", "ttft_p95_ms"):
            m["workloads"].append("new_mix.new-model")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = runner.load_cell("new_mix.new-model", root=tmp_path)
    assert cell.config["num_hidden_layers"] == 4 and cell.traffic["gen_len"] == 8
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert runner.reader("new_metric", tmp_path)(None) == 1.0
    assert runner.driver(cell).__name__ == "bench.drivers.serve"


def test_smollm_parameter_count():
    conf = json.loads((ROOT / "bench/configs/smollm-135m-cim.json").read_text())
    assert flops.n_params(conf) == 134_515_008
    assert flops.train_flops_per_token(conf) == 6 * 134_515_008


def test_serve_flops_by_hand():
    conf = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
            "head_dim": 2, "intermediate_size": 8, "num_hidden_layers": 1, "vocab_size": 10}
    per_layer = 4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8  # q, k+v, o, mlp
    assert flops.linear_params_per_layer(conf) == per_layer
    # batch 1, prompt 2, gen 2: prefill of 2 tokens attending 1 + 2 keys and
    # one head token; one decode token at position 2 attending 3 keys
    want = (2 * per_layer * 2 + 4 * 2 * 2 * 3 + 2 * 4 * 10) + (2 * per_layer + 4 * 2 * 2 * 3 + 2 * 4 * 10)
    assert flops.serve_batch_flops(conf, 1, 2, 2) == want


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_exits_nonzero_without_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and proc.stdout.strip() == ""
