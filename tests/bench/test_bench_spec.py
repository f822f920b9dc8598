"""Cells, configurations, traffic mixes, serving builders and metric
readers are files found by name: a new cell needs new files and a new
entry, and no edit."""

import json
import shutil
from pathlib import Path

from bench import spec, traffic

ROOT = Path(__file__).resolve().parents[2]

# an agent loop: a shared prompt, then turns that each carry the whole
# conversation so far and a fresh tool result; no code knows this shape
AGENT_LOOP = {
    "rate_per_s": 0.5, "apps": 2, "preamble_tokens": [1024, 2048],
    "stages": [
        {"calls": [1, 1], "parts": ["preamble", {"tokens": [64, 192]}],
         "output_tokens": [16, 64]},
        {"calls": [1, 1], "repeat": [3, 6],
         "parts": [{"prompt": 1}, {"answers": 1},
                   {"tokens": [64, 192]}],
         "output_tokens": [16, 64]}],
    "length_quantum": 64, "output_quantum": 8, "shape_seed": 0}


def copy_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
            if p.is_file()}


def test_the_benchmark_is_whole():
    assert spec.validate(spec.load_benchmark(ROOT), ROOT) == []


def test_new_cell_is_found_by_name(tmp_path):
    before = copy_benchmark(tmp_path)
    # a new configuration, serving builder, traffic mix (of a new program
    # shape) and metric: new files only
    conf = json.loads((ROOT / "bench/configs/yi-34b-8l.json").read_text())
    conf["num_hidden_layers"] = 4
    conf["backend"] = "fleet2"
    (tmp_path / "bench/configs/new-model.json").write_text(json.dumps(conf))
    (tmp_path / "bench/backends/fleet2.py").write_text(
        "def build(model, params, conf, tokenizer, devices):\n"
        "    return 'fleet of two'\n")
    (tmp_path / "bench/traffic/new-model.agent.json").write_text(
        json.dumps(AGENT_LOOP))
    (tmp_path / "bench/metrics/serving.new_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "new-model", "source": "test",
                          "file": "bench/configs/new-model.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "new-model.agent", "config": "new-model",
                            "traffic": "new-model.agent", "chips": 4,
                            "why": "test"})
    bm["per_layer"].append({"name": "serving.new_share", "unit": "%",
                            "better": "higher", "source": "program_counter",
                            "layer": "serving (repro.serving.engine)",
                            "moves": "program_p50_s",
                            "workloads": ["new-model.agent"]})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("new-model.agent")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    bm = spec.load_benchmark(tmp_path)
    assert spec.validate(bm, tmp_path) == []
    cell = spec.cell(bm, "new-model.agent")
    conf = spec.load_config(cell["config"], bm, tmp_path)
    assert conf["num_hidden_layers"] == 4
    assert spec.load_builder(conf, tmp_path)(None, None, conf, None,
                                             None) == "fleet of two"
    mix = spec.load_traffic(cell["traffic"], tmp_path)
    sched = traffic.schedule(mix, 5, 51, conf["vocab_size"])
    assert {len(p.stages) for p in sched} == set(range(4, 8))
    ends, layers = spec.cell_metrics(bm, "new-model.agent")
    assert "serving.new_share" in [m["name"] for m in layers]
    assert spec.load_reader("serving.new_share", tmp_path)(None) == 42.0
    # no file that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_missing_file_is_named(tmp_path):
    copy_benchmark(tmp_path)
    (tmp_path / "bench/metrics/device.step_gap_ms.py").unlink()
    (tmp_path / "bench/metrics/itl_p95_ms.py").unlink()
    (tmp_path / "bench/backends/local.py").unlink()
    faults = spec.validate(spec.load_benchmark(tmp_path), tmp_path)
    assert any("device.step_gap_ms" in f for f in faults)
    assert any("itl_p95_ms" in f for f in faults)
    assert any("no backend file" in f for f in faults)


def test_a_mix_the_generator_cannot_read_is_named(tmp_path):
    copy_benchmark(tmp_path)
    path = tmp_path / "bench/traffic/yi-34b-8l.fanout.json"
    mix = json.loads(path.read_text())
    mix["stages"][0]["parts"].append({"tool": [1, 2]})
    path.write_text(json.dumps(mix))
    faults = spec.validate(spec.load_benchmark(tmp_path), tmp_path)
    assert any("cannot read its traffic mix" in f for f in faults)
    mix["stages"][0]["parts"][-1] = {"tokens": [4096, 4096]}
    path.write_text(json.dumps(mix))
    faults = spec.validate(spec.load_benchmark(tmp_path), tmp_path)
    assert any("positions, the engine holds 4096" in f for f in faults)


def test_a_missing_family_is_named(tmp_path):
    copy_benchmark(tmp_path)
    path = tmp_path / "bench/configs/yi-34b-8l.json"
    conf = json.loads(path.read_text())
    conf["family"] = "sparse_experts"
    path.write_text(json.dumps(conf))
    faults = spec.validate(spec.load_benchmark(tmp_path), tmp_path)
    assert faults == ["yi-34b-8l.fanout: no family file for "
                      "'sparse_experts'"]
