"""A toy-sized benchmark root for the CPU tests of the harness: the
repository's registry models at toy widths, a fast fan-out mix, and the
per-layer readers of the real benchmark."""

import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "stablelm": {"registry": "stablelm-3b", "hidden_size": 64,
                 "intermediate_size": 128, "num_attention_heads": 4,
                 "num_key_value_heads": 4, "norm": "layernorm",
                 "layer_norm_eps": 1e-5, "rope_theta": 10000},
    "yi": {"registry": "yi-34b", "hidden_size": 64,
           "intermediate_size": 160, "num_attention_heads": 4,
           "num_key_value_heads": 2, "norm": "rmsnorm",
           "rms_norm_eps": 1e-5, "rope_theta": 5000000.0},
}


# the fan-out-and-combine program of the benchmark's mixes, at toy sizes
TINY_FANOUT = {
    "rate_per_s": 3.0, "apps": 2, "preamble_tokens": [32, 64],
    "stages": [
        {"calls": [2, 4], "parts": ["preamble", {"tokens": [16, 32]}],
         "output_tokens": [16, 32]},
        {"calls": [1, 1],
         "parts": ["preamble", {"text": "Combine:"},
                   {"answers": 1, "digest": 4, "sep": "|"}],
         "output_tokens": [16, 32]}],
    "length_quantum": 16, "output_quantum": 4, "shape_seed": 0}


def tiny_config(kind):
    return {"source": "test", "num_hidden_layers": 2, "vocab_size": 512,
            "max_position_embeddings": 256, "tie_word_embeddings": False,
            "engine": {"max_slots": 4, "max_len": 256, "page_size": 16,
                       "num_pages": 96,
                       "prefill_buckets": [32, 64, 128, 256]},
            **TINY[kind]}


@pytest.fixture(scope="session", name="tiny_config")
def tiny_config_fixture():
    return tiny_config


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_bench")
    bench = root / "bench"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    for d in ("metrics", "backends"):
        shutil.copytree(ROOT / "bench" / d, bench / d)
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"], bm["workloads"] = [], []
    for kind in TINY:
        (bench / "configs" / f"tiny-{kind}.json").write_text(
            json.dumps(tiny_config(kind)))
        bm["configs"].append({"name": f"tiny-{kind}",
                              "file": f"bench/configs/tiny-{kind}.json"})
        bm["workloads"].append({"name": f"tiny-{kind}.fanout",
                                "config": f"tiny-{kind}",
                                "traffic": "tiny.fanout", "chips": 1})
        # toy widths in bf16, five seeds each: sound runs read at most
        # 0.0172, the float8 control in the program's place at least
        # 0.0531 (tests/bench/test_bench_correct.py)
        (bench / "limits" / f"tiny-{kind}.fanout.json").write_text(
            json.dumps({"limits": {"logit_gap": 0.035}}))
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    (bench / "traffic" / "tiny.fanout.json").write_text(json.dumps(
        TINY_FANOUT))
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root
