"""Hand counts of parameters, KV bytes and FLOPs for the benchmark's
configurations (bench/cost.py)."""

import json
from pathlib import Path

import pytest

from bench import cost

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"

# stablelm-3b-4e1t's published sizes: no cell runs it (its partial rotary
# is not in the program), but its counts are the MHA case of the formulas
STABLELM_3B = {"num_hidden_layers": 32, "hidden_size": 2560,
               "intermediate_size": 6912, "num_attention_heads": 32,
               "num_key_value_heads": 32, "vocab_size": 50304,
               "norm": "layernorm", "layer_norm_eps": 1e-5,
               "rope_theta": 10000}


def shape(name):
    if name == "stablelm-3b":
        return cost.Shape.from_config(STABLELM_3B)
    return cost.Shape.from_config(json.loads((CONFIGS / f"{name}.json")
                                             .read_text()))


def test_stablelm_3b_hand_counts():
    s = shape("stablelm-3b")
    # per layer: 4 x 2560^2 attention, 3 x 2560 x 6912 MLP, two LayerNorms
    # of scale and bias
    assert s.layer_params == 4 * 2560**2 + 3 * 2560 * 6912 + 4 * 2560
    assert s.params == 2_795_443_200                 # 2.795B
    assert s.params * 2 == 5_590_886_400             # 5.59 GB in bf16
    assert s.kv_bytes_per_token_layer == 2 * 32 * 80 * 2 == 10_240
    assert s.kv_bytes_per_token == 327_680           # over 32 layers


def test_yi_34b_8l_hand_counts():
    s = shape("yi-34b-8l")
    attn = 2 * 7168 * 7168 + 2 * 7168 * 1024          # q, o; k, v
    assert s.layer_params == attn + 3 * 7168 * 20480 + 2 * 7168
    assert round(s.layer_params / 1e6, 1) == 557.9    # 557.86M a layer
    assert s.kv_bytes_per_token_layer == 2 * 8 * 128 * 2 == 4_096
    assert 2 * 64000 * 7168 * 2 == 1_835_008_000       # embedding and head


@pytest.mark.parametrize("name", ["stablelm-3b", "yi-34b-8l"])
def test_prefill_flops_count_real_tokens(name):
    s = shape(name)
    per_token = 2 * s.layers * (s.attn_params + s.mlp_params)
    qk = 4 * s.layers * s.heads * s.head_dim
    # 3 new tokens after 5 cached: they attend 6, 7 and 8 keys
    assert cost.prefill_flops(s, 3, 5) == (3 * per_token + qk * (6 + 7 + 8)
                                           + 2 * s.d_model * s.vocab)


@pytest.mark.parametrize("name", ["stablelm-3b", "yi-34b-8l"])
def test_decode_counts(name):
    s = shape(name)
    ctx = [100, 1]
    per_token = 2 * s.layers * (s.attn_params + s.mlp_params) \
        + 2 * s.d_model * s.vocab
    assert cost.decode_flops(s, ctx) == (
        2 * per_token + 4 * s.layers * s.heads * s.head_dim * 101)
    weights = (s.params - s.vocab * s.d_model) * 2
    assert cost.decode_bytes(s, ctx) == (
        weights + 2 * s.d_model * 2 + 101 * s.kv_bytes_per_token)
