"""Hand counts of parameters, KV bytes and FLOPs for the benchmark's
configurations (the dense family, bench/families/dense.py)."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.families import dense

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
        return dense.Shape.from_config(STABLELM_3B)
    return dense.Shape.from_config(json.loads((CONFIGS / f"{name}.json")
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
    assert s.prefill_flops(3, 5) == (3 * per_token + qk * (6 + 7 + 8)
                                     + 2 * s.d_model * s.vocab)


@pytest.mark.parametrize("name", ["stablelm-3b", "yi-34b-8l"])
def test_decode_counts(name):
    s = shape(name)
    ctx = [100, 1]
    per_token = 2 * s.layers * (s.attn_params + s.mlp_params) \
        + 2 * s.d_model * s.vocab
    assert s.decode_flops(ctx) == (
        2 * per_token + 4 * s.layers * s.heads * s.head_dim * 101)
    weights = (s.params - s.vocab * s.d_model) * 2
    assert s.decode_bytes(ctx) == (
        weights + 2 * s.d_model * 2 + 101 * s.kv_bytes_per_token)


# What the harness's counts and reference read before the model family
# became a file of its own (the dense decoder's code in bench/cost.py and
# bench/reference.py): the same integers and the same logits, so every
# reading of logit_gap and of the MFU and roofline metrics carries over.
# Per toy configuration: parameters; prefill FLOPs of (3 new, 5 cached)
# and (100, 28); decode FLOPs and bytes of contexts [100, 1, 37]; and of
# the reference logits at rows ROWS of two seeded sequences, the sum, the
# sum of magnitudes, the float8 control's sum and the first three logits
# of each row.
ROWS = [(0, 0), (0, 39), (1, 5), (1, 69), (0, 17)]
PINNED = {
    "stablelm": {
        "params": 148096, "counts": [567808, 20468736, 758784, 301696],
        "sum": 7.320628593442962, "abs": 1677.6601967050228,
        "sum_fp8": 7.190667425253196,
        "first": [-0.5420053601264954, -2.2015178203582764,
                  -0.5636609196662903, -0.40737384557724,
                  -1.8768551349639893, 0.1288806051015854,
                  0.05074895918369293, -1.1448842287063599,
                  -0.3965127468109131, 0.3250635862350464,
                  0.5202844738960266, -0.8412410020828247,
                  -1.2920658588409424, 0.022307340055704117,
                  -0.5059260725975037]},
    "yi": {
        "params": 151872, "counts": [592384, 21287936, 783360, 273920],
        "sum": -54.57460729332524, "abs": 1667.715289590793,
        "sum_fp8": -55.42089016904356,
        "first": [-0.7576366066932678, -0.8454874753952026,
                  -1.8342310190200806, 0.18625952303409576,
                  -0.22317096590995789, 0.31454339623451233,
                  -0.2610338032245636, -0.7560279965400696,
                  0.08325104415416718, -0.7497106194496155,
                  0.6785174608230591, 0.3458854556083679,
                  0.3797564208507538, -0.394894540309906,
                  0.14051540195941925]},
}


def test_dense_counts_and_reference_are_pinned(tiny_config):
    rng = np.random.default_rng(0)
    for kind, want in PINNED.items():
        s = dense.Shape.from_config(tiny_config(kind))
        assert s.params == want["params"]
        assert [s.prefill_flops(3, 5), s.prefill_flops(100, 28),
                s.decode_flops([100, 1, 37]),
                s.decode_bytes([100, 1, 37])] == want["counts"]
        seqs = [list(map(int, rng.integers(0, s.vocab, n))) for n in (40, 70)]
        ref = s.logits_at(2**33 + 3, seqs, ROWS).astype(np.float64)
        ctl = s.logits_at(2**33 + 3, seqs, ROWS, control="fp8")
        # float32 logits of magnitude ~1 summed over 2,560: room for a
        # host whose vector units order the sums differently, and far
        # below what a change to any step of the block moves
        np.testing.assert_allclose(ref[:, :3].ravel(), want["first"],
                                   rtol=0, atol=1e-5)
        np.testing.assert_allclose(
            [ref.sum(), np.abs(ref).sum(), ctl.astype(np.float64).sum()],
            [want["sum"], want["abs"], want["sum_fp8"]], rtol=0, atol=1e-3)
