"""The traffic generator (bench/traffic.py): seeded, sized to fit the
engine, the same work for every seed, and what the programs send."""

from pathlib import Path

import pytest

from bench import programs, spec, traffic
from bench.programs import CharTokenizer, text

ROOT = Path(__file__).resolve().parents[2]
SEED = 3_000_000_017          # above 2**31: seeds may exceed 32 bits

# the benchmark's own mixes, each with its cell's configuration, and
# program shapes that no cell runs yet, on the first cell's configuration
BM = spec.load_benchmark(ROOT)
MIXES = {w["name"]: (spec.load_traffic(w["traffic"]),
                     spec.load_config(w["config"], BM))
         for w in BM["workloads"]}
FIRST = MIXES[BM["workloads"][0]["name"]][1]
SHAPES = {
    "agent_loop": {
        "rate_per_s": 0.4, "apps": 3, "preamble_tokens": [1024, 2048],
        "stages": [
            {"calls": [1, 1], "parts": ["preamble", {"tokens": [64, 192]}],
             "output_tokens": [16, 64]},
            {"calls": [1, 1], "repeat": [3, 6],
             "parts": [{"prompt": 1}, {"answers": 1},
                       {"tokens": [64, 192]}],
             "output_tokens": [16, 64]}],
        "length_quantum": 64, "output_quantum": 8, "shape_seed": 1},
    "bursty_fanout": {
        "rate_per_s": 0.2, "burst": 5, "apps": 4,
        "preamble_tokens": [512, 1536],
        "stages": [
            {"calls": [4, 8], "parts": ["preamble", {"tokens": [64, 384]}],
             "output_tokens": [8, 48]},
            {"calls": [1, 1],
             "parts": ["preamble", {"text": "Combine:"},
                       {"answers": 1, "digest": 8, "sep": "|"}],
             "output_tokens": [16, 32]}],
        "length_quantum": 64, "output_quantum": 8, "shape_seed": 2},
    "single_call": {
        "rate_per_s": 2.0, "apps": 1, "preamble_tokens": [0, 0],
        "stages": [{"calls": [1, 1], "parts": [{"tokens": [256, 1024]}],
                    "output_tokens": [128, 512]}],
        "length_quantum": 64, "output_quantum": 8, "shape_seed": 3},
}
MIXES.update({k: (v, FIRST) for k, v in SHAPES.items()})


def sizes(p):
    return (p.app, tuple((traffic.prompt_length(p, s, c),
                          len(traffic.head(p, s, c)), p.stages[s][c].out)
                         for s, c in traffic.calls(p)))


@pytest.mark.parametrize("cell", MIXES)
def test_same_seed_same_schedule(cell):
    mix, conf = MIXES[cell]
    a = traffic.schedule(mix, SEED, 51, conf["vocab_size"])
    b = traffic.schedule(mix, SEED, 51, conf["vocab_size"])
    assert a == b
    c = traffic.schedule(mix, SEED + 1, 51, conf["vocab_size"])
    assert a != c
    # another seed: other tokens, the same sizes at the same times
    assert list(map(sizes, a)) == list(map(sizes, c))
    assert [p.due for p in a] == [p.due for p in c]


@pytest.mark.parametrize("cell", MIXES)
def test_every_call_fits_the_engine(cell):
    mix, conf = MIXES[cell]
    max_len = conf["engine"]["max_len"]
    for seed in (1, SEED):
        for p in traffic.schedule(mix, seed, 51, conf["vocab_size"]):
            assert traffic.longest_request(p) < max_len
            assert p.due < 51


@pytest.mark.parametrize("cell", MIXES)
def test_prompt_lengths_are_what_the_programs_send(cell):
    """The program's body, run as plain Python on answers as long as each
    call asks, sends prompts of the lengths (and heads) the generator
    promised the warm-up."""
    mix, conf = MIXES[cell]
    tok = CharTokenizer(conf["vocab_size"])
    for p in traffic.schedule(mix, SEED, 51, conf["vocab_size"])[:5]:
        order = iter(traffic.calls(p))
        sent = []

        def served(prompt):
            s, c = next(order)
            sent.append((s, c, tok.encode(prompt)))
            return text([7] * p.stages[s][c].out)
        programs.expected(p, served)
        assert [(s, c) for s, c, _ in sent] == traffic.calls(p)
        for s, c, ids in sent:
            assert len(ids) == traffic.prompt_length(p, s, c)
            h = traffic.head(p, s, c)
            assert tuple(ids[:len(h)]) == h


def test_an_agent_turn_carries_the_conversation():
    mix, conf = MIXES["agent_loop"]
    for p in traffic.schedule(mix, SEED, 51, conf["vocab_size"]):
        def answer(s, c):
            return [s + 1] * p.stages[s][c].out
        for s in range(1, len(p.stages)):
            before = traffic.prompt_tokens(p, s - 1, 0, answer)
            now = traffic.prompt_tokens(p, s, 0, answer)
            assert now[:len(before)] == before
            assert now[len(before):len(before) + p.stages[s - 1][0].out] \
                == answer(s - 1, 0)
        assert 4 <= len(p.stages) <= 7


def test_bursts_arrive_together():
    mix, conf = MIXES["bursty_fanout"]
    due = [p.due for p in traffic.schedule(mix, SEED, 51,
                                           conf["vocab_size"])]
    assert len(due) == 10 and len(set(due)) == 2
    assert due == sorted(due) and due[:5] == [0.0] * 5


def test_the_fanout_mix_keeps_its_schedule():
    """The stage form of the fan-out mix sends what its first measured
    form did (PERF.md's readings stand): the first program's sizes."""
    mix, conf = MIXES["yi-34b-8l.fanout"]
    p = traffic.schedule(mix, 1, 51, conf["vocab_size"])[0]
    assert sizes(p) == (0, ((1984, 1664, 24), (1792, 1664, 16),
                            (1920, 1664, 40), (1856, 1664, 40),
                            (1728, 1664, 40), (1792, 1664, 16),
                            (1726, 1672, 32)))


def test_tokenizer_round_trip():
    tok = CharTokenizer(64000)
    ids = [0, 1, 255, 63999]
    assert tok.encode(tok.decode(ids)) == ids
    assert tok.encode("A|") == [65, 124]
