"""What decides ``correct``, driven on the CPU at toy widths: a sound run
passes; a token altered where it is produced, an answer altered on its
way back, and the float8 control in the program's place all fail."""

import json

import pytest

from bench import run, spec

SEED = 2**33 + 11


def run_tiny(root, cell, fault=None, **kw):
    bm = spec.load_benchmark(root)
    w = {**spec.cell(bm, cell), **kw.pop("entry", {})}
    return run.run_cell(bm, w, SEED, 2.0, False, root=root, strict=False,
                        chips_check=False, fault=fault, log=lambda *a: None,
                        **kw)


@pytest.mark.parametrize("cell", ["tiny-stablelm.fanout", "tiny-yi.fanout"])
def test_sound_run_is_correct_and_control_is_not(tiny_root, cell):
    res = run_tiny(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["logit_gap", "programs_wrong",
                                   "programs_failed", "compiles_in_window"]
    assert list(res)[-1] == "checks"
    # the float8 reference in the program's place, under the same limit
    ctl = run_tiny(tiny_root, cell, control="fp8")
    print(cell, res["checks"]["logit_gap"]["value"],
          ctl["checks"]["logit_gap"]["value"])
    assert not ctl["correct"]
    gap = ctl["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"] == res["checks"]["logit_gap"]["limit"]
    assert all(c["value"] <= c["limit"] for k, c in ctl["checks"].items()
               if k != "logit_gap")


def test_an_agent_loop_runs_from_data_alone(tiny_root):
    """A program shape no code names (turns that carry the conversation
    so far) is a mix file, and runs correct through the whole harness."""
    (tiny_root / "bench/traffic/tiny.agent.json").write_text(json.dumps({
        "rate_per_s": 2.0, "apps": 2, "preamble_tokens": [32, 64],
        "stages": [
            {"calls": [1, 1], "parts": ["preamble", {"tokens": [16, 32]}],
             "output_tokens": [4, 8]},
            {"calls": [1, 1], "repeat": [2, 3],
             "parts": [{"prompt": 1}, {"answers": 1}, {"tokens": [16, 32]}],
             "output_tokens": [4, 8]}],
        "length_quantum": 16, "output_quantum": 4, "shape_seed": 0}))
    res = run_tiny(tiny_root, "tiny-yi.fanout",
                   entry={"traffic": "tiny.agent"})
    assert res["correct"], res["checks"]
    assert res["attempted"] == 4 and res["failed"] == 0


def test_altered_token_is_caught(tiny_root):
    def fault(engine, backend, dispatcher):
        step = engine._decode_once

        def altered():
            step()
            for req in engine.active.values():
                if len(req.out_tokens) == 2:
                    req.out_tokens[-1] = (req.out_tokens[-1] + 1) % 512
        engine._decode_once = altered
    res = run_tiny(tiny_root, "tiny-stablelm.fanout", fault)
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_answer_is_caught(tiny_root):
    def fault(engine, backend, dispatcher):
        gen = backend.generate
        calls = []

        async def altered(prompt, **kw):
            out = await gen(prompt, **kw)
            calls.append(out)
            return out[::-1] if len(calls) % 4 == 0 else out
        backend.generate = altered
    res = run_tiny(tiny_root, "tiny-stablelm.fanout", fault)
    assert not res["correct"]
    assert res["checks"]["programs_wrong"]["value"] >= 1
