"""The one traffic generator.  A mix is a JSON file of parameters in
``bench/traffic/``; this module turns it and a seed into a schedule of
programs.  A new kind of program is a new mix file, never new code.

A mix holds:

* ``rate_per_s``: open-loop arrivals of programs (gaps at the quantiles
  of an exponential distribution); ``burst`` programs arrive together
  (default 1);
* ``apps``: each program draws one app, and each app has its own shared
  preamble of ``preamble_tokens`` [lo, hi] tokens;
* ``stages``: what each program does, in order.  The calls of one stage
  run in parallel; a stage whose prompts read earlier answers waits for
  them.  A stage has ``calls`` [lo, hi] calls, runs ``repeat`` [lo, hi]
  times in a row (default once), answers ``output_tokens`` [lo, hi]
  tokens a call, and builds each call's prompt from ``parts``, in order:

  - ``"preamble"``: the app's preamble;
  - ``{"text": s}``: fixed text;
  - ``{"tokens": [lo, hi]}``: fresh random tokens (a document, a tool
    result, a user turn);
  - ``{"answers": k, "digest": n, "sep": s}``: each answer of the stage
    ``k`` back (1: the one before), its first ``n`` tokens (all of it
    without ``digest``), each followed by ``s``;
  - ``{"prompt": k}``: the prompt of the same call of the stage ``k``
    back (a conversation's history);

* ``length_quantum`` and ``output_quantum``: every drawn length is a
  whole multiple of these, so warm-up can compile every shape the window
  will use;
* ``shape_seed``: fixes the order of the sizes and gaps.

Every seed gets the same schedule: the sizes come from stratified
quantiles of the mix's ranges, in an order and with gaps that the mix's
``shape_seed`` fixes; the run's seed draws only the tokens (and the
weights).  So two seeds do the same work at the same times, and the
spread between runs is the system's own.  (With the order drawn from the
seed, eight programs of a fan-out mix overlapped differently on every
seed and its program latency spread by 18% between seeds, against under
6% between two runs of one seed, on a TPU v5e; see PERF.md.)
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Text:
    """Literal tokens of a prompt; ``shared``: the same in every program
    of the app (a preamble, fixed text)."""

    tokens: tuple
    shared: bool


@dataclass(frozen=True)
class Answer:
    """The first ``n`` tokens (``None``: all) of the answer of call
    ``call`` of stage ``stage`` (indexes into the program's stages)."""

    stage: int
    call: int
    n: int | None


@dataclass(frozen=True)
class Prompt:
    """The whole prompt of call ``call`` of stage ``stage``."""

    stage: int
    call: int


@dataclass
class Call:
    parts: list              # Text | Answer | Prompt
    out: int                 # tokens to answer


@dataclass
class Program:
    """One program of a schedule.  ``due`` is seconds after the window
    opens; ``stages[s][c]`` is call ``c`` of stage ``s``."""

    index: int
    due: float
    app: int
    stages: list


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _ints(rng, n, span, quantum):
    """n values, stratified over [lo, hi], rounded to ``quantum``, in
    random order (a fixed range draws nothing)."""
    lo, hi = span
    if lo == hi:
        return [int(lo)] * n
    v = lo + _quantiles(n) * (hi - lo)
    v = np.clip(np.round(v / quantum) * quantum, lo, hi).astype(int)
    return [int(x) for x in rng.permutation(v)]


def _tokens(rng, n, vocab):
    return tuple(int(t) for t in rng.integers(0, vocab, n))


def _encode(s: str) -> tuple:
    """Fixed text as token ids: one ASCII character a token (the
    harness's tokenizer, ``bench/programs.py``)."""
    return tuple(map(ord, s))


def program_count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The programs due in ``seconds`` of open-loop arrivals."""
    n = program_count(mix, seconds)
    shape = np.random.default_rng(mix.get("shape_seed", 0))
    text = np.random.default_rng([seed % (1 << 63), 2])
    q, oq = mix["length_quantum"], mix["output_quantum"]
    K = mix["apps"]
    plens = _ints(shape, K, mix["preamble_tokens"], q)
    preambles = [_tokens(text, k, vocab) for k in plens]
    apps = shape.permutation(np.arange(n) % K)

    # sizes of every stage instance and call, stratified over programs
    stages = []
    for st in mix["stages"]:
        reps = _ints(shape, n, st.get("repeat", [1, 1]), 1)
        calls = _ints(shape, int(sum(reps)), st["calls"], 1)
        total = int(sum(calls))
        lens = [_ints(shape, total, part["tokens"], q)
                for part in st["parts"]
                if isinstance(part, dict) and "tokens" in part]
        outs = _ints(shape, total, st["output_tokens"], oq)
        stages.append((st, reps, calls, lens, outs))

    groups = -(-n // mix.get("burst", 1))
    gaps = shape.permutation(-np.log(1.0 - _quantiles(groups))
                             / mix["rate_per_s"] * mix.get("burst", 1))
    due = np.repeat(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]),
                    mix.get("burst", 1))[:n]
    progs = []
    for i, src in enumerate(shape.permutation(n)):
        app = int(apps[src])
        out_stages = []
        for st, reps, calls, lens, outs in stages:
            inst = int(sum(reps[:src]))                  # first instance
            first_call = int(sum(calls[:inst]))
            for r in range(reps[src]):
                k = calls[inst + r]
                made = []
                for c in range(k):
                    j = first_call + c
                    made.append(Call(_parts(st["parts"], out_stages, c,
                                            preambles[app], text, vocab,
                                            [ln[j] for ln in lens]),
                                     outs[j]))
                first_call += k
                out_stages.append(made)
        progs.append(Program(i, float(due[i]), app, out_stages))
    return progs


def _parts(spec, done, c, preamble, text, vocab, lens):
    """The parts of call ``c`` of a new stage, after the stages ``done``."""
    parts, lens = [], list(lens)
    for part in spec:
        if part == "preamble":
            parts.append(Text(preamble, True))
        elif "text" in part:
            parts.append(Text(_encode(part["text"]), True))
        elif "tokens" in part:
            parts.append(Text(_tokens(text, lens.pop(0), vocab), False))
        elif "answers" in part:
            s = len(done) - part["answers"]
            for a in range(len(done[s])):
                parts.append(Answer(s, a, part.get("digest")))
                parts.append(Text(_encode(part.get("sep", "")), True))
        elif "prompt" in part:
            s = len(done) - part["prompt"]
            parts.append(Prompt(s, min(c, len(done[s]) - 1)))
        else:
            raise ValueError(f"unknown prompt part {part!r}")
    return parts


# -- what a program sends ---------------------------------------------------------


def expand(p: Program, s: int, c: int) -> list:
    """The prompt of call ``c`` of stage ``s`` as ``Text`` and ``Answer``
    parts (each ``Prompt`` replaced by what it refers to)."""
    out = []
    for part in p.stages[s][c].parts:
        if isinstance(part, Prompt):
            out += expand(p, part.stage, part.call)
        else:
            out.append(part)
    return out


def prompt_tokens(p: Program, s: int, c: int, answer) -> list:
    """Call (s, c)'s prompt, given ``answer(s, c) -> tokens``."""
    toks = []
    for part in expand(p, s, c):
        if isinstance(part, Text):
            toks += part.tokens
        else:
            toks += list(answer(part.stage, part.call))[:part.n]
    return toks


def prompt_length(p: Program, s: int, c: int) -> int:
    """Token count of call (s, c)'s prompt: every answer is as long as
    its call asks (the engine stops only there)."""
    return len(prompt_tokens(p, s, c,
                             lambda s2, c2: [0] * p.stages[s2][c2].out))


def head(p: Program, s: int, c: int) -> tuple:
    """The leading tokens of call (s, c)'s prompt that every program of
    the app sends alike."""
    toks = []
    for part in expand(p, s, c):
        if not (isinstance(part, Text) and part.shared):
            break
        toks += part.tokens
    return tuple(toks)


def calls(p: Program):
    """Every (stage, call) of ``p``, in order."""
    return [(s, c) for s, st in enumerate(p.stages) for c in range(len(st))]


def longest_request(p: Program) -> int:
    """Prompt plus answer, the most positions any call of ``p`` needs."""
    return max(prompt_length(p, s, c) + p.stages[s][c].out
               for s, c in calls(p))
