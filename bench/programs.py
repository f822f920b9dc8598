"""The ``@poppy`` program a window runs, the tokenizer it uses, and the
plain reference of what each program must return.

Every mix runs the one program :func:`staged_app`: it takes a program's
stages (``bench/traffic.py``) as data, sends each stage's calls, and
builds later prompts from earlier answers.  PopPy finds the parallelism:
the calls of a stage go out together, and a stage that reads an answer
waits for it.

Text is one character per token: token id ``t`` is the private-use
character ``U+F0000 + t``, and an ASCII character is its own code.  So
a prompt's length in tokens is the length of its text, a served answer
goes into a later prompt token for token, and the generator controls
every prompt length exactly.
"""

from __future__ import annotations

from repro.core import poppy
from repro.core.ai import llm

from bench.traffic import Answer, Program, Prompt

BASE = 0xF0000


class CharTokenizer:
    """One character per token (see the module docstring)."""

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str) -> list:
        return [o - BASE if o >= BASE else o for o in map(ord, text)]

    def decode(self, ids) -> str:
        return "".join(chr(BASE + int(i)) for i in ids)


def text(ids) -> str:
    return "".join(chr(BASE + int(i)) for i in ids)


@poppy
def staged_app(stages):
    """``stages``: one tuple of calls per stage, a call ``(parts,
    max_tokens)``; a part is text, ``("p", s, c)`` (the prompt of call c
    of stage s) or ``("a", s, c, n)`` (the first n characters of its
    answer; all of them for ``None``)."""
    prompts = ()
    answers = ()
    for calls in stages:
        sent = ()
        got = ()
        for parts, max_tokens in calls:
            prompt = ""
            for part in parts:
                if type(part) is str:
                    prompt += part
                elif part[0] == "p":
                    prompt += prompts[part[1]][part[2]]
                else:
                    prompt += answers[part[1]][part[2]][:part[3]]
            sent += (prompt,)
            got += (llm(prompt, max_tokens=max_tokens),)
        prompts += (sent,)
        answers += (got,)
    return answers


def _arg(part):
    if isinstance(part, Answer):
        return ("a", part.stage, part.call, part.n)
    if isinstance(part, Prompt):
        return ("p", part.stage, part.call)
    return text(part.tokens)


def call_args(p: Program):
    """(function, args) that run program ``p``."""
    return staged_app, (tuple(
        tuple((tuple(map(_arg, c.parts)), c.out) for c in stage)
        for stage in p.stages),)


def expected(p: Program, served) -> tuple:
    """What PopPy's sequential semantics return for ``p``, given
    ``served(prompt_text) -> answer text`` for each call: the program's
    body as plain Python."""
    (stages,) = call_args(p)[1]
    prompts, answers = [], []
    for calls in stages:
        sent, got = [], []
        for parts, _ in calls:
            prompt = ""
            for part in parts:
                if type(part) is str:
                    prompt += part
                elif part[0] == "p":
                    prompt += prompts[part[1]][part[2]]
                else:
                    prompt += answers[part[1]][part[2]][:part[3]]
            sent.append(prompt)
            got.append(served(prompt))
        prompts.append(tuple(sent))
        answers.append(tuple(got))
    return tuple(answers)
