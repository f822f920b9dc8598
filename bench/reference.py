"""What every family's float32 reference shares: the float8 rounding of
its control, the padding and grouping of the sequences it reads, and
:func:`served_gaps`, which scores what a run served against the
reference of the run's family (its shape's ``logits_at``,
``bench/families/<family>.py``).

A served token's gap is how far its reference logit lies below the
reference's best logit at that position.  ``control="fp8"`` computes the
same forward with each weight matrix rounded to float8 e4m3 (per output
channel scale), the step below the bfloat16 the configuration states,
and scores the token that it puts first instead.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def fp8(w):
    """Round a [in, out] matrix to float8 e4m3 with one scale per output
    channel (the largest magnitude maps to e4m3's largest finite 448)."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def by_sequence(seqs, rows, pad_to: int):
    """For each sequence ``b`` of ``seqs``: its tokens [1, n], padded at
    the end to a multiple of ``pad_to`` (causal attention never reads the
    padding); the indices ``i`` of the ``rows[i] == (b, p)`` that ask for
    the logits after its position ``p``; and those positions, padded to a
    power-of-two group.  So a few compiled shapes serve every run."""
    for b, t in enumerate(seqs):
        n = -(-len(t) // pad_to) * pad_to
        toks = np.zeros((1, n), np.int32)
        toks[0, :len(t)] = t
        mine = [i for i, (bb, _) in enumerate(rows) if bb == b]
        pos = np.zeros(_bucket(len(mine), 64), np.int32)
        pos[:len(mine)] = [rows[i][1] for i in mine]
        yield toks, mine, pos


def served_gaps(s, seed: int, served, *, control=None):
    """Gaps for ``served``: a list of (prompt tokens, served tokens),
    under the reference of the family's shape ``s``.  Without
    ``control``: reference best logit minus the reference logit of each
    served token.  With it: reference best minus the reference logit of
    the token the control puts first, at the same positions of the same
    sequences.  Returns a flat float64 array, one gap per served token."""
    seqs, rows, toks = [], [], []
    for b, (prompt, out) in enumerate(served):
        seqs.append(list(prompt) + list(out[:-1]))
        for j, t in enumerate(out):
            rows.append((b, len(prompt) - 1 + j))
            toks.append(t)
    ref = s.logits_at(seed, seqs, rows).astype(np.float64)
    if control is not None:
        toks = np.argmax(s.logits_at(seed, seqs, rows, control=control),
                         axis=-1)
    return ref.max(axis=-1) - ref[np.arange(len(rows)), np.asarray(toks)]
