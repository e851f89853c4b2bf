"""The one general traffic generator: every mix is a data file it reads.

A traffic file (``traffic/<name>.json``) holds parameters only:

* ``edit_frac`` — E, the share of a read's length that may be edited;
* ``sub_prob`` / ``ins_prob`` — the edit mix (deletions take the rest);
* ``output`` — ``"score"`` or ``"cigar"``;
* ``pool_pairs`` — pairs in the seeded pool that the window replays
  cyclically, one wave per submission, as fast as the session takes them.

Pairs follow the paper's synthetic regime (the semantics of
``repro.data.reads.generate_pairs``, vectorised here so that a pool of
10^5 pairs costs well under a second of set-up): a reference read drawn
uniformly over ``ACGT``, and its mate made from it by ``n`` edits, ``n``
uniform on ``0..ceil(E*L)``, each a substitution by another base, an
insertion of a random base or a deletion, at a uniform position of the
sequence as edited so far.  Bases are ASCII codes in int32, padding 0.
"""
from __future__ import annotations

import math
from typing import Tuple

import numpy as np

BASES = np.frombuffer(b"ACGT", dtype=np.uint8).astype(np.int32)

Pairs = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def generate_pairs(n: int, read_len: int, edit_frac: float,
                   sub_prob: float, ins_prob: float, seed: int) -> Pairs:
    """-> (patterns [n, L+e], plen [n], texts [n, L+e], tlen [n]) int32.

    ``e = ceil(edit_frac * read_len)``; the pattern is the reference read
    and the text its mutated mate, both padded with 0 to the widest row
    either can reach, so every seed gives the same array shapes.
    """
    rng = np.random.default_rng([int(seed), 0])
    L = int(read_len)
    n_err = int(math.ceil(edit_frac * L))
    width = L + n_err
    ref = BASES[rng.integers(0, 4, size=(n, L))]
    pat = np.zeros((n, width), np.int32)
    pat[:, :L] = ref
    txt = pat.copy()
    tlen = np.full((n,), L, np.int64)
    n_edits = rng.integers(0, n_err + 1, size=n)
    cols = np.arange(width)[None, :]
    for j in range(n_err):
        live = n_edits > j
        r = rng.random(n)
        pos = (rng.random(n) * np.maximum(tlen, 1)).astype(np.int64)
        shift = rng.integers(1, 4, size=n)      # substitution: another base
        base = BASES[rng.integers(0, 4, size=n)]  # insertion: a random base
        sub = live & (r < sub_prob) & (tlen > 0)
        ins = live & (r >= sub_prob) & (r < sub_prob + ins_prob)
        dele = live & (r >= sub_prob + ins_prob) & (tlen > 0)
        rows = np.nonzero(sub)[0]
        old = txt[rows, pos[rows]]
        old_i = np.searchsorted(BASES, old)
        txt[rows, pos[rows]] = BASES[(old_i + shift[rows]) % 4]
        p = pos[:, None]
        if ins.any():
            right = np.concatenate([txt[:, :1], txt[:, :-1]], axis=1)
            moved = np.where(cols > p, right, txt)
            moved[np.arange(n), pos] = np.where(ins, base, moved[np.arange(n),
                                                                 pos])
            txt = np.where(ins[:, None], moved, txt)
            tlen = tlen + ins
        if dele.any():
            left = np.concatenate([txt[:, 1:], np.zeros((n, 1), np.int32)],
                                  axis=1)
            moved = np.where(cols >= p, left, txt)
            txt = np.where(dele[:, None], moved, txt)
            tlen = tlen - dele
    # clear anything past each mate's end (a deletion shifts a 0 in, an
    # insertion can push a base past the old end: both stay in width)
    txt = np.where(cols < tlen[:, None], txt, 0).astype(np.int32)
    plen = np.full((n,), L, np.int32)
    return pat, plen, txt, tlen.astype(np.int32)


def build_pool(traffic: dict, config: dict, seed: int) -> Pairs:
    """The cell's seeded pair pool."""
    return generate_pairs(int(traffic["pool_pairs"]), int(config["read_len"]),
                          float(traffic["edit_frac"]),
                          float(traffic["sub_prob"]),
                          float(traffic["ins_prob"]), seed)
