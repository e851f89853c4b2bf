"""The algorithm's work per pair, counted the same whatever implements it.

Work depends only on ``(plen, tlen, score, penalties)`` and the output
mode: never on the kernel's padding, block size, diagonal width or how
it fetches characters.  It is a lower bound on what any gap-affine WFA
must do, so the roofline share it gives cannot pass 100%.

Operations.  A gap-affine WFA computes, for each score ``s`` from 0 to the
pair's optimal score, three wavefronts over diagonal ranges that follow
from the recurrence alone (diagonal ``k = h - v``, text offset ``h``,
pattern offset ``v``)::

    I_s = hull(M_{s-o-e}, I_{s-e}) + 1
    D_s = hull(M_{s-o-e}, D_{s-e}) - 1
    M_s = hull(M_{s-x}, I_s, D_s),      M_0 = [0, 0]

each clipped to the matrix's diagonals ``[-plen, tlen]`` (an empty or
sourceless range is no wavefront).  A cell of ``I`` costs 2 int32 vector
operations (a max and the +1), of ``D`` 1 (a max), of ``M`` 3 (the +1 of
the mismatch step and two maxes), and every ``M`` cell at least one
compare that ends its extension.  Every matched base costs one more
compare; an optimal alignment matches at least
``min(plen, tlen) - score // min(x, e)`` bases, since each base of the
shorter read that is not matched costs at least ``min(x, e)``.

Bytes.  The two reads at one byte per base, plus the two lengths and the
score at four bytes each, plus in CIGAR mode one byte per alignment
column, of which there are at least ``max(plen, tlen)``.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

OPS_M, OPS_I, OPS_D = 3, 2, 1

Range = Optional[Tuple[int, int]]


def _hull(*rs: Range) -> Range:
    live = [r for r in rs if r is not None]
    if not live:
        return None
    return min(r[0] for r in live), max(r[1] for r in live)


def _shift(r: Range, d: int) -> Range:
    return None if r is None else (r[0] + d, r[1] + d)


def wavefront_ranges(max_score: int, x: int, o: int, e: int
                     ) -> List[Tuple[Range, Range, Range]]:
    """[(M_s, I_s, D_s)] unclipped diagonal ranges for s = 0..max_score."""
    out: List[Tuple[Range, Range, Range]] = []

    def get(s: int, c: int) -> Range:
        return out[s][c] if 0 <= s < len(out) else None

    for s in range(max_score + 1):
        if s == 0:
            out.append(((0, 0), None, None))
            continue
        m_open = get(s - o - e, 0)
        ins = _shift(_hull(m_open, get(s - e, 1)), 1)
        dele = _shift(_hull(m_open, get(s - e, 2)), -1)
        out.append((_hull(get(s - x, 0), ins, dele), ins, dele))
    return out


def cells(plen: np.ndarray, tlen: np.ndarray, score: np.ndarray,
          pen: Tuple[int, int, int]) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
    """-> ([n] M cells, [n] I cells, [n] D cells) over s = 0..score."""
    x, o, e = pen
    plen = np.asarray(plen, np.int64)
    tlen = np.asarray(tlen, np.int64)
    score = np.asarray(score, np.int64)
    n = plen.shape[0]
    acc = [np.zeros(n, np.int64) for _ in range(3)]
    if n == 0:
        return tuple(acc)
    for s, rs in enumerate(wavefront_ranges(int(score.max()), x, o, e)):
        live = score >= s
        for c, r in enumerate(rs):
            if r is not None:
                lo = np.maximum(r[0], -plen)
                hi = np.minimum(r[1], tlen)
                acc[c] += np.where(live, np.maximum(hi - lo + 1, 0), 0)
    return tuple(acc)


def pair_ops(plen, tlen, score, pen) -> np.ndarray:
    """[n] int32 vector operations a WFA needs for each pair, at least."""
    m, i, d = cells(plen, tlen, score, pen)
    x, _, e = pen
    matched = np.maximum(np.minimum(plen, tlen).astype(np.int64)
                         - np.asarray(score, np.int64) // min(x, e), 0)
    return (OPS_M + 1) * m + OPS_I * i + OPS_D * d + matched


def pair_bytes(plen, tlen, output: str) -> np.ndarray:
    """[n] bytes each pair must move, at least."""
    plen = np.asarray(plen, np.int64)
    tlen = np.asarray(tlen, np.int64)
    b = plen + tlen + 12
    if output == "cigar":
        b = b + np.maximum(plen, tlen)
    return b


def least_seconds(plen, tlen, score, pen, output: str,
                  peak: dict) -> Tuple[float, str]:
    """-> (least device seconds for all the pairs, "ops" or "bytes": the
    bound that sets it), from the peaks table entry ``peak``."""
    t_ops = float(pair_ops(plen, tlen, score, pen).sum()) / float(
        peak["int32_ops_per_s"])
    t_bytes = float(pair_bytes(plen, tlen, output).sum()) / float(
        peak["hbm_bytes_per_s"])
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
