"""The plain reference the benchmark compares the timed path with.

It imports nothing of the program.  Scores come from Gotoh's gap-affine
dynamic programme over the whole (plen+1) x (tlen+1) matrix: match 0,
mismatch ``x``, a gap of length ``n`` costs ``o + n*e``; the answer is the
least cost of any global alignment.  The row loop is vectorised over pairs
(and over columns, with the horizontal gap taken as a running minimum), in
blocks that fit the host's cache, and blocks are spread over a few worker
processes, which touch no accelerator.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
from typing import Sequence

import numpy as np

BLOCK = 4096


def gotoh_block(P: np.ndarray, plen: np.ndarray, T: np.ndarray,
                tlen: np.ndarray, x: int, o: int, e: int) -> np.ndarray:
    """[n] least gap-affine cost of each pair (pattern rows i, text cols j).

    Arrays are laid out [column, pair], so every step, the running minimum
    included, is a vector operation across pairs.
    """
    n = P.shape[0]
    W = int(tlen.max(initial=0))
    Lp = int(plen.max(initial=0))
    # int16 holds every cost of short reads (and halves the memory
    # traffic); the sentinel stays far below the type's limit
    worst = (x + e) * (Lp + W) + o
    dt = np.int16 if worst < 1 << 13 else np.int32
    inf = (1 << 13) if dt == np.int16 else (1 << 28)
    TT = np.ascontiguousarray(T[:, :W].T)
    PT = np.ascontiguousarray(P[:, :Lp].T)
    j = np.arange(W + 1)[:, None]
    ej = (e * j).astype(dt)
    # row 0: H[0][j] = o + j*e (j > 0), H[0][0] = 0; no vertical gap yet
    H = (np.where(j > 0, o + j * e, 0) + np.zeros((1, n), int)).astype(dt)
    F = np.full((W + 1, n), inf, dt)
    G = np.empty_like(H)
    E = np.full_like(H, inf)
    tmp = np.empty_like(H)
    out = np.full((n,), -1, np.int32)
    cols = np.arange(n)
    done = plen == 0
    out[done] = H[tlen[done], cols[done]]
    for i in range(1, Lp + 1):
        np.add(H, o + e, out=tmp)                    # vertical gap
        np.add(F, e, out=F)
        np.minimum(tmp, F, out=F)
        sub = (PT[i - 1][None, :] != TT).astype(dt)
        sub *= x
        np.add(H[:-1], sub, out=G[1:])               # diagonal step
        np.minimum(G[1:], F[1:], out=G[1:])
        G[0] = F[0]
        # horizontal gap: E[j] = o + e*j + min_{j' < j} (G[j'] - e*j')
        np.subtract(G, ej, out=tmp)
        np.minimum.accumulate(tmp, axis=0, out=tmp)
        np.add(tmp[:-1], ej[1:] + o, out=E[1:])
        np.minimum(G, E, out=H)
        hit = plen == i
        if hit.any():
            out[hit] = H[tlen[hit], cols[hit]]
    return out


def _block_job(args):
    return gotoh_block(*args)


def gotoh_scores(P: np.ndarray, plen: np.ndarray, T: np.ndarray,
                 tlen: np.ndarray, pen: Sequence[int],
                 workers: int = 1) -> np.ndarray:
    """[n] reference scores, in blocks of ``BLOCK`` pairs over
    ``workers`` processes (spawned: no accelerator state is inherited)."""
    x, o, e = (int(v) for v in pen)
    jobs = [(P[lo:lo + BLOCK], plen[lo:lo + BLOCK], T[lo:lo + BLOCK],
             tlen[lo:lo + BLOCK], x, o, e)
            for lo in range(0, P.shape[0], BLOCK)]
    if workers <= 1 or len(jobs) <= 1:
        parts = [_block_job(j) for j in jobs]
    else:
        ctx = multiprocessing.get_context("spawn")
        with concurrent.futures.ProcessPoolExecutor(
                max_workers=min(workers, len(jobs)),
                mp_context=ctx) as pool:
            parts = list(pool.map(_block_job, jobs))
    return (np.concatenate(parts) if parts
            else np.zeros((0,), np.int32))
