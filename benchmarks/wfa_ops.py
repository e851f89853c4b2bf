"""Micro-decomposition of the WFA inner loop (DPU-kernel ops): cost of one
score step (recurrences) vs one extension trip vs a gather char fetch —
the quantities the Pallas kernel's VMEM schedule is built around."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import Row, time_fn
from repro.configs import wfa_paper
from repro.core.engine import AlignmentEngine, problem_bounds
from repro.core.wavefront import NEG, _extend, wfa_scores
from repro.data.reads import ReadPairSpec, generate_pairs


def run(batch: int = 1024, read_len: int = 100,
        edit_frac: float = 0.02) -> list[Row]:
    spec = ReadPairSpec(n_pairs=batch, read_len=read_len,
                        edit_frac=edit_frac, seed=3)
    P, plen, T, tlen = generate_pairs(spec)
    s_max, k_max = problem_bounds(wfa_paper.pen, plen, tlen, edit_frac)
    K = 2 * k_max + 1
    Pj, Tj = jnp.asarray(P), jnp.asarray(T)
    plj, tlj = jnp.asarray(plen), jnp.asarray(tlen)
    ks = jnp.arange(K, dtype=jnp.int32) - k_max

    rows: list[Row] = []

    # full solve
    sec = time_fn(lambda: wfa_scores(Pj, Tj, plj, tlj, pen=wfa_paper.pen,
                                     s_max=s_max, k_max=k_max).score,
                  warmup=1, iters=3)
    res = wfa_scores(Pj, Tj, plj, tlj, pen=wfa_paper.pen, s_max=s_max,
                     k_max=k_max)
    steps = int(res.n_steps)
    rows.append((f"wfa_ops/full-solve-b{batch}", sec * 1e6,
                 f"{batch / sec:,.0f} pairs/s, {steps} score steps"))
    rows.append((f"wfa_ops/per-score-step-b{batch}", sec / steps * 1e6,
                 f"K={K} diagonals live"))

    # one extension trip in isolation (jitted)
    M0 = jnp.full((batch, K), NEG, jnp.int32).at[:, k_max].set(0)
    ext = jax.jit(lambda M: _extend(M, Pj, Tj, plj, tlj, ks))
    sec_e = time_fn(ext, M0, warmup=1, iters=3)
    rows.append((f"wfa_ops/extend-full-lcp-b{batch}", sec_e * 1e6,
                 "greedy LCP along all diagonals (worst-case trips)"))

    # the gather primitive (take_along_axis char fetch)
    idx = jnp.clip(M0, 0, Tj.shape[1] - 1)
    fetch = jax.jit(lambda i: jnp.take_along_axis(Tj, i, axis=1))
    sec_f = time_fn(fetch, idx, warmup=1, iters=5)
    rows.append((f"wfa_ops/char-fetch-b{batch}", sec_f * 1e6,
                 f"[B={batch},K={K}] gather"))

    # end-to-end engine path (bucketing + executable cache + recovery):
    # the Total-vs-Kernel overhead the micro-ops above decompose
    eng = AlignmentEngine(wfa_paper.pen, backend="ring", edit_frac=edit_frac)
    eng.align_packed(P, plen, T, tlen)          # compile / populate cache
    sec_g = time_fn(lambda: eng.align_packed(P, plen, T, tlen).scores,
                    warmup=1, iters=3)
    res = eng.align_packed(P, plen, T, tlen)
    rows.append((f"wfa_ops/engine-cached-b{batch}", sec_g * 1e6,
                 f"{batch / sec_g:,.0f} pairs/s, "
                 f"{res.stats.cache_hits} cache hits, "
                 f"{res.stats.n_traces} retraces"))
    return rows
