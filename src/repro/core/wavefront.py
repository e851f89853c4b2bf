"""Batched pure-JAX Wavefront Algorithm (WFA, Marco-Sola et al. 2021).

This is the paper's algorithm, expressed so a *batch* of pairs advances in
lock-step (the TPU analogue of the paper's "each DPU thread aligns a pair
independently" — see DESIGN.md §2).  All buffers are statically sized from
``(s_max, k_max)`` bounds (``core.penalties`` / ``core.scoring``).

Conventions
-----------
pattern ``p`` (length ``n``, vertical axis), text ``t`` (length ``m``,
horizontal).  A wavefront cell on diagonal ``k = h - v`` stores the furthest
reaching *offset* ``h`` (text chars consumed) attainable with cost exactly
``s``; ``v = h - k`` is the pattern position.

Every solver takes a ``pen`` that may be a legacy gap-affine
:class:`~repro.core.penalties.Penalties` triple or any
:class:`~repro.core.scoring.PenaltyModel`; the model's ``kind`` statically
selects the recurrence:

* ``"affine"`` (gap cost o + L*e) — the classic three-matrix scheme:

      I_s[k] = max(M_{s-o-e}[k-1], I_{s-e}[k-1]) + 1    (gap consuming text)
      D_s[k] = max(M_{s-o-e}[k+1], D_{s-e}[k+1])        (gap consuming pat)
      M_s[k] = max(M_{s-x}[k] + 1, I_s[k], D_s[k])      (mismatch/close gap)

* ``"linear"`` (gap cost L*e; includes ``Edit`` where x = e = 1) — with no
  open cost the I/D fronts are redundant and the whole recurrence collapses
  to **one matrix** (one ring buffer, one backtrace plane, ~3x less state):

      M_s[k] = max(M_{s-x}[k] + 1, M_{s-e}[k-1] + 1, M_{s-e}[k+1])

Both kinds share the extend step ``M_s[k] += LCP(t[h:], p[v:])`` (free
matches) and terminate at the first ``s`` with ``M_s[m-n] == m``.  Invalid
cells hold ``NEG`` and all candidates are masked against the rectangle
``0 <= h <= m, 0 <= v <= n`` so out-of-board offsets never propagate.

A :class:`~repro.core.scoring.WavefrontHeuristic` (``heur=``) optionally
prunes k-lanes after each score step (WFA-adaptive band / z-drop): pruned
lanes are written back as ``NEG`` so they cost no extension work on any
later step and their provenance chains die.  On the step where a pair
*reaches* its target, that lane cannot be pruned under either built-in
policy (its remaining-distance estimate is 0 / its antidiagonal progress
maximal), so a reached score is always traceable — but mid-run the lane
carrying the eventual optimal path *can* lag and be pruned, which is
precisely how heuristic scores become approximate (an upper bound;
divergent pairs may stay unresolved at ``-1``).

Three modes:

* ``wfa_forward(..., keep_history=True)`` — full ``[s_max+1, B, K]``
  offset history (M/I/D for affine, M only for linear), enabling exact
  traceback (``core.cigar``).
* ``wfa_scores`` — ring buffer of depth ``window`` (the paper's
  WRAM-resident working set), score-only throughput mode.
* ``wfa_scores_packed`` — the ring buffer *plus* a packed backtrace: 2-bit
  per-cell provenance codes (which predecessor produced each
  furthest-reaching offset) packed 16 cells to an int32 word along the
  score axis.  ``core.cigar`` re-derives the exact alignment from the
  codes alone by replaying the provenance chain forward and re-extending
  matches against the sequences, so full CIGARs cost
  ``ceil((s_max+1)/16) * B * K`` int32 words per plane (3 planes for
  affine, 1 for linear) — ~16x less memory than the full history.

Provenance code values (2 bits each, 0 = invalid/never-written):

    affine M cell: 1 = from mismatch (M_{s-x}[k]+1), 2 = folded I_s[k],
                   3 = folded D_s[k]
    affine I cell: 1 = gap open (M_{s-o-e}[k-1]+1), 2 = extend (I_{s-e}[k-1]+1)
    affine D cell: 1 = gap open (M_{s-o-e}[k+1]),   2 = extend (D_{s-e}[k+1])
    linear M cell: 1 = mismatch (M_{s-x}[k]+1), 2 = insertion
                   (M_{s-e}[k-1]+1), 3 = deletion (M_{s-e}[k+1])
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import scoring
from repro.core.scoring import AdaptiveBand, ZDrop

NEG = -(1 << 20)  # invalid-cell sentinel; survives +1 arithmetic harmlessly
_VALID_THRESH = NEG // 2
_BIG = 1 << 20

# Packed-backtrace provenance codes (2 bits per cell; 0 = invalid).
BT_NONE = 0
BT_M_FROM_X, BT_M_FROM_I, BT_M_FROM_D = 1, 2, 3   # M-cell origins
BT_GAP_OPEN, BT_GAP_EXT = 1, 2                     # I/D-cell origins
TRACE_CELLS_PER_WORD = 16                          # 2-bit cells in an int32


def n_trace_words(s_max: int) -> int:
    """int32 words along the packed score axis covering s in [0, s_max]."""
    return (int(s_max) + TRACE_CELLS_PER_WORD) // TRACE_CELLS_PER_WORD


# Boundary states for sub-alignments (BiWFA recursion, ``repro.biwfa``).
# ``begin_state="I"`` means an insertion gap is already open when the
# alignment starts (continuing it pays only ``e`` per base, no open);
# ``end_state="I"`` means the alignment must end inside an insertion run
# (its cost is the I-matrix value: the final run's open IS charged).
# ``"M"`` on either side is the ordinary full-alignment boundary.
STATES = ("M", "I", "D")


def _check_states(model, begin_state: str, end_state: str) -> None:
    if begin_state not in STATES or end_state not in STATES:
        raise ValueError(f"boundary states must be one of {STATES}; got "
                         f"({begin_state!r}, {end_state!r})")
    if model.kind != "affine" and (begin_state != "M" or end_state != "M"):
        raise ValueError(
            "gap-linear/edit models have no I/D states; boundary-state "
            "sub-alignments need a gap-affine penalty model")


def _resolve(pen, heur):
    """Normalize (pen, heur) to (PenaltyModel, WavefrontHeuristic)."""
    return scoring.as_model(pen), scoring.as_heuristic(heur)


class WFAResult(NamedTuple):
    score: jax.Array            # [B] int32 alignment cost, -1 if > s_max
    m_hist: Optional[jax.Array]  # [s_max+1, B, K] or None
    i_hist: Optional[jax.Array]  # None for linear models (no I/D fronts)
    d_hist: Optional[jax.Array]
    # [] int32: score loop trips taken (telemetry); the Pallas kernel's
    # are summed over its grid blocks, each running its own loop, and
    # under ``shardmap`` come back as one sum per shard ([shards])
    n_steps: jax.Array
    m_bt: Optional[jax.Array] = None  # [n_trace_words, B, K] packed 2-bit
    i_bt: Optional[jax.Array] = None  # provenance codes, or None (score mode
    d_bt: Optional[jax.Array] = None  # / linear models)
    # [] int32 extend trips summed over the Pallas kernel's grid blocks
    # ([shards] under ``shardmap``; telemetry); None for backends without
    # a trip counter
    n_ext_trips: Optional[jax.Array] = None


def _shift_from_km1(w):
    """w[..., k] <- w[..., k-1]  (diagonal k reads its left neighbour)."""
    neg = jnp.full(w.shape[:-1] + (1,), NEG, w.dtype)
    return jnp.concatenate([neg, w[..., :-1]], axis=-1)


def _shift_from_kp1(w):
    """w[..., k] <- w[..., k+1]."""
    neg = jnp.full(w.shape[:-1] + (1,), NEG, w.dtype)
    return jnp.concatenate([w[..., 1:], neg], axis=-1)


def _extend(M, pattern, text, plen, tlen, ks):
    """Greedy diagonal extension, all (pair, diagonal) lanes in lock-step.

    One matched character per while-trip across the whole [B, K] front — the
    vectorized counterpart of the DPU's scalar per-diagonal extend loop.
    """
    Lt = text.shape[1]
    Lp = pattern.shape[1]
    ks2 = ks if ks.ndim == 2 else ks[None, :]   # [B, K] under a compact band

    def trip(state):
        M, _ = state
        h = M
        v = M - ks2
        can = ((M > _VALID_THRESH)
               & (h >= 0) & (h < tlen[:, None])
               & (v >= 0) & (v < plen[:, None]))
        tc = jnp.take_along_axis(text, jnp.clip(h, 0, Lt - 1), axis=1)
        pc = jnp.take_along_axis(pattern, jnp.clip(v, 0, Lp - 1), axis=1)
        adv = can & (tc == pc)
        return M + adv.astype(M.dtype), jnp.any(adv)

    def cond(state):
        return state[1]

    M, _ = lax.while_loop(cond, trip, trip((M, jnp.bool_(True))))
    return M


def keep_mask(heur, M, plen, tlen, ks):
    """[B, K] bool: lanes the heuristic keeps live after this score step.

    ``M`` is the post-extend M wavefront; ``plen``/``tlen`` must be
    column-broadcastable (``[B, 1]``) and ``ks`` row-broadcastable
    (``[1, K]`` or ``[B, K]``) against it — the shared implementation for
    the jnp solvers *and* the Pallas kernel (whose inputs are natively
    ``[BP, 1]`` / ``[BP, K]``), so a new heuristic lands here once and
    every backend prunes identically.

    Exact heuristics keep every lane; :class:`AdaptiveBand` prunes lanes
    whose remaining-distance estimate ``max(m - h, n - v)`` exceeds the
    front's best by more than ``max_distance_diff`` (only once more than
    ``min_wf_len`` lanes are live); :class:`ZDrop` prunes lanes whose
    antidiagonal progress ``h + v`` trails the front's best by more than
    ``zdrop``.  On its *reaching* step the target lane estimates 0 /
    progresses furthest and so survives (reached scores stay traceable);
    on earlier steps it can lag and be pruned — that is the
    approximation.
    """
    if heur.exact:
        return None
    valid = M > _VALID_THRESH
    h = M
    v = M - ks
    if isinstance(heur, AdaptiveBand):
        d = jnp.maximum(tlen - h, plen - v)
        d = jnp.where(valid, d, _BIG)
        d_min = jnp.min(d, axis=-1, keepdims=True)
        n_live = jnp.sum(valid.astype(jnp.int32), axis=-1, keepdims=True)
        return valid & ((n_live <= heur.min_wf_len)
                        | (d - d_min <= heur.max_distance_diff))
    if isinstance(heur, ZDrop):
        a = jnp.where(valid, h + v, -_BIG)
        best = jnp.max(a, axis=-1, keepdims=True)
        return valid & (best - a <= heur.zdrop)
    raise TypeError(f"unknown heuristic {heur!r}")


def _pruned(keep, *fronts):
    """Apply a keep mask to each non-None wavefront (None mask = exact)."""
    if keep is None:
        return fronts if len(fronts) > 1 else fronts[0]
    out = tuple(w if w is None else jnp.where(keep, w, NEG) for w in fronts)
    return out if len(out) > 1 else out[0]


def _prune_step(heur, plen, tlen, ks, *fronts):
    """One solver-side pruning step: mask from M (``fronts[0]``), applied
    to every front.  Broadcasts the solvers' [B]/[K] layout into
    :func:`keep_mask`'s 2-D convention."""
    keep = keep_mask(heur, fronts[0], plen[:, None], tlen[:, None],
                     ks[None, :])
    return _pruned(keep, *fronts)


# ---------------------------------------------------------------------------
# Compacting band (WFA-adaptive style).
#
# Under a pruning heuristic only a bounded span of diagonals stays live, so
# instead of masking dead lanes at full width K the solvers can carry the
# wavefronts at a *compact* width Kc and slide the window along the diagonal
# axis: each ring row stores, besides the Kc offsets, the absolute K-index of
# its lane 0 (``off``).  Per step the window re-centers on the live span of
# the previous front, reads from older rows realign by gathering with the
# offset delta, the target test and the ks plane shift by ``off``, and (in
# packed mode) provenance codes scatter back to absolute k before packing —
# so ``core.cigar`` decodes them unchanged.  Lanes that fall outside the
# window are pruned exactly as if the heuristic had killed them: when the
# heuristic's live span fits in Kc (see ``WavefrontHeuristic.band_cap``)
# results are bit-identical to the full-width solver; when it does not, the
# window truncation is just additional (heuristic-grade) pruning.
# ---------------------------------------------------------------------------


def _band_recenter(valid, prev_off, Kc, K):
    """New window offset centered on the live compact lanes ``valid`` [B,Kc].

    Keeps the previous offset when nothing is live (finished / diverged
    pairs just coast to loop exit)."""
    jidx = jnp.arange(Kc, dtype=jnp.int32)[None, :]
    lo = jnp.min(jnp.where(valid, jidx, Kc), axis=1)
    hi = jnp.max(jnp.where(valid, jidx, -1), axis=1)
    off = jnp.clip(prev_off + (lo + hi) // 2 - Kc // 2, 0, K - Kc)
    return jnp.where(hi >= lo, off, prev_off)


def _band_scatter(code, off, K):
    """Spread a compact [B, Kc] code plane to absolute width [B, K]."""
    Kc = code.shape[-1]
    idx = jnp.arange(K, dtype=jnp.int32)[None, :] - off[:, None]
    ok = (idx >= 0) & (idx < Kc)
    return jnp.where(ok, jnp.take_along_axis(code, jnp.clip(idx, 0, Kc - 1),
                                             axis=1), 0)


def _band_width(band_cap, K):
    """Validated compact width, or None to run full width."""
    if band_cap is None:
        return None
    Kc = max(int(band_cap), 9)     # floor keeps shifts/seed well-defined
    return Kc if Kc < K else None


def _next_affine(model, read_m, pattern, text, plen, tlen, ks,
                 read_i, read_d, with_codes=False, with_pre=False):
    """One gap-affine step: (M_s, I_s, D_s) from history accessors.

    ``read_m/read_i/read_d(delta)`` return the wavefront at score
    ``s - delta`` (NEG-filled when s - delta < 0).  With ``with_codes``
    also returns the 2-bit provenance code planes ``(code_m, code_i,
    code_d)`` recording which predecessor produced each cell (the
    packed-backtrace payload).
    """
    x, o, e = model.x, model.o, model.e
    m_owe = read_m(o + e)
    m_x = read_m(x)
    i_e = read_i(e)
    d_e = read_d(e)

    tl = tlen[:, None]
    pl = plen[:, None]
    ks2 = ks if ks.ndim == 2 else ks[None, :]

    # Insertion: source on diagonal k-1, offset +1; needs new h <= m.
    i_open = _shift_from_km1(m_owe)
    i_ext = _shift_from_km1(i_e)
    i_src = jnp.maximum(i_open, i_ext)
    I_new = i_src + 1
    I_new = jnp.where((i_src > _VALID_THRESH) & (I_new <= tl), I_new, NEG)

    # Deletion: source on diagonal k+1, offset unchanged; needs new v <= n.
    d_open = _shift_from_kp1(m_owe)
    d_ext = _shift_from_kp1(d_e)
    d_src = jnp.maximum(d_open, d_ext)
    D_new = jnp.where((d_src > _VALID_THRESH)
                      & (d_src - ks2 <= pl), d_src, NEG)

    # Mismatch: same diagonal, offset +1; consumes one char of each sequence.
    X_new = m_x + 1
    X_new = jnp.where((m_x > _VALID_THRESH) & (X_new <= tl)
                      & (X_new - ks2 <= pl), X_new, NEG)

    M_pre = jnp.maximum(jnp.maximum(X_new, I_new), D_new)
    M_new = _extend(M_pre, pattern, text, plen, tlen, ks)
    if with_pre:
        # pre-extension M wanted (bidir meet): the split-safety interval
        # needs both endpoints of each cell's free-match extension run.
        return M_new, I_new, D_new, M_pre
    if not with_codes:
        return M_new, I_new, D_new
    # Any candidate achieving the max is a valid optimal predecessor; the
    # tie-break (X, then I, then D; extend over open) is fixed so forward
    # and traceback agree deterministically.
    code_m = jnp.where(
        M_pre > _VALID_THRESH,
        jnp.where(M_pre == X_new, BT_M_FROM_X,
                  jnp.where(M_pre == I_new, BT_M_FROM_I, BT_M_FROM_D)),
        BT_NONE).astype(jnp.int32)
    code_i = jnp.where(
        I_new > _VALID_THRESH,
        jnp.where(i_ext >= i_open, BT_GAP_EXT, BT_GAP_OPEN),
        BT_NONE).astype(jnp.int32)
    code_d = jnp.where(
        D_new > _VALID_THRESH,
        jnp.where(d_ext >= d_open, BT_GAP_EXT, BT_GAP_OPEN),
        BT_NONE).astype(jnp.int32)
    return M_new, I_new, D_new, code_m, code_i, code_d


def _next_linear(model, read_m, pattern, text, plen, tlen, ks,
                 with_codes=False, with_pre=False):
    """One gap-linear step: M_s from the single M-history accessor.

    The one-matrix recurrence (module doc): gaps open and extend at the
    same cost, so insertions/deletions source directly from M at
    ``s - e``.  With ``with_codes`` also returns the M provenance plane
    (1 = mismatch, 2 = insertion, 3 = deletion).
    """
    x, e = model.x, model.e
    m_x = read_m(x)
    m_e = m_x if x == e else read_m(e)

    tl = tlen[:, None]
    pl = plen[:, None]
    ks2 = ks if ks.ndim == 2 else ks[None, :]

    i_src = _shift_from_km1(m_e)
    I_new = i_src + 1
    I_new = jnp.where((i_src > _VALID_THRESH) & (I_new <= tl), I_new, NEG)

    d_src = _shift_from_kp1(m_e)
    D_new = jnp.where((d_src > _VALID_THRESH)
                      & (d_src - ks2 <= pl), d_src, NEG)

    X_new = m_x + 1
    X_new = jnp.where((m_x > _VALID_THRESH) & (X_new <= tl)
                      & (X_new - ks2 <= pl), X_new, NEG)

    M_pre = jnp.maximum(jnp.maximum(X_new, I_new), D_new)
    M_new = _extend(M_pre, pattern, text, plen, tlen, ks)
    if with_pre:
        return M_new, M_pre
    if not with_codes:
        return M_new
    code_m = jnp.where(
        M_pre > _VALID_THRESH,
        jnp.where(M_pre == X_new, BT_M_FROM_X,
                  jnp.where(M_pre == I_new, BT_M_FROM_I, BT_M_FROM_D)),
        BT_NONE).astype(jnp.int32)
    return M_new, code_m


def _target_reached(M, plen, tlen, k_max, off=None):
    """[B] bool: does M hold offset == tlen on the final diagonal?

    ``off`` ([B]) is the window offset of a compacting band's lane 0."""
    k_final = tlen - plen + k_max                   # index into K axis
    if off is not None:
        k_final = k_final - off                     # compact index
    K = M.shape[-1]
    in_band = (k_final >= 0) & (k_final < K)
    idx = jnp.clip(k_final, 0, K - 1)
    val = jnp.take_along_axis(M, idx[:, None], axis=1)[:, 0]
    return in_band & (val >= tlen) & (val > _VALID_THRESH)


def _ring_read(ring, s, delta, W, off_hist=None, off=None):
    """Ring row at score ``s - delta`` (NEG where ``s < delta``).

    Under a compacting band (``off_hist`` [W, B], ``off`` [B]) the row is
    realigned from the window offset it was stored at to ``off``."""
    row = lax.rem(jnp.maximum(s - delta, 0), W)
    r = lax.dynamic_index_in_dim(ring, row, keepdims=False)
    if off_hist is None:
        return jnp.where(s >= delta, r, NEG)
    roff = lax.dynamic_index_in_dim(off_hist, row, keepdims=False)  # [B]
    Kc = r.shape[-1]
    idx = jnp.arange(Kc, dtype=jnp.int32)[None, :] + (off - roff)[:, None]
    ok = (idx >= 0) & (idx < Kc) & (s >= delta)
    return jnp.where(ok, jnp.take_along_axis(r, jnp.clip(idx, 0, Kc - 1),
                                             axis=1), NEG)


def _pack_codes(bt, s, code):
    """OR the [B, K] code plane into word s//16 at bit offset 2*(s%16)."""
    w = s // TRACE_CELLS_PER_WORD
    sh = 2 * lax.rem(s, TRACE_CELLS_PER_WORD)
    word = lax.dynamic_index_in_dim(bt, w, keepdims=False)
    return lax.dynamic_update_index_in_dim(
        bt, word | jnp.left_shift(code, sh), w, axis=0)


def _prep(pattern, text, plen, tlen):
    pattern = jnp.asarray(pattern)
    text = jnp.asarray(text)
    if pattern.dtype != jnp.int32:
        pattern = pattern.astype(jnp.int32)
    if text.dtype != jnp.int32:
        text = text.astype(jnp.int32)
    return pattern, text, jnp.asarray(plen, jnp.int32), jnp.asarray(tlen, jnp.int32)


@functools.partial(jax.jit, static_argnames=("pen", "s_max", "k_max",
                                             "keep_history", "heur",
                                             "begin_state", "end_state"))
def wfa_forward(pattern, text, plen, tlen, *, pen, s_max: int,
                k_max: int, keep_history: bool = True,
                heur=None, begin_state: str = "M",
                end_state: str = "M") -> WFAResult:
    """Full-history batched WFA.

    pattern/text: [B, Lp]/[B, Lt] integer codes (padding values arbitrary —
    bounds masking never reads past plen/tlen).  Returns per-pair cost and
    the wavefront history for traceback (M/I/D for affine models, M only
    for linear ones).

    ``begin_state``/``end_state`` select boundary states for BiWFA
    sub-alignments (affine only): begin ``"I"``/``"D"`` seeds the gap
    front at the origin with an already-open gap (continuation pays only
    ``e``); end ``"I"``/``"D"`` terminates on the gap front reaching the
    final cell (the alignment must end mid-gap).
    """
    model, heur = _resolve(pen, heur)
    _check_states(model, begin_state, end_state)
    pattern, text, plen, tlen = _prep(pattern, text, plen, tlen)
    B = pattern.shape[0]
    K = 2 * k_max + 1
    ks = jnp.arange(K, dtype=jnp.int32) - k_max
    affine = model.kind == "affine"

    hist_shape = (s_max + 1, B, K)
    m_hist = jnp.full(hist_shape, NEG, jnp.int32)
    i_hist = jnp.full(hist_shape, NEG, jnp.int32) if affine else None
    d_hist = jnp.full(hist_shape, NEG, jnp.int32) if affine else None

    # s = 0: M_0[k=0] = LCP(p, t); I/D invalid unless an open gap is
    # inherited from the caller (begin-state seeding).
    seed = jnp.full((B, K), NEG, jnp.int32).at[:, k_max].set(0)
    M0 = _extend(seed, pattern, text, plen, tlen, ks)
    m_hist = m_hist.at[0].set(M0)
    if affine:
        I0 = seed if begin_state == "I" else jnp.full((B, K), NEG, jnp.int32)
        D0 = seed if begin_state == "D" else jnp.full((B, K), NEG, jnp.int32)
        i_hist = i_hist.at[0].set(I0)
        d_hist = d_hist.at[0].set(D0)

    def end_front(M, I, D):
        return {"M": M, "I": I, "D": D}[end_state]

    front0 = M0 if not affine else end_front(M0, I0, D0)
    score0 = jnp.where(_target_reached(front0, plen, tlen, k_max), 0, -1)

    def read(hist, s, delta):
        row = lax.dynamic_index_in_dim(hist, jnp.maximum(s - delta, 0),
                                       keepdims=False)
        return jnp.where(s >= delta, row, NEG)

    if affine:
        def body(carry):
            s, score, m_hist, i_hist, d_hist = carry
            M_new, I_new, D_new = _next_affine(
                model, lambda d: read(m_hist, s, d), pattern, text,
                plen, tlen, ks, lambda d: read(i_hist, s, d),
                lambda d: read(d_hist, s, d))
            reached = _target_reached(end_front(M_new, I_new, D_new),
                                      plen, tlen, k_max)
            score = jnp.where((score < 0) & reached, s, score)
            M_new, I_new, D_new = _prune_step(heur, plen, tlen, ks,
                                              M_new, I_new, D_new)
            m_hist = lax.dynamic_update_index_in_dim(m_hist, M_new, s, axis=0)
            i_hist = lax.dynamic_update_index_in_dim(i_hist, I_new, s, axis=0)
            d_hist = lax.dynamic_update_index_in_dim(d_hist, D_new, s, axis=0)
            return s + 1, score, m_hist, i_hist, d_hist

        def cond(carry):
            s, score, *_ = carry
            return (s <= s_max) & jnp.any(score < 0)

        s, score, m_hist, i_hist, d_hist = lax.while_loop(
            cond, body, (jnp.int32(1), score0, m_hist, i_hist, d_hist))
    else:
        def body(carry):
            s, score, m_hist = carry
            M_new = _next_linear(model, lambda d: read(m_hist, s, d),
                                 pattern, text, plen, tlen, ks)
            reached = _target_reached(M_new, plen, tlen, k_max)
            score = jnp.where((score < 0) & reached, s, score)
            M_new = _prune_step(heur, plen, tlen, ks, M_new)
            m_hist = lax.dynamic_update_index_in_dim(m_hist, M_new, s, axis=0)
            return s + 1, score, m_hist

        def cond(carry):
            s, score, _ = carry
            return (s <= s_max) & jnp.any(score < 0)

        s, score, m_hist = lax.while_loop(
            cond, body, (jnp.int32(1), score0, m_hist))

    if keep_history:
        return WFAResult(score, m_hist, i_hist, d_hist, s)
    return WFAResult(score, None, None, None, s)


def _ring_loop(pattern, text, plen, tlen, model, heur, s_max, k_max,
               band_cap, packed, begin_state="M", end_state="M"):
    """The ring-buffer score loop behind :func:`wfa_scores` and
    :func:`wfa_scores_packed`.

    Specialised statically by ``packed`` (trace planes or none), by the
    model's ring count (3 for affine, 1 for linear; rings and planes are
    carried as tuples) and by the band width: at full width the loop
    carries no window offsets; under a compacting band (``band_cap``
    below K) each step re-centers the window and keeps each ring row's
    offset (see the compacting-band block comment).  Trace planes stay
    full width either way, so traceback is oblivious to the band."""
    B = pattern.shape[0]
    K = 2 * k_max + 1
    Kc = _band_width(band_cap, K)
    band = Kc is not None
    W = model.window
    n_rings = 3 if model.kind == "affine" else 1
    end = "MID".index(end_state)                    # front the target is on

    # data-dependent zero: keeps the while-loop carries' varying-manual-axes
    # consistent when this solver runs inside shard_map (per-shard loops)
    taint = (plen.reshape(-1)[0] * 0).astype(jnp.int32)
    if band:
        off0s = min(max(k_max - Kc // 2, 0), K - Kc)
        off0 = jnp.full((B,), off0s, jnp.int32) + taint
        off_hist = jnp.full((W, B), off0s, jnp.int32) + taint
        j0, width = k_max - off0s, Kc               # seed lane, in [0, Kc)

        def ks_of(off):
            return (off[:, None] + jnp.arange(Kc, dtype=jnp.int32)[None, :]
                    - k_max)
        ks0 = ks_of(off0)
    else:
        off0 = off_hist = None
        j0, width = k_max, K
        ks0 = jnp.arange(K, dtype=jnp.int32) - k_max

    seed0 = jnp.full((B, width), NEG, jnp.int32).at[:, j0].set(0)
    negBK = jnp.full((B, width), NEG, jnp.int32)
    fronts0 = ((_extend(seed0, pattern, text, plen, tlen, ks0),)
               + tuple(seed0 if begin_state == st else negBK
                       for st in "ID")[:n_rings - 1])
    rings = tuple((jnp.full((W, B, width), NEG, jnp.int32) + taint)
                  .at[0].set(f) for f in fronts0)
    reached0 = _target_reached(fronts0[end], plen, tlen, k_max, off0)
    score0 = jnp.where(reached0, 0, -1)
    bts = tuple(jnp.zeros((n_trace_words(s_max), B, K), jnp.int32) + taint
                for _ in range(n_rings)) if packed else ()

    def body(carry):
        s, score, rings, off_hist, bts = carry
        if band:
            prow = lax.rem(s - 1, W)
            # I/D fronts can outrun M between prunes; center on the union
            live = functools.reduce(jnp.logical_or, (
                lax.dynamic_index_in_dim(r, prow, keepdims=False)
                > _VALID_THRESH for r in rings))
            prev_off = lax.dynamic_index_in_dim(off_hist, prow,
                                                keepdims=False)
            off = _band_recenter(live, prev_off, Kc, K)
            ks = ks_of(off)
        else:
            off, ks = None, ks0
        reads = [lambda d, r=r: _ring_read(r, s, d, W, off_hist, off)
                 for r in rings]
        if n_rings == 3:
            out = _next_affine(model, reads[0], pattern, text, plen, tlen,
                               ks, reads[1], reads[2], with_codes=packed)
        else:
            out = _next_linear(model, reads[0], pattern, text, plen, tlen,
                               ks, with_codes=packed)
            out = out if packed else (out,)
        fronts, codes = out[:n_rings], out[n_rings:]
        reached = _target_reached(fronts[end], plen, tlen, k_max, off)
        score = jnp.where((score < 0) & reached, s, score)
        keep = keep_mask(heur, fronts[0], plen[:, None], tlen[:, None],
                         ks if band else ks[None, :])
        if keep is not None:
            fronts = tuple(jnp.where(keep, f, NEG) for f in fronts)

        row = lax.rem(s, W)
        rings = tuple(lax.dynamic_update_index_in_dim(r, f, row, axis=0)
                      for r, f in zip(rings, fronts))
        if band:
            off_hist = lax.dynamic_update_index_in_dim(off_hist, off, row,
                                                       axis=0)
            codes = tuple(_band_scatter(c, off, K) for c in codes)
        bts = tuple(_pack_codes(bt, s, c) for bt, c in zip(bts, codes))
        return s + 1, score, rings, off_hist, bts

    def cond(carry):
        s, score = carry[0], carry[1]
        return (s <= s_max) & jnp.any(score < 0)

    s, score, _, _, bts = lax.while_loop(
        cond, body, (jnp.int32(1), score0, rings, off_hist, bts))
    return WFAResult(score, None, None, None, s, *bts)


@functools.partial(jax.jit, static_argnames=("pen", "s_max", "k_max", "heur",
                                             "band_cap"))
def wfa_scores(pattern, text, plen, tlen, *, pen, s_max: int,
               k_max: int, heur=None, band_cap=None) -> WFAResult:
    """Ring-buffer batched WFA — score-only throughput mode.

    Memory: rings of ``[window, B, K]`` (3 for affine, 1 for linear) with
    ``window = max(x, o+e) + 1``, the WFA metadata the paper keeps hot in
    WRAM.  This is the jnp reference for the Pallas kernel (same rolling-
    window discipline).

    ``band_cap`` (static int) switches on the compacting band: wavefronts
    are carried at width ``min(band_cap, K)`` in a window that re-centers
    on the live diagonal span each step (see the compacting-band block
    comment).  Identical results to full width whenever the live span fits
    the window; otherwise the truncation acts as extra heuristic pruning —
    so pass it only alongside a non-exact ``heur`` (or when a plain banded
    approximation is explicitly wanted).
    """
    model, heur = _resolve(pen, heur)
    return _ring_loop(*_prep(pattern, text, plen, tlen), model, heur,
                      s_max, k_max, band_cap, packed=False)


@functools.partial(jax.jit, static_argnames=("pen", "s_max", "k_max", "heur",
                                             "begin_state", "end_state",
                                             "band_cap"))
def wfa_scores_packed(pattern, text, plen, tlen, *, pen,
                      s_max: int, k_max: int, heur=None,
                      begin_state: str = "M",
                      end_state: str = "M", band_cap=None) -> WFAResult:
    """Ring-buffer batched WFA *with* a packed backtrace.

    Identical wavefront recurrence and rolling-window memory discipline as
    :func:`wfa_scores` (the same loop), plus ``[n_trace_words, B, K]``
    int32 arrays of 2-bit provenance codes (16 score steps per word,
    OR-accumulated in the score loop) — three planes for affine models,
    one for linear.  ``core.cigar`` decodes them into exact CIGARs without
    ever materializing the full offset history.

    ``begin_state``/``end_state`` as in :func:`wfa_forward` (BiWFA
    sub-alignment boundaries, affine only).  The gap seed cell carries no
    provenance code; the traceback walker terminates on it directly.

    ``band_cap`` as in :func:`wfa_scores` — the backtrace planes stay full
    width (codes scatter to absolute k before packing), so ``core.cigar``
    decodes band-mode traces unchanged.
    """
    model, heur = _resolve(pen, heur)
    _check_states(model, begin_state, end_state)
    return _ring_loop(*_prep(pattern, text, plen, tlen), model, heur,
                      s_max, k_max, band_cap, packed=True,
                      begin_state=begin_state, end_state=end_state)


class BidirMeetResult(NamedTuple):
    """Per-pair breakpoint from the meet-in-the-middle solver.

    ``score`` mirrors :class:`WFAResult` (``starget`` where a breakpoint
    was found, ``-1`` where the fronts never joined) so the session's
    retirement path can block on / store it unchanged.
    """
    score: jax.Array       # [B] int32: starget if met, -1 if not
    n_steps: jax.Array     # [] int32 lockstep trips taken (telemetry)
    meet_state: jax.Array  # [B] 0 = M/M, 1 = I/I, 2 = D/D; -1 unmet
    meet_a: jax.Array      # [B] prefix-side cost at the breakpoint (the
                           #     forward cost convention; the suffix side is
                           #     always starget - meet_a)
    meet_b: jax.Array      # [B] detector-internal reverse-side cost (gap
                           #     joins re-charge the open; end-state I/D
                           #     shifts by -o) — use starget - meet_a for
                           #     the suffix child's cost
    meet_k: jax.Array      # [B] forward diagonal k = h - v of the breakpoint
    meet_h: jax.Array      # [B] text offset h of the breakpoint
    meet_safe: jax.Array   # [B] 1 = provably cost-exact split, 0 = accepted
                           #     opportunistically (recurse.py re-verifies)


def _reverse_rows(codes, lens):
    """Per-row suffix reversal: out[b, i] = codes[b, lens[b]-1-i], 0-padded.

    Padding value is irrelevant downstream — every solver masks reads
    beyond plen/tlen."""
    L = codes.shape[1]
    idx = lens[:, None] - 1 - jnp.arange(L, dtype=jnp.int32)[None, :]
    ok = idx >= 0
    g = jnp.take_along_axis(codes, jnp.clip(idx, 0, L - 1), axis=1)
    return jnp.where(ok, g, 0)


@functools.partial(jax.jit, static_argnames=("pen", "s_max", "k_max", "heur",
                                             "begin_state", "end_state"))
def wfa_bidir_meet(pattern, text, plen, tlen, starget, *, pen, s_max: int,
                   k_max: int, heur=None, begin_state: str = "M",
                   end_state: str = "M") -> BidirMeetResult:
    """Meet-in-the-middle BiWFA breakpoint solver (O(s) memory).

    Runs a forward wavefront on ``(p, t)`` and a reverse wavefront on the
    reversed pair in lockstep score steps, keeping only rolling windows of
    depth ``Wd = max(window, 2*max(x, o+e) + 2)`` — never a full history.
    ``starget`` ([B] int32) is each pair's known optimal cost (from a
    prior score-only pass); the solver looks for a *breakpoint*: a cell
    reached by the forward front at cost ``a`` and by the reverse front at
    cost ``b`` with

    * ``a + b == starget``          meeting in match/mismatch state (M/M)
    * ``a + b == starget + o``      meeting inside one gap run (I/I, D/D)
      — the gap open is charged by both halves, so the sum overshoots by
      exactly ``o``; the suffix half's true cost is ``b - o``.

    Forward diagonal ``k`` and reverse diagonal ``k' = (m-n) - k`` address
    the same cell; coverage ``h_f + h_r == m`` on complementary diagonals
    joins both coordinates at once (the pattern side follows from the
    diagonal identity).  Per step ``s`` the candidate cost splits
    ``(s, T-s)`` and ``(T-s, s)`` are examined, so every split with
    ``|a - b| < Wd`` is eventually checked — and along an optimal path
    some operation boundary (or in-gap position) always lands within
    ``max(x, o+e)`` of the half-cost point, which the window covers.

    An M/M candidate is *provably exact* when the split offset can be
    placed on both furthest-reaching match runs (pre-extension forward
    value ``<= m - h_rev``): then prefix cost ``a`` and suffix cost ``b``
    are simultaneously realized and ``a + b = starget`` forces both halves
    optimal.  Gap joins are exact at exact coverage.  Remaining coverage
    overshoots are accepted opportunistically with ``meet_safe = 0`` —
    ``repro.biwfa.recurse`` re-scores every stitched CIGAR and falls back
    to the packed-trace path on any mismatch, so end-to-end exactness
    never rests on the detector.

    With a non-exact heuristic both fronts prune identically to the
    forward solvers and breakpoints become approximate (or unmet);
    unresolved pairs surface as ``score = -1``.
    """
    model, heur = _resolve(pen, heur)
    _check_states(model, begin_state, end_state)
    pattern, text, plen, tlen = _prep(pattern, text, plen, tlen)
    starget = jnp.asarray(starget, jnp.int32)
    B = pattern.shape[0]
    K = 2 * k_max + 1
    affine = model.kind == "affine"
    o = model.o if affine else 0
    # end_state "I"/"D" segments charge the trailing run's gap open in the
    # forward cost convention, but the reverse rings seed that run at 0 (it
    # is the reversed problem's *leading* gap), so every reverse cost sits
    # exactly o below the forward-convention suffix cost — shift the
    # detection target once instead of special-casing every class
    oend = o if end_state != "M" else 0
    maxop = max(model.x, model.o + model.e) if affine \
        else max(model.x, model.e)
    Wd = max(model.window, 2 * maxop + 2)
    ks = jnp.arange(K, dtype=jnp.int32) - k_max
    bidx = jnp.arange(B)

    pr = _reverse_rows(pattern, plen)
    tr = _reverse_rows(text, tlen)

    seed = jnp.full((B, K), NEG, jnp.int32).at[:, k_max].set(0)
    negBK = jnp.full((B, K), NEG, jnp.int32)
    M0f = _extend(seed, pattern, text, plen, tlen, ks)
    M0r = _extend(seed, pr, tr, plen, tlen, ks)

    def ring0(row0):
        return jnp.full((Wd, B, K), NEG, jnp.int32).at[0].set(row0)

    fm, fmp, rm = ring0(M0f), ring0(seed), ring0(M0r)
    if affine:
        fi = ring0(seed if begin_state == "I" else negBK)
        fd = ring0(seed if begin_state == "D" else negBK)
        ri = ring0(seed if end_state == "I" else negBK)
        rd = ring0(seed if end_state == "D" else negBK)

    def read(ring, s, delta):
        return _ring_read(ring, s, delta, Wd)

    # complement-diagonal gather: rev K-index addressing the same cell
    jj = jnp.arange(K, dtype=jnp.int32)[None, :]
    jprime = (tlen - plen)[:, None] + 2 * k_max - jj
    jpok = (jprime >= 0) & (jprime < K)
    jpc = jnp.clip(jprime, 0, K - 1)

    def comp(arr):
        return jnp.where(jpok, jnp.take_along_axis(arr, jpc, axis=1), NEG)

    m2 = tlen[:, None]
    low = jnp.maximum(ks[None, :], 0)

    def body(carry):
        s, met, jst, ja, jb, jk, jh, jsf, rings = carry
        if affine:
            fm, fmp, fi, fd, rm, ri, rd = rings
            Mf, If, Df, Mfp = _next_affine(
                model, lambda d: read(fm, s, d), pattern, text, plen, tlen,
                ks, lambda d: read(fi, s, d), lambda d: read(fd, s, d),
                with_pre=True)
            Mr, Ir, Dr = _next_affine(
                model, lambda d: read(rm, s, d), pr, tr, plen, tlen,
                ks, lambda d: read(ri, s, d), lambda d: read(rd, s, d))
            Mf, If, Df, Mfp = _prune_step(heur, plen, tlen, ks,
                                          Mf, If, Df, Mfp)
            Mr, Ir, Dr = _prune_step(heur, plen, tlen, ks, Mr, Ir, Dr)
        else:
            fm, fmp, rm = rings
            Mf, Mfp = _next_linear(model, lambda d: read(fm, s, d),
                                   pattern, text, plen, tlen, ks,
                                   with_pre=True)
            Mr = _next_linear(model, lambda d: read(rm, s, d),
                              pr, tr, plen, tlen, ks)
            Mf, Mfp = _prune_step(heur, plen, tlen, ks, Mf, Mfp)
            Mr = _prune_step(heur, plen, tlen, ks, Mr)
        row = lax.rem(s, Wd)

        def put(ring, w):
            return lax.dynamic_update_index_in_dim(ring, w, row, axis=0)

        fm, fmp, rm = put(fm, Mf), put(fmp, Mfp), put(rm, Mr)
        if affine:
            fi, fd = put(fi, If), put(fd, Df)
            ri, rd = put(ri, Ir), put(rd, Dr)
            rings = (fm, fmp, fi, fd, rm, ri, rd)
        else:
            rings = (fm, fmp, rm)

        def at(ring, c):
            ok = (c >= 0) & (c <= s) & (c > s - Wd)
            sel = ring[lax.rem(jnp.maximum(c, 0), Wd), bidx]
            return jnp.where(ok[:, None], sel, NEG)

        def orient(a_m, a_g, b_m, b_g):
            """Candidate classes for prefix costs a_*, suffix costs b_*.

            Returns {name: (mask2d, state, a, b, h_plane, safe)} — a_m/b_m
            sum to starget (M/M), a_g/b_g to starget + o (gap joins)."""
            fa_m, fa_mp = at(fm, a_m), at(fmp, a_m)
            rb_m = comp(at(rm, b_m))
            vmm = (fa_m > _VALID_THRESH) & (rb_m > _VALID_THRESH)
            cov = vmm & (fa_m + rb_m >= m2)
            h_mm = jnp.clip(m2 - rb_m, low, jnp.maximum(fa_m, low))
            out = {"mm_safe": (cov & (fa_mp + rb_m <= m2), 0, a_m, b_m,
                               h_mm, 1),
                   "mm_cov": (cov, 0, a_m, b_m, h_mm, 0)}
            if affine:
                fa_i, rb_i = at(fi, a_g), comp(at(ri, b_g))
                fa_d, rb_d = at(fd, a_g), comp(at(rd, b_g))
                vii = (fa_i > _VALID_THRESH) & (rb_i > _VALID_THRESH)
                vdd = (fa_d > _VALID_THRESH) & (rb_d > _VALID_THRESH)
                out["ii0"] = (vii & (fa_i + rb_i == m2), 1, a_g, b_g,
                              fa_i, 1)
                out["dd0"] = (vdd & (fa_d + rb_d == m2), 2, a_g, b_g,
                              fa_d, 1)
                out["ii_cov"] = (vii & (fa_i + rb_i >= m2), 1, a_g, b_g,
                                 fa_i, 0)
                out["dd_cov"] = (vdd & (fa_d + rb_d >= m2), 2, a_g, b_g,
                                 fa_d, 0)
            return out

        sb = jnp.broadcast_to(s, (B,)).astype(jnp.int32)
        st2 = starget - oend
        A = orient(sb, sb, st2 - s, st2 + o - s)
        Bo = orient(st2 - s, st2 + o - s, sb, sb)
        names = ["mm_safe"] + (["ii0", "dd0"] if affine else []) \
            + ["mm_cov"] + (["ii_cov", "dd_cov"] if affine else [])
        for name in names:
            for side in (A, Bo):
                mask2d, stc, a_arr, b_arr, hplane, sf = side[name]
                anyk = jnp.any(mask2d, axis=1)
                kidx = jnp.argmax(mask2d, axis=1).astype(jnp.int32)
                hsel = jnp.take_along_axis(hplane, kidx[:, None],
                                           axis=1)[:, 0]
                take = (~met) & anyk
                met = met | take
                jst = jnp.where(take, stc, jst)
                ja = jnp.where(take, a_arr, ja)
                jb = jnp.where(take, b_arr, jb)
                jk = jnp.where(take, kidx - k_max, jk)
                jh = jnp.where(take, hsel, jh)
                jsf = jnp.where(take, sf, jsf)
        return s + 1, met, jst, ja, jb, jk, jh, jsf, rings

    def cond(carry):
        s, met, *_ = carry
        return (s <= s_max) & ~jnp.all(met)

    z = jnp.zeros((B,), jnp.int32)
    rings = (fm, fmp, fi, fd, rm, ri, rd) if affine else (fm, fmp, rm)
    s, met, jst, ja, jb, jk, jh, jsf, _ = lax.while_loop(
        cond, body, (jnp.int32(1), jnp.zeros((B,), bool), z - 1, z, z, z,
                     z, z, rings))
    return BidirMeetResult(jnp.where(met, starget, -1), s,
                           jnp.where(met, jst, -1), ja, jb, jk, jh, jsf)


def wfa_scores_shardmap(pattern, text, plen, tlen, *, pen,
                        s_max: int, k_max: int, mesh, axis_names=None,
                        heur=None, band_cap=None):
    """PIM-faithful distributed WFA: per-shard termination via shard_map.

    The ring solver per shard, kept for ``launch.lowering``'s dry-run
    cells; the ``shardmap`` backend runs the Pallas kernel per shard.

    The pjit formulation's while-condition ``any(score < 0)`` spans the
    GLOBAL batch, so SPMD inserts a small all-reduce every score iteration
    and every shard runs until the globally-slowest pair finishes.  Wrapping
    the ring-buffer solver in ``shard_map`` gives each shard its own loop —
    exactly the paper's "no inter-DPU communication": zero collectives in
    the lowered HLO (asserted by tests) and per-shard early exit.
    """
    from jax.sharding import PartitionSpec as P

    names = tuple(axis_names if axis_names is not None else mesh.axis_names)
    spec2 = P(names, None)
    spec1 = P(names)

    def local(p, t, pl, tl):
        return wfa_scores(p, t, pl, tl, pen=pen, s_max=s_max,
                          k_max=k_max, heur=heur, band_cap=band_cap).score

    # the per-shard score loop is replication-safe by construction, so
    # the varying-manual-axes check is off
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec2, spec2, spec1, spec1),
                       out_specs=spec1, check_vma=False)
    return fn(pattern, text, plen, tlen)
