"""Pallas TPU kernel for batched WFA — the DPU inner loop, re-vectorized.

Hardware mapping (DESIGN.md §2):

* one **grid program** ≙ one DPU: it owns a block of ``BP`` pairs and runs
  their entire alignment without leaving VMEM — the whole score loop is a
  ``lax.while_loop`` *inside* the kernel body with an all-pairs-done early
  exit per block;
* **BlockSpec** HBM→VMEM tiling of the pair batch ≙ the MRAM→WRAM DMA;
* the wavefront **ring buffers** (depth ``window = max(x,o+e)+1``) live in
  VMEM scratch ≙ the WFA metadata the paper keeps hot in WRAM;
* wavefronts are laid out ``[pairs, diagonals]`` on (sublane, lane) —
  every arithmetic op is a full-width vector op;
* no communication between grid programs ≙ no inter-DPU communication.

Character fetch during extension is selected by the static ``gather`` mode:

* ``"onehot"`` — compare-and-reduce (``sum_l [idx == l] * seq[l]``), the
  only formulation a real TPU VPU supports (no per-lane gather); it
  materializes a ``[BP, K, L]`` intermediate per extend trip, which is
  exactly what made interpret mode ~100x slower than the jnp ring solver;
* ``"index"`` — ``jnp.take_along_axis``: in interpret mode the kernel body
  is discharged to plain jax ops on CPU, where a real gather exists and is
  ~25x faster.  The wrapper defaults to ``index`` under ``interpret`` and
  ``onehot`` when compiled.

The sequences arrive as **packed words** (built by the ops wrapper):
word ``l`` holds characters ``l .. l + C - 1`` at ``char_bits`` bits each,
character ``l`` in the lowest field, with ``C = 32 // char_bits`` (16 for
2-bit DNA, 4 for bytes, 1 for raw int32 codes).  One extend trip fetches
the text word at ``M`` and the pattern word at ``M - k``; the trailing
zero fields of their XOR are the matched prefix, so each lane advances by
up to ``C`` characters per trip (the WFA2-lib word compare, with a field
count in place of a count of trailing zero bits).  Every lane still stops
at its maximal offset, so scores and trace codes do not depend on ``C``.

``band_cap`` switches on the **compacting band** (the in-kernel counterpart
of ``core.wavefront``'s ``band_cap``): rings are allocated at a compact
width ``Kc`` and a per-block window offset tracks where those lanes sit on
the absolute diagonal axis.  Each step the window re-centers on the union
of the block's live lanes (min/max reduction), ring reads from older score
rows realign by the offset delta (pad + dynamic slice — no gather needed),
and packed-backtrace codes scatter to absolute k before OR-packing, so the
trace decoder is oblivious.  Lanes outside the window are pruned exactly
like heuristic kills; when the heuristic's live span fits ``Kc`` the
results are identical to full width.

The kernel is specialized per **penalty model** (``core.scoring``): affine
models run the three-matrix M/I/D recurrence over three VMEM rings;
linear models (``GapLinear`` / ``Edit``) collapse to the one-matrix
recurrence over a **single** ring.  A **wavefront heuristic**
(``AdaptiveBand`` / ``ZDrop``) optionally masks pruned k-lanes to the
invalid sentinel after each step (shared ``keep_mask`` policy).

Two output modes, built from the same kernel body:

* score-only (throughput) — exactly like the ring-buffer jnp reference
  ``kernels.wfa.ref.ref_scores`` it is validated against;
* packed backtrace (``trace=True``) — additionally OR-accumulates 2-bit
  per-cell provenance codes into ``[n_words, B, K]`` int32 words (16 score
  steps per word, same encoding as ``core.wavefront.wfa_scores_packed``;
  three planes for affine, one for linear), which ``core.cigar`` decodes
  into exact CIGARs on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import scoring
from repro.core.wavefront import (BT_GAP_EXT, BT_GAP_OPEN, BT_M_FROM_D,
                                  BT_M_FROM_I, BT_M_FROM_X,
                                  TRACE_CELLS_PER_WORD, keep_mask,
                                  n_trace_words)

NEG = -(1 << 20)
_THRESH = NEG // 2

# The TPU compiler's default scoped-VMEM limit for one kernel.
SCOPED_VMEM_BYTES = 16 << 20


def vmem_estimate(block_pairs: int, k_pad: int, n_bt: int,
                  n_words: int) -> int:
    """Scoped VMEM bytes a compiled block needs, calibrated on v5e compiles.

    About 4.2 KB per (pair, diagonal lane) for the rings, the [BP, K]
    fronts and the onehot fetch, plus the double-buffered packed-trace
    output blocks (``n_bt`` planes of ``n_words`` int32 words).  At 8 pairs
    per block that admits k_pad <= 384 at every bucket width from 128 to
    1024, and trace depth up to s_max = 704 at k_pad = 384.
    """
    return block_pairs * k_pad * (4332 + 8 * n_bt * n_words)


def _gather_chars(seq, idx, mode: str):
    """seq [BP, L], idx [BP, K] -> seq[b, idx[b, k]] as [BP, K].

    idx is pre-clipped here; out-of-range lanes read junk that the caller's
    validity mask discards.  ``onehot`` is the VPU compare-and-reduce
    formulation (TPUs lack per-lane gather), taken 128 diagonal lanes at a
    time so that its [BP, 128, L] intermediate, not a [BP, K, L] one, sets
    its scoped VMEM; ``index`` is a real gather for interpret mode, where
    the body runs as plain jax ops.
    """
    L = seq.shape[1]
    idx_c = jnp.clip(idx, 0, L - 1)
    if mode == "index":
        return jnp.take_along_axis(seq, idx_c, axis=1)
    BP, K = idx.shape
    parts = []
    for k0 in range(0, K, 128):
        part = idx_c[:, k0:k0 + 128]
        l_iota = lax.broadcasted_iota(jnp.int32, part.shape + (L,), 2)
        hit = (l_iota == part[:, :, None])
        parts.append(jnp.sum(jnp.where(hit, seq[:, None, :], 0), axis=2))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _zero_fields(x, char_bits: int):
    """Trailing zero ``char_bits``-wide fields of each int32 in x: 0 .. C,
    C = 32 // char_bits where x == 0.

    A binary search of masks and compares (log2 C rounds), since a bit
    count may not lower in Mosaic.  The shifts are logical: the sign bit
    is a data bit."""
    C = 32 // char_bits
    n = jnp.zeros_like(x)
    y = x
    half = 16
    while half >= char_bits:
        z = (y & ((1 << half) - 1)) == 0
        n = n + jnp.where(z, half // char_bits, 0)
        y = jnp.where(z, lax.shift_right_logical(y, jnp.full_like(y, half)),
                      y)
        half //= 2
    return jnp.where(x == 0, C, n)


def _make_kernel(model, heur, s_max: int, k_pad: int, trace: bool,
                 gather: str, char_bits: int, band: bool):
    x, o, e = model.x, model.o, model.e
    W = model.window
    affine = model.kind == "affine"
    n_bt = (3 if affine else 1) if trace else 0
    C = 32 // char_bits                     # characters per packed word
    kc_full = k_pad // 2                     # absolute diagonal center

    def kernel(p_ref, t_ref, pl_ref, tl_ref, out_ref, steps_ref, trips_ref,
               *refs):
        bt_refs = refs[:n_bt]
        rings = refs[:-1][n_bt:] if band else refs[n_bt:]
        off_ref = refs[-1] if band else None  # [W, 1] SMEM row offsets
        if affine:
            m_ring, i_ring, d_ring = rings
        else:
            (m_ring,) = rings
        BP, Lp = p_ref.shape
        _, Lt = t_ref.shape
        Kc = m_ring.shape[-1]                # compact (== k_pad unless band)

        pat = p_ref[...]
        txt = t_ref[...]
        plen = pl_ref[...]                   # [BP, 1]
        tlen = tl_ref[...]
        jidx = lax.broadcasted_iota(jnp.int32, (BP, Kc), 1)

        def ks_of(off):
            """Absolute diagonal of each compact lane (off = 0 unbanded)."""
            return jidx + (off - kc_full)

        def extend(M, ks):
            """-> (extended M, extend trips taken: one per lock-step
            word fetch over the block's [BP, Kc] front)."""
            def trip(st):
                M, _, n = st
                n = n + 1
                v = M - ks
                can = ((M > _THRESH) & (M >= 0) & (M < tlen)
                       & (v >= 0) & (v < plen))
                tw = _gather_chars(txt, M, gather)
                pw = _gather_chars(pat, v, gather)
                # The onehot fetch's lane reduce leaves the words one per
                # sublane row, 8 to a vreg.  Selecting on `can`, laid out
                # like the front (1,024 to a vreg), moves the XOR into the
                # front's layout once, so the field count and the clamp
                # below run on that; -1 matches no field.
                x = jnp.where(can, tw ^ pw, -1)
                # matched prefix of the two words, cut at either end
                run = jnp.minimum(jnp.minimum(tlen - M, plen - v),
                                  _zero_fields(x, char_bits))
                adv = jnp.where(can, run, 0)
                # a lane that matched a whole word may match further
                return M + adv, jnp.any(adv == C), n

            st = trip((M, jnp.bool_(True), jnp.int32(0)))
            M, _, n = lax.while_loop(lambda st: st[1], trip, st)
            return M, n

        def reached(M, ks):
            """[BP, 1] bool: furthest offset hit the (tlen, plen) corner."""
            k_final = tlen - plen            # [BP, 1] diagonal value
            hit = (ks == k_final) & (M >= tlen) & (M > _THRESH)
            return jnp.any(hit, axis=1, keepdims=True)

        def prune(M, ks):
            # shared policy implementation; plen/tlen/ks are already in
            # keep_mask's 2-D convention ([BP, 1] / [BP, Kc])
            keep = keep_mask(heur, M, plen, tlen, ks)
            if keep is None:
                return M, None
            return jnp.where(keep, M, NEG), keep

        def store_row(ring, row, val):
            ring[pl.ds(row, 1)] = val[None]

        neg_kc = jnp.full((BP, Kc), NEG, jnp.int32)

        def load_row(ring, s, delta, off):
            row = lax.rem(jnp.maximum(s - delta, 0), W)
            val = ring[pl.ds(row, 1)][0]
            if band:
                # realign the stored window to the current offset:
                # val'[j] = val[j + shift], NEG where j + shift leaves the
                # window (a lane rotation plus a mask — no gather, no
                # dynamic slice, both of which Mosaic refuses)
                shift = jnp.clip(off - off_ref[row, 0], -Kc, Kc)
                rolled = pltpu.roll(val, lax.rem(Kc - shift, Kc), 1)
                src = jidx + shift
                val = jnp.where((src >= 0) & (src < Kc), rolled, NEG)
            return jnp.where(s >= delta, val, NEG)

        def scatter_full(code, off):
            """Place a compact [BP, Kc] plane at absolute k (width k_pad):
            zero-extend to k_pad lanes and rotate by ``off`` (the clip in
            ``recenter`` keeps off + Kc <= k_pad, so only zeros wrap)."""
            if not band:
                return code
            full = jnp.concatenate(
                [code, jnp.zeros((BP, k_pad - Kc), jnp.int32)], axis=1)
            return pltpu.roll(full, off, 1)

        def pack_code(bt_ref, s, code, off):
            """OR the 2-bit code plane into word s//16 of bt_ref."""
            w = s // TRACE_CELLS_PER_WORD
            sh = 2 * lax.rem(s, TRACE_CELLS_PER_WORD)
            cur = bt_ref[pl.ds(w, 1)]
            full = scatter_full(code, off)
            bt_ref[pl.ds(w, 1)] = cur | jnp.left_shift(full, sh)[None]

        # s = 0
        if trace:
            # out buffers are uninitialized; codes are OR-accumulated
            for bt in bt_refs:
                bt[...] = jnp.zeros_like(bt)
        if band:
            off0 = min(max(kc_full - Kc // 2, 0), k_pad - Kc)
            for w in range(W):               # SMEM takes scalar stores only
                off_ref[w, 0] = jnp.int32(off0)
        else:
            off0 = 0
        ks0 = ks_of(off0)
        M0, trips0 = extend(jnp.where(ks0 == 0, 0, NEG), ks0)
        store_row(m_ring, 0, M0)
        if affine:
            store_row(i_ring, 0, neg_kc)
            store_row(d_ring, 0, neg_kc)
        score0 = jnp.where(reached(M0, ks0), 0, -1)

        neg_col = jnp.full((BP, 1), NEG, jnp.int32)
        sh_r = lambda w: jnp.concatenate([neg_col, w[:, :-1]], axis=1)
        sh_l = lambda w: jnp.concatenate([w[:, 1:], neg_col], axis=1)

        def recenter(s):
            """New window offset from the previous row's live lanes."""
            if not band:
                return 0
            prow = lax.rem(s - 1, W)
            live = m_ring[pl.ds(prow, 1)][0] > _THRESH
            if affine:
                # I/D fronts can outrun M between prunes; use the union
                live = (live | (i_ring[pl.ds(prow, 1)][0] > _THRESH)
                        | (d_ring[pl.ds(prow, 1)][0] > _THRESH))
            poff = off_ref[prow, 0]
            lo = jnp.min(jnp.where(live, jidx, Kc))
            hi = jnp.max(jnp.where(live, jidx, -1))
            new = jnp.clip(poff + (lo + hi) // 2 - Kc // 2, 0, k_pad - Kc)
            return jnp.where(hi >= lo, new, poff)

        def body(carry):
            s, score, trips = carry
            off = recenter(s)
            ks = ks_of(off)
            m_x = load_row(m_ring, s, x, off)
            if affine:
                m_owe = load_row(m_ring, s, o + e, off)
                i_e = load_row(i_ring, s, e, off)
                d_e = load_row(d_ring, s, e, off)
                i_open, i_ext = sh_r(m_owe), sh_r(i_e)
                i_src = jnp.maximum(i_open, i_ext)
                d_open, d_ext = sh_l(m_owe), sh_l(d_e)
                d_src = jnp.maximum(d_open, d_ext)
            else:
                m_e = m_x if x == e else load_row(m_ring, s, e, off)
                i_src = sh_r(m_e)
                d_src = sh_l(m_e)

            I_new = jnp.where((i_src > _THRESH) & (i_src + 1 <= tlen),
                              i_src + 1, NEG)
            D_new = jnp.where((d_src > _THRESH) & (d_src - ks <= plen),
                              d_src, NEG)
            X_new = jnp.where((m_x > _THRESH) & (m_x + 1 <= tlen)
                              & (m_x + 1 - ks <= plen), m_x + 1, NEG)
            M_pre = jnp.maximum(jnp.maximum(X_new, I_new), D_new)
            M_new, n_trips = extend(M_pre, ks)

            if trace:
                # codes from the PRE-prune fronts — bit-identical to
                # wfa_scores_packed even on lanes a heuristic then kills
                # (those codes are unreachable in traceback either way)
                code_m = jnp.where(
                    M_pre > _THRESH,
                    jnp.where(M_pre == X_new, BT_M_FROM_X,
                              jnp.where(M_pre == I_new, BT_M_FROM_I,
                                        BT_M_FROM_D)), 0)
                pack_code(bt_refs[0], s, code_m, off)
                if affine:
                    code_i = jnp.where(
                        I_new > _THRESH,
                        jnp.where(i_ext >= i_open, BT_GAP_EXT,
                                  BT_GAP_OPEN), 0)
                    code_d = jnp.where(
                        D_new > _THRESH,
                        jnp.where(d_ext >= d_open, BT_GAP_EXT,
                                  BT_GAP_OPEN), 0)
                    pack_code(bt_refs[1], s, code_i, off)
                    pack_code(bt_refs[2], s, code_d, off)

            score = jnp.where((score < 0) & reached(M_new, ks), s, score)
            M_new, keep = prune(M_new, ks)
            if affine and keep is not None:
                I_new = jnp.where(keep, I_new, NEG)
                D_new = jnp.where(keep, D_new, NEG)

            row = lax.rem(s, W)
            store_row(m_ring, row, M_new)
            if affine:
                store_row(i_ring, row, I_new)
                store_row(d_ring, row, D_new)
            if band:
                off_ref[row, 0] = off
            return s + 1, score, trips + n_trips

        def cond(carry):
            s, score, _ = carry
            return (s <= s_max) & jnp.any(score < 0)

        s_end, score, trips = lax.while_loop(
            cond, body, (jnp.int32(1), score0, trips0))
        out_ref[...] = score
        # the block's loop counters, broadcast over its rows: score steps
        # (rows 0 .. s_end - 1 computed) and extend trips
        steps_ref[...] = jnp.broadcast_to(s_end, steps_ref.shape)
        trips_ref[...] = jnp.broadcast_to(trips, trips_ref.shape)

    return kernel, W, affine


@functools.partial(jax.jit, static_argnames=("pen", "s_max", "k_pad",
                                             "block_pairs", "interpret",
                                             "trace", "heur", "gather",
                                             "char_bits", "band_cap"))
def wfa_pallas(pattern, text, plen, tlen, *, pen, s_max: int,
               k_pad: int, block_pairs: int = 8, interpret: bool = True,
               trace: bool = False, heur=None, gather=None,
               char_bits: int = 32, band_cap=None):
    """pattern/text [B, L*] int32 packed words of ``char_bits``-bit
    characters (B % block_pairs == 0, L* % 128 == 0; with ``char_bits``
    32 the words are the codes themselves), plen/tlen [B, 1] int32 in
    characters, k_pad % 128 == 0 is the padded diagonal count.
    -> (score [B, 1] int32, steps [B, 1] int32, trips [B, 1] int32), the
    last two the block's score steps and extend trips on each of its rows;
    with ``trace`` additionally
    the [n_words, B, k_pad] int32 packed provenance arrays (three for
    affine models, one for linear).

    ``gather`` (``"index"``/``"onehot"``; None = index under interpret,
    onehot compiled), ``char_bits`` (2, 8 or 32) and ``band_cap`` (compact
    ring width, lane-aligned by the ops wrapper; None = full width) are
    static — see the module docstring.
    """
    B, Lp = pattern.shape
    Lt = text.shape[1]
    BP = block_pairs
    assert B % BP == 0, (B, BP)
    model = scoring.as_model(pen)
    heur = scoring.as_heuristic(heur)
    if gather is None:
        gather = "index" if interpret else "onehot"
    band = band_cap is not None and band_cap < k_pad
    Kc = band_cap if band else k_pad
    n_bt = (3 if model.kind == "affine" else 1) if trace else 0
    if not interpret:
        need = vmem_estimate(BP, k_pad, n_bt, n_trace_words(s_max))
        if need > SCOPED_VMEM_BYTES:
            raise ValueError(
                f"WFA kernel block too wide for the TPU's scoped VMEM: "
                f"block_pairs={BP}, k_pad={k_pad}, s_max={s_max}, "
                f"trace={trace} needs ~{need / 2**20:.1f} MiB of "
                f"{SCOPED_VMEM_BYTES >> 20} MiB (bucket [{B}, {Lp}]); "
                f"align this bucket on the 'ring' backend")
    kernel, W, affine = _make_kernel(model, heur, s_max, k_pad, trace,
                                     gather, char_bits, band)
    grid = (B // BP,)
    n_rings = 3 if affine else 1

    spec2 = lambda L: pl.BlockSpec((BP, L), lambda i: (i, 0))
    out_specs = [spec2(1)] * 3
    out_shape = [jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 3
    if trace:
        NW = n_trace_words(s_max)
        bt_spec = pl.BlockSpec((NW, BP, k_pad), lambda i: (0, i, 0))
        out_specs += [bt_spec] * n_bt
        out_shape += [jax.ShapeDtypeStruct((NW, B, k_pad), jnp.int32)] * n_bt
    scratch = [pltpu.VMEM((W, BP, Kc), jnp.int32)] * n_rings
    if band:
        scratch += [pltpu.SMEM((W, 1), jnp.int32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec2(Lp), spec2(Lt), spec2(1), spec2(1)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(pattern, text, plen, tlen)


def _make_meet_kernel(model, heur, s_max: int, k_pad: int,
                      begin_state: str, end_state: str):
    """BiWFA meet-in-the-middle as one fused grid program.

    Port of ``core.wavefront.wfa_bidir_meet`` (same candidate classes,
    window ``Wd`` and safety flags — see its docstring for the algorithm):
    forward and reverse fronts step in lockstep inside a single
    ``lax.while_loop``, both sets of rings resident in VMEM scratch, and
    the meet test is fused into the same loop — so one grid program runs
    the whole breakpoint search for its block and exits as soon as *its*
    pairs have met (the jnp solver's early exit spans the whole batch).
    Index-gather only (the per-pair ring reads and complement-diagonal
    gathers are real gathers): interpret mode / CPU, the validation target.
    """
    x, o, e = model.x, model.o, model.e
    affine = model.kind == "affine"
    oend = (o if affine else 0) if end_state != "M" else 0
    maxop = max(x, o + e) if affine else max(x, e)
    Wd = max(model.window, 2 * maxop + 2)
    kc = k_pad // 2
    n_rings = 7 if affine else 3

    def kernel(p_ref, t_ref, pr_ref, tr_ref, pl_ref, tl_ref, st_ref,
               score_ref, steps_ref, state_ref, a_ref, b_ref, kk_ref,
               h_ref, safe_ref, *rings):
        if affine:
            fm, fmp, fi, fd, rm, ri, rd = rings
        else:
            fm, fmp, rm = rings
        BP = p_ref.shape[0]
        K = fm.shape[-1]

        pat, txt = p_ref[...], t_ref[...]
        patr, txtr = pr_ref[...], tr_ref[...]
        plen, tlen = pl_ref[...], tl_ref[...]          # [BP, 1]
        starget = st_ref[...]
        jidx = lax.broadcasted_iota(jnp.int32, (BP, K), 1)
        ks = jidx - kc

        def extend(M, p2, t2):
            def trip(st):
                M, _ = st
                v = M - ks
                can = ((M > _THRESH) & (M >= 0) & (M < tlen)
                       & (v >= 0) & (v < plen))
                tc = _gather_chars(t2, M, "index")
                pc = _gather_chars(p2, v, "index")
                adv = (can & (tc == pc)).astype(jnp.int32)
                return M + adv, jnp.any(adv == 1)

            st = trip((M, jnp.bool_(True)))
            M, _ = lax.while_loop(lambda st: st[1], trip, st)
            return M

        def store(ring, row, val):
            ring[pl.ds(row, 1)] = val[None]

        def load(ring, s, delta):
            row = lax.rem(jnp.maximum(s - delta, 0), Wd)
            val = ring[pl.ds(row, 1)][0]
            return jnp.where(s >= delta, val, NEG)

        neg_col = jnp.full((BP, 1), NEG, jnp.int32)
        sh_r = lambda w: jnp.concatenate([neg_col, w[:, :-1]], axis=1)
        sh_l = lambda w: jnp.concatenate([w[:, 1:], neg_col], axis=1)

        def step(mring, iring, dring, s, p2, t2):
            """One affine/linear score step from the given rings.

            Returns (M_new, I_new, D_new, M_pre); I/D are None for
            linear models (their sources fold into M directly)."""
            m_x = load(mring, s, x)
            if affine:
                m_owe = load(mring, s, o + e)
                i_src = jnp.maximum(sh_r(m_owe), sh_r(load(iring, s, e)))
                d_src = jnp.maximum(sh_l(m_owe), sh_l(load(dring, s, e)))
            else:
                m_e = m_x if x == e else load(mring, s, e)
                i_src, d_src = sh_r(m_e), sh_l(m_e)
            I_new = jnp.where((i_src > _THRESH) & (i_src + 1 <= tlen),
                              i_src + 1, NEG)
            D_new = jnp.where((d_src > _THRESH) & (d_src - ks <= plen),
                              d_src, NEG)
            X_new = jnp.where((m_x > _THRESH) & (m_x + 1 <= tlen)
                              & (m_x + 1 - ks <= plen), m_x + 1, NEG)
            M_pre = jnp.maximum(jnp.maximum(X_new, I_new), D_new)
            return extend(M_pre, p2, t2), I_new, D_new, M_pre

        def prune(*fronts):
            keep = keep_mask(heur, fronts[0], plen, tlen, ks)
            if keep is None:
                return fronts
            return tuple(jnp.where(keep, w, NEG) for w in fronts)

        # s = 0 seeds (fwd M + begin-state gap; rev M + end-state gap —
        # the reversed problem's *leading* gap, hence the oend shift)
        seed = jnp.where(ks == 0, 0, NEG)
        negK = jnp.full((BP, K), NEG, jnp.int32)
        store(fm, 0, extend(seed, pat, txt))
        store(fmp, 0, seed)
        store(rm, 0, extend(seed, patr, txtr))
        if affine:
            store(fi, 0, seed if begin_state == "I" else negK)
            store(fd, 0, seed if begin_state == "D" else negK)
            store(ri, 0, seed if end_state == "I" else negK)
            store(rd, 0, seed if end_state == "D" else negK)

        # complement-diagonal gather: rev K-index addressing the same cell
        jprime = (tlen - plen) + 2 * kc - jidx
        jpok = (jprime >= 0) & (jprime < K)
        jpc = jnp.clip(jprime, 0, K - 1)

        def comp(arr):
            return jnp.where(jpok, jnp.take_along_axis(arr, jpc, axis=1),
                             NEG)

        def at(ring, c):
            """Ring row at per-pair cost c [BP, 1] (NEG outside window)."""
            ok = (c >= 0) & (c <= s_cur[0]) & (c > s_cur[0] - Wd)
            rows = lax.rem(jnp.maximum(c[:, 0], 0), Wd)
            all_rows = ring[...]
            sel = jnp.take_along_axis(
                all_rows, jnp.broadcast_to(rows[None, :, None], (1, BP, K)),
                axis=0)[0]
            return jnp.where(ok, sel, NEG)

        m2 = tlen
        low = jnp.maximum(ks, 0)
        met0 = (plen == 0) & (tlen == 0)       # padded rows: free the exit

        # mutable closure cell for the current step (at() needs it)
        s_cur = [jnp.int32(0)]

        def body(carry):
            s, met, jst, ja, jb, jk, jh, jsf = carry
            s_cur[0] = s
            Mf, If, Df, Mfp = step(fm, fi if affine else None,
                                   fd if affine else None, s, pat, txt)
            Mr, Ir, Dr, _ = step(rm, ri if affine else None,
                                 rd if affine else None, s, patr, txtr)
            if affine:
                Mf, If, Df, Mfp = prune(Mf, If, Df, Mfp)
                Mr, Ir, Dr = prune(Mr, Ir, Dr)
            else:
                Mf, Mfp = prune(Mf, Mfp)
                (Mr,) = prune(Mr)
            row = lax.rem(s, Wd)
            store(fm, row, Mf)
            store(fmp, row, Mfp)
            store(rm, row, Mr)
            if affine:
                store(fi, row, If)
                store(fd, row, Df)
                store(ri, row, Ir)
                store(rd, row, Dr)

            def orient(a_m, a_g, b_m, b_g):
                """Candidate classes for prefix costs a_*, suffix costs
                b_* (see wfa_bidir_meet.orient)."""
                fa_m, fa_mp = at(fm, a_m), at(fmp, a_m)
                rb_m = comp(at(rm, b_m))
                vmm = (fa_m > _THRESH) & (rb_m > _THRESH)
                cov = vmm & (fa_m + rb_m >= m2)
                h_mm = jnp.clip(m2 - rb_m, low, jnp.maximum(fa_m, low))
                out = {"mm_safe": (cov & (fa_mp + rb_m <= m2), 0, a_m, b_m,
                                   h_mm, 1),
                       "mm_cov": (cov, 0, a_m, b_m, h_mm, 0)}
                if affine:
                    fa_i, rb_i = at(fi, a_g), comp(at(ri, b_g))
                    fa_d, rb_d = at(fd, a_g), comp(at(rd, b_g))
                    vii = (fa_i > _THRESH) & (rb_i > _THRESH)
                    vdd = (fa_d > _THRESH) & (rb_d > _THRESH)
                    out["ii0"] = (vii & (fa_i + rb_i == m2), 1, a_g, b_g,
                                  fa_i, 1)
                    out["dd0"] = (vdd & (fa_d + rb_d == m2), 2, a_g, b_g,
                                  fa_d, 1)
                    out["ii_cov"] = (vii & (fa_i + rb_i >= m2), 1, a_g,
                                     b_g, fa_i, 0)
                    out["dd_cov"] = (vdd & (fa_d + rb_d >= m2), 2, a_g,
                                     b_g, fa_d, 0)
                return out

            sb = jnp.full((BP, 1), 0, jnp.int32) + s
            st2 = starget - oend
            A = orient(sb, sb, st2 - s, st2 + (o if affine else 0) - s)
            Bo = orient(st2 - s, st2 + (o if affine else 0) - s, sb, sb)
            names = ["mm_safe"] + (["ii0", "dd0"] if affine else []) \
                + ["mm_cov"] + (["ii_cov", "dd_cov"] if affine else [])
            for name in names:
                for side in (A, Bo):
                    mask2d, stc, a_arr, b_arr, hplane, sf = side[name]
                    anyk = jnp.any(mask2d, axis=1, keepdims=True)
                    kidx = jnp.argmax(mask2d, axis=1).astype(
                        jnp.int32)[:, None]
                    hsel = jnp.take_along_axis(hplane, kidx, axis=1)
                    take = (~met) & anyk
                    met = met | take
                    jst = jnp.where(take, stc, jst)
                    ja = jnp.where(take, a_arr, ja)
                    jb = jnp.where(take, b_arr, jb)
                    jk = jnp.where(take, kidx - kc, jk)
                    jh = jnp.where(take, hsel, jh)
                    jsf = jnp.where(take, sf, jsf)
            return s + 1, met, jst, ja, jb, jk, jh, jsf

        def cond(carry):
            s, met = carry[0], carry[1]
            return (s <= s_max) & ~jnp.all(met)

        z = jnp.zeros((BP, 1), jnp.int32)
        s_end, met, jst, ja, jb, jk, jh, jsf = lax.while_loop(
            cond, body, (jnp.int32(1), met0, z - 1, z, z, z, z, z))
        hit = met & ~met0                      # padded rows report unmet
        score_ref[...] = jnp.where(hit, starget, -1)
        steps_ref[...] = jnp.broadcast_to(s_end, (BP, 1))
        state_ref[...] = jnp.where(hit, jst, -1)
        a_ref[...] = ja
        b_ref[...] = jb
        kk_ref[...] = jk
        h_ref[...] = jh
        safe_ref[...] = jsf

    return kernel, Wd, n_rings


@functools.partial(jax.jit, static_argnames=("pen", "s_max", "k_pad",
                                             "block_pairs", "interpret",
                                             "heur", "begin_state",
                                             "end_state"))
def wfa_meet_pallas(pattern, text, pat_rev, txt_rev, plen, tlen, starget, *,
                    pen, s_max: int, k_pad: int, block_pairs: int = 8,
                    interpret: bool = True, heur=None,
                    begin_state: str = "M", end_state: str = "M"):
    """Fused BiWFA meet search: same input contract as :func:`wfa_pallas`
    plus per-row-reversed sequences (computed by the ops wrapper — cheaper
    batched on the host side of the grid) and ``starget`` [B, 1].
    Returns 8 ``[B, 1]`` int32 arrays: score, steps, meet_state, meet_a,
    meet_b, meet_k, meet_h, meet_safe (``BidirMeetResult`` fields)."""
    B, Lp = pattern.shape
    BP = block_pairs
    assert B % BP == 0, (B, BP)
    model = scoring.as_model(pen)
    heur = scoring.as_heuristic(heur)
    kernel, Wd, n_rings = _make_meet_kernel(model, heur, s_max, k_pad,
                                            begin_state, end_state)
    grid = (B // BP,)
    spec2 = lambda L: pl.BlockSpec((BP, L), lambda i: (i, 0))
    Lt = text.shape[1]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec2(Lp), spec2(Lt), spec2(Lp), spec2(Lt),
                  spec2(1), spec2(1), spec2(1)],
        out_specs=[spec2(1)] * 8,
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 8,
        scratch_shapes=[pltpu.VMEM((Wd, BP, k_pad), jnp.int32)] * n_rings,
        interpret=interpret,
    )(pattern, text, pat_rev, txt_rev, plen, tlen, starget)
