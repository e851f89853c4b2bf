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

Extension fetches each lane's word with a binary select tree (a TPU VPU
has no per-lane gather).  Once per grid block each sequence is laid out
in VMEM as ``[L, BP, 128]``: slab ``l`` holds word ``l`` of every pair on
all 128 lanes of its row, the layout of a ``[BP, 128]`` front.  A fetch
then picks, lane by lane, between slabs by the bits of the lane's index:
127 full-vreg selects per 128 words and 128 diagonal lanes, no
cross-lane reduce, no gather, and the words land where the front's XOR
wants them.  A bucket too wide to keep its slabs in VMEM builds them per
fetch instead.  Interpret mode runs the same fetch, so the CPU tests
exercise the program the chip runs.

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
rows realign by the offset delta (a lane rotation and a mask, no gather),
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

There is no kernel for the BiWFA meet: the engine's meet waves run
``core.wavefront.wfa_bidir_meet`` on every backend and platform.
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

# Words per group of the extend fetch's select tree (a power of two).
FETCH_GROUP = 32


def vmem_estimate(block_pairs: int, k_pad: int, n_bt: int, n_words: int,
                  resident_words: int = 0) -> int:
    """Scoped VMEM bytes a compiled block needs: an upper envelope of the
    least limit under which v5e compiles take each shape.

    A fixed 1.25 MiB (the fetch's staging and the rest), 192 B per (pair,
    diagonal lane) for the rings and the [BP, K] fronts, the
    double-buffered packed-trace output blocks (``n_bt`` planes of
    ``n_words`` int32 words), and 576 B per (pair, word) that the fetch
    keeps resident (``resident_words``: both sequences' bucket widths,
    summed), for their [L, BP, 128] slabs.  At 8 pairs per block the
    slabs stay resident up to 1,024-word buckets at k_pad 384, trace
    depth included; wider buckets build them per fetch.
    """
    return (5 << 18) + block_pairs * (k_pad * (192 + 8 * n_bt * n_words)
                                      + 576 * resident_words)


def seq_scratch(block_pairs: int, width: int):
    """VMEM scratch for one sequence's words as the fetch reads them:
    ``[width, block_pairs, 128]`` int32 (see :func:`_slabs`)."""
    return pltpu.VMEM((width, block_pairs, 128), jnp.int32)


def _slabs(seq):
    """seq [BP, n] -> [n, BP, 128]: slab l holds word l of pair b on every
    lane of row b, laid out like a [BP, 128] front."""
    BP, n = seq.shape
    return jnp.broadcast_to(seq.T[:, :, None], (n, BP, 128))


def _fetch_words(seq_ref, scratch_ref):
    """The fetch's source of slabs for one sequence: ``words(l0, n)`` ->
    slabs l0 .. l0 + n - 1 (l0 a multiple of 128, maybe traced).

    With a scratch (``seq_scratch``) the slabs are built once per grid
    block and every fetch reads them; without one (a bucket too wide to
    keep them in VMEM, see :func:`wfa_pallas`) each fetch builds them
    from the block's words, 128 words at a time."""
    L = seq_ref.shape[1]
    if scratch_ref is None:
        return lambda l0, n: _slabs(seq_ref[:, pl.ds(l0, n)])
    for l0 in range(0, L, 128):
        n = min(128, L - l0)
        scratch_ref[l0:l0 + n] = _slabs(seq_ref[:, l0:l0 + n])
    return lambda l0, n: scratch_ref.at[pl.ds(l0, n)]


def _select_tree(x, idx, bit):
    """x [n, BP, 128] -> [BP, 128]: on each lane, x[i] where i is that
    lane's idx read from bit ``bit`` up (the lower bits chose x)."""
    while x.shape[0] > 1:
        n = x.shape[0]
        pairs = x[:n - n % 2].reshape((n // 2, 2) + x.shape[1:])
        y = jnp.where((idx & (1 << bit)) != 0, pairs[:, 1], pairs[:, 0])
        # an unpaired last word stands for itself: a lane that reaches it
        # has the bit clear, since its idx is below L
        x = y if n % 2 == 0 else jnp.concatenate([y, x[n - 1:]])
        bit += 1
    return x[0]


def _gather_chars(words, L: int, idx):
    """words (:func:`_fetch_words`) of a bucket L words wide (L <= 128 or
    a multiple of 128), idx [BP, K] -> seq[b, clip(idx[b, k], 0, L - 1)]
    as [BP, K].

    Out-of-range lanes read junk that the caller's validity mask
    discards.  Per 128 diagonal lanes, a binary select tree over the
    slabs picks each lane's word by the bits of its index, with no
    cross-lane reduce and no gather (TPUs lack per-lane gather); the
    result lands in the front's own layout.  The tree runs
    ``FETCH_GROUP`` slabs at a time, so a group's words and partial
    selects stay in vector registers, then over the groups' picks, 127
    selects per 128 words.  A wider bucket loops over its 128-word
    chunks, keeping the pick of the chunk each lane's index falls in."""
    assert L <= 128 or L % 128 == 0, L
    idx_c = jnp.clip(idx, 0, L - 1)
    top = (FETCH_GROUP - 1).bit_length()

    def chunk_pick(part, l0, n):
        chunk = words(l0, n)
        picks = [_select_tree(chunk[g:g + FETCH_GROUP], part, 0)
                 for g in range(0, n, FETCH_GROUP)]
        return _select_tree(jnp.stack(picks), part, top)

    parts = []
    for k0 in range(0, idx.shape[1], 128):
        part = idx_c[:, k0:k0 + 128]
        if L <= 128:
            parts.append(chunk_pick(part, 0, L))
            continue
        hi = part >> 7

        def step(c, acc, part=part, hi=hi):
            pick = chunk_pick(part, pl.multiple_of(c * 128, 128), 128)
            return jnp.where(hi == c, pick, acc)
        parts.append(lax.fori_loop(0, L // 128, step, jnp.zeros_like(part)))
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
                 char_bits: int, band: bool, resident: bool):
    x, o, e = model.x, model.o, model.e
    W = model.window
    affine = model.kind == "affine"
    n_bt = (3 if affine else 1) if trace else 0
    C = 32 // char_bits                     # characters per packed word
    kc_full = k_pad // 2                     # absolute diagonal center

    def kernel(p_ref, t_ref, pl_ref, tl_ref, out_ref, steps_ref, trips_ref,
               *refs):
        bt_refs = refs[:n_bt]
        # [L, BP, 128] word slabs, when they stay resident
        p_w, t_w = refs[n_bt:n_bt + 2] if resident else (None, None)
        rings = refs[n_bt + 2 * resident:len(refs) - band]
        off_ref = refs[-1] if band else None  # [W, 1] SMEM row offsets
        if affine:
            m_ring, i_ring, d_ring = rings
        else:
            (m_ring,) = rings
        BP, Lp = p_ref.shape
        _, Lt = t_ref.shape
        Kc = m_ring.shape[-1]                # compact (== k_pad unless band)

        pat = _fetch_words(p_ref, p_w)
        txt = _fetch_words(t_ref, t_w)
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
                # both words arrive in the front's layout; -1 matches no
                # field, so lanes that may not extend count none
                x = jnp.where(can, _gather_chars(txt, Lt, M)
                              ^ _gather_chars(pat, Lp, v), -1)
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
                                             "trace", "heur",
                                             "char_bits", "band_cap"))
def wfa_pallas(pattern, text, plen, tlen, *, pen, s_max: int,
               k_pad: int, block_pairs: int = 8, interpret: bool = True,
               trace: bool = False, heur=None, char_bits: int = 32,
               band_cap=None):
    """pattern/text [B, L*] int32 packed words of ``char_bits``-bit
    characters (B % block_pairs == 0, L* % 128 == 0; with ``char_bits``
    32 the words are the codes themselves), plen/tlen [B, 1] int32 in
    characters, k_pad % 128 == 0 is the padded diagonal count.
    -> (score [B, 1] int32, steps [B, 1] int32, trips [B, 1] int32), the
    last two the block's score steps and extend trips on each of its rows;
    with ``trace`` additionally
    the [n_words, B, k_pad] int32 packed provenance arrays (three for
    affine models, one for linear).

    ``char_bits`` (2, 8 or 32) and ``band_cap`` (compact ring width,
    lane-aligned by the ops wrapper; None = full width) are static — see
    the module docstring.
    """
    B, Lp = pattern.shape
    Lt = text.shape[1]
    BP = block_pairs
    assert B % BP == 0, (B, BP)
    model = scoring.as_model(pen)
    heur = scoring.as_heuristic(heur)
    band = band_cap is not None and band_cap < k_pad
    Kc = band_cap if band else k_pad
    n_bt = (3 if model.kind == "affine" else 1) if trace else 0
    NW = n_trace_words(s_max)
    # keep the fetch's word slabs resident where they fit, else build them
    # per fetch (the same words, the same picks)
    resident = (vmem_estimate(BP, k_pad, n_bt, NW, Lp + Lt)
                <= SCOPED_VMEM_BYTES)
    if not interpret:
        need = vmem_estimate(BP, k_pad, n_bt, NW, (Lp + Lt) * resident)
        if need > SCOPED_VMEM_BYTES:
            raise ValueError(
                f"WFA kernel block too wide for the TPU's scoped VMEM: "
                f"block_pairs={BP}, k_pad={k_pad}, s_max={s_max}, "
                f"trace={trace} needs ~{need / 2**20:.1f} MiB of "
                f"{SCOPED_VMEM_BYTES >> 20} MiB (bucket [{B}, {Lp}]); "
                f"align this bucket on the 'ring' backend")
    kernel, W, affine = _make_kernel(model, heur, s_max, k_pad, trace,
                                     char_bits, band, resident)
    grid = (B // BP,)
    n_rings = 3 if affine else 1

    spec2 = lambda L: pl.BlockSpec((BP, L), lambda i: (i, 0))
    out_specs = [spec2(1)] * 3
    out_shape = [jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 3
    if trace:
        bt_spec = pl.BlockSpec((NW, BP, k_pad), lambda i: (0, i, 0))
        out_specs += [bt_spec] * n_bt
        out_shape += [jax.ShapeDtypeStruct((NW, B, k_pad), jnp.int32)] * n_bt
    scratch = [seq_scratch(BP, Lp), seq_scratch(BP, Lt)] if resident else []
    scratch += [pltpu.VMEM((W, BP, Kc), jnp.int32)] * n_rings
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

