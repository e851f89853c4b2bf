"""jit'd wrapper around the Pallas WFA kernel: padding, blocking, unpadding.

Hardware-alignment contract (DESIGN.md §2): sequence buffers pad to lane
multiples (128), the diagonal axis pads to a lane multiple, the pair axis
pads to the block size — the TPU analogue of UPMEM's 8-byte DMA alignment,
absorbed here by the wrapper exactly like the paper's custom allocator.

Tuning knobs (all optional, threaded from
``AlignmentEngine(backend_opts=...)``):

* ``block_pairs`` — pairs per grid program.  ``None`` picks the platform
  auto-default (8: one int32 sublane tile on TPU, and the measured best in
  interpret mode, where a small block keeps the per-block early exit
  effective).
* ``band_cap`` — compacting-band width; lane-aligned here (rounded up to
  128) before reaching the kernel, so the compact rings stay legal TPU
  tiles.  None = full width.

``char_bits`` is not a knob: the session derives it from the codes it
is given (``core.engine.fetch_char_bits``).  The wrapper packs both
sequence arrays into words of ``32 // char_bits`` characters inside the
same jit (:func:`pack_words`), so the extend loop compares that many
characters per trip.  Nor is the fetch itself: the kernel has one, the
same interpreted and compiled (``kernel.py``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scoring
from repro.kernels.wfa.kernel import wfa_pallas

LANE = 128
DEFAULT_BLOCK_PAIRS = 8


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _pad_axis(x, axis: int, to: int, value=0):
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def default_interpret() -> bool:
    """Interpret mode off the TPU (CPU validation), compiled on it."""
    return jax.default_backend() != "tpu"


def resolve_block_pairs(block_pairs: Optional[int]) -> int:
    """Auto-default for pairs-per-grid-program (one int32 sublane tile)."""
    if block_pairs is None:
        return DEFAULT_BLOCK_PAIRS
    bp = int(block_pairs)
    if bp < 1:
        raise ValueError(f"block_pairs must be >= 1, got {block_pairs}")
    return bp


def _band_lanes(band_cap, k_pad: int) -> Optional[int]:
    """Lane-aligned compact ring width, or None for full width."""
    if band_cap is None:
        return None
    kc = _round_up(max(int(band_cap), 1), LANE)
    return kc if kc < k_pad else None


def _prep(pattern, text, plen, tlen, block_pairs):
    pattern = jnp.asarray(pattern, jnp.int32)
    text = jnp.asarray(text, jnp.int32)
    plen = jnp.asarray(plen, jnp.int32).reshape(-1)
    tlen = jnp.asarray(tlen, jnp.int32).reshape(-1)
    B, Lp = pattern.shape
    Lt = text.shape[1]
    Bp = _round_up(max(B, 1), block_pairs)
    pattern = _pad_axis(_pad_axis(pattern, 1, _round_up(max(Lp, 1), LANE)),
                        0, Bp)
    text = _pad_axis(_pad_axis(text, 1, _round_up(max(Lt, 1), LANE)), 0, Bp)
    # padded pairs have plen = tlen = 0 -> score 0 at s = 0, no extra trips
    plen2 = _pad_axis(plen[:, None], 0, Bp)
    tlen2 = _pad_axis(tlen[:, None], 0, Bp)
    return pattern, text, plen2, tlen2, B


CHAR_BITS = (2, 8, 32)


def pack_words(seq, char_bits: int):
    """[B, L] int32 codes -> [B, L] int32 words of ``C = 32 // char_bits``
    characters: word ``l`` holds characters ``l .. l + C - 1``, character
    ``l`` in the lowest field, positions past ``L`` as 0.

    ``char_bits`` 2 takes the field ``(c >> 1) & 3``, one-to-one on the
    ASCII codes A, C, G, T (0, 1, 3, 2); 8 takes ``c`` itself (0..255);
    32 returns the codes unchanged (one character per word).
    """
    if char_bits not in CHAR_BITS:
        raise ValueError(f"char_bits must be one of {CHAR_BITS}, "
                         f"got {char_bits!r}")
    if char_bits == 32:
        return seq
    C = 32 // char_bits
    field = (seq >> 1) & 3 if char_bits == 2 else seq
    L = seq.shape[1]
    ext = jnp.pad(field, ((0, 0), (0, C - 1)))
    word = field
    for c in range(1, C):
        word = word | jnp.left_shift(ext[:, c:c + L], char_bits * c)
    return word


def _block_sums(steps, trips, bp: int):
    """The kernel's per-block loop counters ([B, 1], one value repeated
    over each block's rows) summed over grid blocks -> two [] int32."""
    return steps[::bp, 0].sum(), trips[::bp, 0].sum()


def wfa_align(pattern, text, plen, tlen, *, pen, s_max: int,
              k_max: int, block_pairs: Optional[int] = None,
              interpret: Optional[bool] = None, heur=None,
              char_bits: int = 32, band_cap: Optional[int] = None,
              counts: bool = False):
    """Batched WFA scores via the Pallas kernel.

    pattern/text: [B, L*] int; plen/tlen: [B] int.  Returns [B] int32 costs
    (-1 where the optimal cost exceeds ``s_max``); with ``counts``,
    ``(score, steps, trips)``: the score steps and extend trips of every
    grid block, summed.  ``char_bits`` (2, 8 or 32) packs the codes
    into words of ``32 // char_bits`` characters (:func:`pack_words`);
    the caller vouches that they fit.  ``pen`` may be any
    ``PenaltyModel`` (or a legacy ``Penalties`` triple) and ``heur`` an
    optional ``WavefrontHeuristic``; both specialize the kernel statically.
    ``interpret`` defaults to True off-TPU (CPU validation) and False on
    TPU; the remaining knobs are documented in the module docstring.
    """
    if interpret is None:
        interpret = default_interpret()
    bp = resolve_block_pairs(block_pairs)
    pattern, text, plen2, tlen2, B = _prep(pattern, text, plen, tlen, bp)
    k_pad = _round_up(2 * k_max + 1, LANE)

    score, steps, trips = wfa_pallas(
        pack_words(pattern, char_bits), pack_words(text, char_bits), plen2,
        tlen2, pen=pen, s_max=s_max, k_pad=k_pad, block_pairs=bp,
        interpret=interpret, heur=scoring.as_heuristic(heur),
        char_bits=char_bits, band_cap=_band_lanes(band_cap, k_pad))
    if counts:
        return (score[:B, 0],) + _block_sums(steps, trips, bp)
    return score[:B, 0]


def wfa_align_trace(pattern, text, plen, tlen, *, pen, s_max: int,
                    k_max: int, block_pairs: Optional[int] = None,
                    interpret: Optional[bool] = None, heur=None,
                    char_bits: int = 32, band_cap: Optional[int] = None,
                    counts: bool = False):
    """Batched WFA scores *plus* packed backtrace via the Pallas kernel.

    Same padding contract as :func:`wfa_align`; returns
    ``(score [B], m_bt, i_bt, d_bt)`` where the bt arrays are
    ``[n_words, B, k_pad]`` int32 packed 2-bit provenance words
    (``core.cigar.traceback_packed_batch`` decodes them; the diagonal
    center is ``k_pad // 2`` — under a compacting band the codes are
    scattered back to absolute k in-kernel, so the decoder is unchanged).
    Linear penalty models record a single M plane: ``i_bt = d_bt = None``.
    ``counts`` appends the summed score steps and extend trips, as in
    :func:`wfa_align`.
    """
    if interpret is None:
        interpret = default_interpret()
    bp = resolve_block_pairs(block_pairs)
    pattern, text, plen2, tlen2, B = _prep(pattern, text, plen, tlen, bp)
    k_pad = _round_up(2 * k_max + 1, LANE)

    out = wfa_pallas(
        pack_words(pattern, char_bits), pack_words(text, char_bits), plen2,
        tlen2, pen=pen, s_max=s_max, k_pad=k_pad, block_pairs=bp,
        interpret=interpret, trace=True, heur=scoring.as_heuristic(heur),
        char_bits=char_bits, band_cap=_band_lanes(band_cap, k_pad))
    score, steps, trips, m_bt, *gaps = out
    i_bt, d_bt = (g[:, :B, :] for g in gaps) if gaps else (None, None)
    res = (score[:B, 0], m_bt[:, :B, :], i_bt, d_bt)
    if counts:
        return res + _block_sums(steps, trips, bp)
    return res


def wfa_align_np(pattern, text, plen, tlen, **kw):
    return np.asarray(wfa_align(pattern, text, plen, tlen, **kw))

