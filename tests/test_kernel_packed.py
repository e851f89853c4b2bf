"""The kernel's packed extend fetch: 16 (2-bit), 4 (8-bit) or 1 (32-bit)
characters compared per trip, all with the same result.

Each case runs the interpreted kernel (the chip's fetch) at one fetch
width, penalty model and output mode, and checks that scores and every
packed trace plane equal the one-character fetch's bit for bit, and that
the scores equal the Gotoh oracle.  The rows put match runs against the word boundaries and
the sequence ends; lowercase, N and other byte codes run at the 8-bit
and 32-bit widths only, the widths the session picks for them.
"""
import numpy as np
import pytest

from repro.core.engine import AlignmentEngine, fetch_char_bits, pack_batch
from repro.core.gotoh import gotoh_score_vec
from repro.core.penalties import DEFAULT
from repro.core.scoring import AdaptiveBand, GapLinear
from repro.data.reads import ReadPairSpec, generate_pairs
from repro.kernels.wfa import ops as kops
from repro.kernels.wfa.kernel import _zero_fields

S_MAX, K_MAX = 160, 40
_SUB = {"A": "C", "C": "G", "G": "T", "T": "A"}


def _acgt_batch():
    rng = np.random.default_rng(41)
    base = lambda n: "".join(rng.choice(list("ACGT"), size=n))
    pairs = []
    # identical: runs end exactly at a word boundary and at both ends
    for n in (16, 32, 48):
        s = base(n)
        pairs.append((s, s))
    # one mismatch just before, at and just after a word boundary
    for i in (15, 16, 17, 32):
        p = base(50)
        pairs.append((p, p[:i] + _SUB[p[i]] + p[i + 1:]))
    # one sequence a prefix of the other: the run reaches the shorter end
    p = base(40)
    pairs += [(p, p[:33]), (p[:21], p)]
    # ... and the longer one goes on with A, whose 2-bit field is the
    # padding's: only the length clamp stops the run
    p = base(21)
    pairs += [(p, p + "AAAAT" + base(6)), (p + "AAAAC" + base(6), p)]
    # lengths that are not multiples of 16, with an insertion or deletion
    p = base(37)
    pairs.append((p, p[:10] + "G" + p[10:]))
    p = base(53)
    pairs.append((p, p[:20] + p[21:]))
    # empty on either side, and both
    pairs += [("", base(5)), (base(7), ""), ("", "")]
    P, plen = pack_batch([a for a, _ in pairs])
    T, tlen = pack_batch([b for _, b in pairs])
    G = generate_pairs(ReadPairSpec(n_pairs=8, read_len=90, edit_frac=0.06,
                                    seed=42))
    # 24 rows: the wrapper pads none; the band run and the mixed batch
    # (9 rows) pad their last block with zero-length rows
    return _stack((P, plen, T, tlen), G)


def _mixed_batch():
    """Lowercase, N, and byte codes above ASCII: the session routes these
    away from the 2-bit fetch (see ``fetch_char_bits``)."""
    rng = np.random.default_rng(43)
    alpha = np.frombuffer(b"ACGTacgtN", np.uint8)
    rows_p, rows_t = [], []
    for n in (18, 33, 47, 64):
        p = rng.choice(alpha, size=n).astype(np.int32)
        t = p.copy()
        t[n // 2] = 255 if t[n // 2] != 255 else 65
        rows_p.append(p)
        rows_t.append(t)
    # case differs: 'a' against 'A' is a mismatch
    p = rng.choice(alpha[:4], size=30).astype(np.int32)
    rows_p.append(p)
    rows_t.append(np.where(np.arange(30) == 16, p + 32, p))
    # raw codes 0..3, the text going on with the padding's code 0
    p = np.concatenate([np.tile(np.arange(4, dtype=np.int32), 5), [1, 2]])
    rows_p += [p, np.concatenate([p, [0, 0, 0, 0, 3, 1]])]
    rows_t += [np.concatenate([p, [0, 0, 0, 0, 3, 1]]), p]
    # a run of N, and 200s, across a word boundary
    p = np.full(40, ord("N"), np.int32)
    p[10:20] = 200
    rows_p.append(p)
    rows_t.append(p[:36].copy())
    P, plen = pack_batch(rows_p)
    T, tlen = pack_batch(rows_t)
    return P, plen, T, tlen


def _stack(a, b):
    (P1, pl1, T1, tl1), (P2, pl2, T2, tl2) = a, b
    w = max(P1.shape[1], P2.shape[1], T1.shape[1], T2.shape[1])
    fit = lambda X: np.pad(np.asarray(X, np.int32),
                           ((0, 0), (0, w - X.shape[1])))
    return (np.concatenate([fit(P1), fit(P2)]), np.concatenate([pl1, pl2]),
            np.concatenate([fit(T1), fit(T2)]), np.concatenate([tl1, tl2]))


def _run(batch, char_bits, trace, pen=DEFAULT, k_max=K_MAX, **kw):
    P, plen, T, tlen = batch
    fn = kops.wfa_align_trace if trace else kops.wfa_align
    out = fn(P, T, plen, tlen, pen=pen, s_max=S_MAX, k_max=k_max,
             char_bits=char_bits, interpret=True, **kw)
    out = out if trace else (out,)
    return [None if a is None else np.asarray(a) for a in out]


def _gotoh(batch, pen=DEFAULT):
    P, plen, T, tlen = batch
    return np.array([gotoh_score_vec(P[i, :plen[i]], T[i, :tlen[i]],
                                     pen) for i in range(len(plen))])


@pytest.mark.parametrize("output", ["score", "trace"])
@pytest.mark.parametrize("pen", [DEFAULT, GapLinear(4, 2)],
                         ids=["affine", "linear"])
@pytest.mark.parametrize("char_bits", [2, 8, 32])
def test_packed_fetch_is_exact(char_bits, pen, output):
    trace = output == "trace"
    batches = [_acgt_batch()] + ([_mixed_batch()] if char_bits != 2 else [])
    for batch in batches:
        got = _run(batch, char_bits, trace, pen)
        want = _run(batch, 32, trace, pen)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(got[0], _gotoh(batch, pen))
    # the compacting band (128 of 256 lanes): pruned, so no oracle, but
    # the same at any width
    heur = AdaptiveBand(min_wf_len=4, max_distance_diff=10)
    kw = dict(k_max=2 * K_MAX, heur=heur, band_cap=heur.band_cap(161))
    assert kops._band_lanes(kw["band_cap"], 256) == 128
    got = _run(batches[0], char_bits, trace, pen, **kw)
    want = _run(batches[0], 32, trace, pen, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_every_batch_here_takes_its_width():
    assert fetch_char_bits(*_acgt_batch()) == 2
    assert fetch_char_bits(*_mixed_batch()) == 8


@pytest.mark.parametrize("char_bits", [2, 8, 32])
def test_zero_fields_counts_like_a_loop(char_bits):
    """The field count's binary search against a field-by-field loop,
    sign bit included."""
    rng = np.random.default_rng(char_bits)
    words = rng.integers(-2**31, 2**31, size=(8, 128), dtype=np.int64)
    # clear low fields so every count from 0 to C occurs
    C = 32 // char_bits
    keep = rng.integers(0, C + 1, size=words.shape)
    mask = np.where(keep >= C, 0, (-1 << (keep * char_bits)) & 0xFFFFFFFF)
    words = ((words & 0xFFFFFFFF) & mask).astype(np.uint32).view(np.int32)
    got = np.asarray(_zero_fields(words, char_bits))
    u = words.view(np.uint32).astype(np.int64)
    want = np.zeros_like(u)
    for f in range(C):
        live = want == f
        zero = ((u >> (f * char_bits)) & ((1 << char_bits) - 1)) == 0
        want = np.where(live & zero, f + 1, want)
    np.testing.assert_array_equal(got, want)
    assert set(np.unique(got)) == set(range(C + 1))


@pytest.mark.parametrize("char_bits", [2, 8, 32])
def test_pack_words_layout(char_bits):
    """Word l holds characters l .. l+C-1, character l lowest; past the
    row's end the fields are 0."""
    P, _ = pack_batch(["ACGTTGCAACGTAGCTAGGT", "GATTACA"])
    W = np.asarray(kops.pack_words(np.asarray(P), char_bits))
    C = 32 // char_bits
    field = (P >> 1) & 3 if char_bits == 2 else P
    for b in range(P.shape[0]):
        for l in range(P.shape[1]):
            want = 0
            for c in range(C):
                if l + c < P.shape[1]:
                    want |= int(field[b, l + c]) << (char_bits * c)
            assert W[b, l] == np.uint32(want).view(np.int32), (b, l)


def test_pack_words_refuses_other_widths():
    with pytest.raises(ValueError, match="char_bits"):
        kops.pack_words(np.zeros((1, 4), np.int32), 4)


def test_fetch_width_is_not_a_backend_option():
    with pytest.raises(ValueError, match="char_bits"):
        AlignmentEngine(DEFAULT, backend="kernel",
                        backend_opts={"char_bits": 2})
