"""The Pallas kernel's loop counters: per grid block, the score steps it
ran and the extend trips it took, against a plain Python loop over the
same gap-affine recurrence (dicts of diagonals, one lane at a time), and
carried out through the kernel backend into ``EngineResult``.

The kernel runs interpret=True on CPU; the counts are the algorithm's,
so they are the same compiled on the chip.  A trip compares up to
``chars_per_trip`` characters (16, 4 or 1: the fetch width the session
derives from the codes), and the plain loop states the same rule."""
import numpy as np
import pytest
from conftest import gotoh_oracle, random_pairs

from repro.core import session as session_mod
from repro.core.backends import get_backend
from repro.core.engine import AlignmentEngine, fetch_char_bits, pack_batch
from repro.core.gotoh import gotoh_score_vec
from repro.core.penalties import DEFAULT
from repro.core.scoring import as_model
from repro.kernels.wfa import ops as kops
from repro.obs.metrics import REGISTRY
from repro.serve import ServeLoop

BP = 8          # pairs per grid block
S_MAX = 40
K_MAX = 16      # -> k_pad 128, lanes k = -64 .. 63


def _k_pad(k_max: int) -> int:
    return -(-(2 * k_max + 1) // 128) * 128


def _extend(fronts, pats, txts, chars_per_trip=1):
    """Lock-step greedy extension over the block -> (fronts, trips).

    Each trip advances every lane by its matched prefix, up to
    ``chars_per_trip`` characters; the block stops after the first trip
    in which no lane advanced that far."""
    trips = 0
    while True:
        trips += 1
        full = False
        for b, front in enumerate(fronts):
            p, t = pats[b], txts[b]
            for k, h in front.items():
                n = 0
                while (n < chars_per_trip and 0 <= h + n < len(t)
                       and 0 <= h + n - k < len(p)
                       and t[h + n] == p[h + n - k]):
                    n += 1
                front[k] = h + n
                full = full or n == chars_per_trip
        if not full:
            return fronts, trips


def _block_counters(pats, txts, pen, s_max, k_pad, chars_per_trip=1):
    """(steps, trips) of one grid block: the score rows it computed
    (0 .. last) and its extend trips, by plain loops."""
    x, o, e = pen.x, pen.o, pen.e
    lo, hi = -(k_pad // 2), k_pad // 2 - 1          # the kernel's lanes
    pats = list(pats) + [""] * (BP - len(pats))     # the wrapper's padding
    txts = list(txts) + [""] * (BP - len(txts))
    n = len(pats)

    def reached(front, b):
        k = len(txts[b]) - len(pats[b])
        return front.get(k, -1) >= len(txts[b])

    M, I, D = [{} for _ in range(n)], [{} for _ in range(n)], \
        [{} for _ in range(n)]
    fronts, trips = _extend([{0: 0} for _ in range(n)], pats, txts,
                            chars_per_trip)
    for b in range(n):
        M[b][0], I[b][0], D[b][0] = fronts[b], {}, {}
    done = [reached(fronts[b], b) for b in range(n)]
    s = 1
    while s <= s_max and not all(done):
        pre = []
        for b in range(n):
            plen, tlen = len(pats[b]), len(txts[b])

            def row(hist, d):
                return hist[b].get(s - d, {}) if s >= d else {}
            m_x, m_owe, i_e, d_e = (row(M, x), row(M, o + e), row(I, e),
                                    row(D, e))
            mp, ip, dp = {}, {}, {}
            for k in range(lo, hi + 1):
                srcs = [h for h in (m_owe.get(k - 1), i_e.get(k - 1))
                        if h is not None] if k > lo else []
                if srcs and max(srcs) + 1 <= tlen:
                    ip[k] = max(srcs) + 1
                srcs = [h for h in (m_owe.get(k + 1), d_e.get(k + 1))
                        if h is not None] if k < hi else []
                if srcs and max(srcs) - k <= plen:
                    dp[k] = max(srcs)
                cand = [h for h in (ip.get(k), dp.get(k)) if h is not None]
                h = m_x.get(k)
                if h is not None and h + 1 <= tlen and h + 1 - k <= plen:
                    cand.append(h + 1)
                if cand:
                    mp[k] = max(cand)
            I[b][s], D[b][s] = ip, dp
            pre.append(mp)
        fronts, n_trips = _extend(pre, pats, txts, chars_per_trip)
        trips += n_trips
        for b in range(n):
            M[b][s] = fronts[b]
            done[b] = done[b] or reached(fronts[b], b)
        s += 1
    return s, trips


def _mutate(rng, seq, kind):
    s = list(seq)
    i = int(rng.integers(5, len(s) - 5))
    if kind == "mismatch":
        s[i] = {"A": "C", "C": "G", "G": "T", "T": "A"}[s[i]]
    elif kind == "insertion":
        s.insert(i, "ACGT"[int(rng.integers(4))])
    elif kind == "deletion":
        del s[i]
    return "".join(s)


def _pairs(kinds, seed=0, length=44):
    rng = np.random.default_rng(seed)
    pats, txts = [], []
    for kind in kinds:
        p = "".join(rng.choice(list("ACGT"), size=length))
        pats.append(p)
        txts.append(p if kind == "same" else _mutate(rng, p, kind))
    return pats, txts


def _reference(pats, txts, pen=DEFAULT, s_max=S_MAX, k_max=K_MAX,
               chars_per_trip=1):
    blocks = [_block_counters(pats[i:i + BP], txts[i:i + BP], pen, s_max,
                              _k_pad(k_max), chars_per_trip)
              for i in range(0, len(pats), BP)]
    return blocks, sum(s for s, _ in blocks), sum(t for _, t in blocks)


CASES = {
    "zero_edits": ["same"] * 8,
    "one_mismatch": ["mismatch"] * 8,
    "indel": ["insertion", "deletion"] * 4,
    "mixed_two_blocks": ["same", "mismatch", "insertion", "deletion"] * 3,
}


@pytest.mark.parametrize("chars_per_trip", [1, 4, 16])
@pytest.mark.parametrize("trace", [False, True], ids=["score", "trace"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_block_counters_match_a_plain_loop(case, trace, chars_per_trip):
    pats, txts = _pairs(CASES[case])
    P, plen = pack_batch(pats)
    T, tlen = pack_batch(txts)
    bp = 8
    bits = 32 // chars_per_trip
    P2, T2, plen2, tlen2, _ = kops._prep(P, T, plen, tlen, bp)
    out = kops.wfa_pallas(kops.pack_words(P2, bits),
                          kops.pack_words(T2, bits), plen2, tlen2,
                          pen=DEFAULT, s_max=S_MAX, k_pad=_k_pad(K_MAX),
                          block_pairs=bp, interpret=True, trace=trace,
                          char_bits=bits)
    steps, trips = np.asarray(out[1])[:, 0], np.asarray(out[2])[:, 0]
    blocks, _, _ = _reference(pats, txts, chars_per_trip=chars_per_trip)
    assert len(steps) == len(blocks) * bp
    for j, (want_s, want_t) in enumerate(blocks):
        # one value per block, repeated over its rows
        assert set(steps[j * bp:(j + 1) * bp]) == {want_s}, j
        assert set(trips[j * bp:(j + 1) * bp]) == {want_t}, j


@pytest.mark.parametrize("chars_per_trip", [1, 4, 16])
def test_zero_edit_block_takes_one_step_and_one_run_of_trips(
        chars_per_trip):
    """Identical pairs finish at s = 0: one score row, and the extension
    walks the whole read once (one trip per whole word, plus the trip
    that comes up short)."""
    pats, txts = _pairs(CASES["zero_edits"])
    blocks, _, _ = _reference(pats, txts, chars_per_trip=chars_per_trip)
    assert blocks == [(1, len(pats[0]) // chars_per_trip + 1)]


def _fetch_pairs(chars_per_trip):
    c = REGISTRY.get(session_mod.FETCH_PAIRS[chars_per_trip])
    return 0 if c is None else c.value


@pytest.mark.parametrize("output", ["score", "cigar"])
def test_kernel_backend_reports_its_real_counts(output):
    """``n_steps`` is the kernel's summed block steps (not ``s_max``), and
    ``n_ext_trips`` its summed trips, through the backend and the
    engine, at the fetch width the engine chose for these codes.  The
    engine runs 4/6/2 divided by its gcd, as 2/3/1 under ``S_MAX // 2``,
    so its counts are the plain loop's under that model."""
    pats, txts = _pairs(CASES["mixed_two_blocks"] + ["mismatch"] * 4,
                        seed=3)
    P, plen = pack_batch(pats)
    T, tlen = pack_batch(txts)
    chars = 32 // fetch_char_bits(P, plen, T, tlen)
    assert chars == 16                               # ACGT
    _, want_steps, want_trips = _reference(pats, txts, chars_per_trip=chars)
    spec = get_backend("kernel")
    fn = spec.variant(output, "affine")
    res = fn(P, T, plen, tlen, pen=DEFAULT, s_max=S_MAX, k_max=K_MAX,
             char_bits=32 // chars)
    assert int(res.n_steps) == want_steps
    assert int(res.n_ext_trips) == want_trips

    run_pen, g = as_model(DEFAULT).reduced()
    assert g == 2
    _, run_steps, run_trips = _reference(pats, txts, pen=run_pen,
                                         s_max=S_MAX // g,
                                         chars_per_trip=chars)
    eng = AlignmentEngine(DEFAULT, backend="kernel", s_max=S_MAX,
                          k_max=K_MAX)
    before = _fetch_pairs(chars)
    er = eng.align(pats, txts, output=output)
    assert (er.scores >= 0).all()
    assert er.n_steps == run_steps != er.s_max
    assert er.stats.n_ext_trips == run_trips
    assert _fetch_pairs(chars) - before == len(pats)


def _codes(seqs):
    return [np.frombuffer(s.encode(), np.uint8).astype(np.int32)
            if isinstance(s, str) else np.asarray(s, np.int32)
            for s in seqs]


_CLASS_CASES = {
    "acgt": (["ACGTACGGTTACGATCGATCAGT", "GGATTACA"], 16),
    "lowercase": (["acgtacggttacgatcgatcagt", "GGATTACA"], 4),
    "n": (["ACGTACGGNNACGATCGATCAGT", "GGATTACA"], 4),
    "code_255": (["ACGTACGG", [65, 67, 255, 84, 71, 65]], 4),
    "code_300": (["ACGTACGG", [65, 67, 300, 84, 71, 65]], 1),
}


@pytest.mark.parametrize("case", sorted(_CLASS_CASES))
def test_fetch_width_follows_the_codes(case):
    """ACGT takes the 16-base fetch; lowercase, N or any other code up to
    255 takes 4; a wider code takes 1.  The per-width counter counts the
    pairs, and the scores are Gotoh's whatever the width."""
    seqs, chars = _CLASS_CASES[case]
    pats = _codes(seqs)
    txts = [np.concatenate([p[:2], p[3:1:-1], p[4:]]) for p in pats]
    P, plen = pack_batch(pats)
    T, tlen = pack_batch(txts)
    assert 32 // fetch_char_bits(P, plen, T, tlen) == chars
    eng = AlignmentEngine(DEFAULT, backend="kernel", s_max=S_MAX,
                          k_max=K_MAX)
    before = {c: _fetch_pairs(c) for c in session_mod.FETCH_PAIRS}
    er = eng.align(pats, txts)
    for c in session_mod.FETCH_PAIRS:
        assert _fetch_pairs(c) - before[c] == (len(pats) if c == chars
                                               else 0), c
    want = [gotoh_score_vec(p, t, DEFAULT) for p, t in zip(pats, txts)]
    np.testing.assert_array_equal(er.scores, want)


def test_codes_past_the_lengths_do_not_count():
    P, plen = pack_batch(["ACGTAC", "GATTACA"])
    P[0, 6:] = 300                              # padding, never read
    assert fetch_char_bits(P, plen, P, plen) == 2
    assert fetch_char_bits(P, plen + 1, P, plen) == 32


def test_padded_serve_wave_keeps_the_16_base_fetch(rng):
    """A deadline-flushed ``ServeLoop`` wave of ACGT rows is padded with
    self-aligning ACGT rows, so it takes the 16-base fetch and the full
    wave's executable: serving compiles nothing more."""
    pats, txts = random_pairs(rng, 8, lo=40, hi=60)    # one bucket
    eng = AlignmentEngine(DEFAULT, backend="kernel", s_max=S_MAX,
                          k_max=K_MAX)
    with ServeLoop(eng, wave_pairs=8, form_deadline=0.01) as server:
        server.submit(pats, txts).result(timeout=120)          # full
        traces0 = eng.cache_traces()
        before = {c: _fetch_pairs(c) for c in session_mod.FETCH_PAIRS}
        res = server.submit(pats[:2], txts[:2]).result(timeout=120)
    assert eng.cache_traces() == traces0
    assert server.stats().waves_deadline >= 1
    # the whole padded wave, pad rows included, counts as 16 per trip
    assert _fetch_pairs(16) - before[16] == 8
    assert _fetch_pairs(4) == before[4] and _fetch_pairs(1) == before[1]
    np.testing.assert_array_equal(res.scores,
                                  gotoh_oracle(pats[:2], txts[:2]))


def test_other_backends_have_no_trip_counter():
    pats, txts = _pairs(["mismatch"] * 4)
    er = AlignmentEngine(DEFAULT, backend="ring", s_max=S_MAX,
                         k_max=K_MAX).align(pats, txts)
    assert er.stats.n_ext_trips == 0
    P, plen = pack_batch(pats)
    T, tlen = pack_batch(txts)
    res = get_backend("ring").fn(P, T, plen, tlen, pen=DEFAULT,
                                 s_max=S_MAX, k_max=K_MAX)
    assert res.n_ext_trips is None
