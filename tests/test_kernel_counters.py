"""The Pallas kernel's loop counters: per grid block, the score steps it
ran and the extend trips it took, against a plain Python loop over the
same gap-affine recurrence (dicts of diagonals, one lane at a time), and
carried out through the kernel backend into ``EngineResult``.

The kernel runs interpret=True on CPU; the counts are the algorithm's,
so they are the same compiled on the chip (one character per trip)."""
import numpy as np
import pytest

from repro.core.backends import get_backend
from repro.core.engine import AlignmentEngine, pack_batch
from repro.core.penalties import DEFAULT
from repro.kernels.wfa import ops as kops

BP = 8          # pairs per grid block
S_MAX = 40
K_MAX = 16      # -> k_pad 128, lanes k = -64 .. 63


def _k_pad(k_max: int) -> int:
    return -(-(2 * k_max + 1) // 128) * 128


def _extend(fronts, pats, txts):
    """Lock-step greedy extension over the block -> (fronts, trips).

    Each trip advances every lane whose next characters match; the block
    stops after the first trip in which no lane advanced."""
    trips = 0
    while True:
        trips += 1
        moved = False
        for b, front in enumerate(fronts):
            p, t = pats[b], txts[b]
            for k, h in front.items():
                v = h - k
                if 0 <= h < len(t) and 0 <= v < len(p) and t[h] == p[v]:
                    front[k] = h + 1
                    moved = True
        if not moved:
            return fronts, trips


def _block_counters(pats, txts, pen, s_max, k_pad):
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
    fronts, trips = _extend([{0: 0} for _ in range(n)], pats, txts)
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
        fronts, n_trips = _extend(pre, pats, txts)
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


def _reference(pats, txts, pen=DEFAULT, s_max=S_MAX, k_max=K_MAX):
    blocks = [_block_counters(pats[i:i + BP], txts[i:i + BP], pen, s_max,
                              _k_pad(k_max))
              for i in range(0, len(pats), BP)]
    return blocks, sum(s for s, _ in blocks), sum(t for _, t in blocks)


CASES = {
    "zero_edits": ["same"] * 8,
    "one_mismatch": ["mismatch"] * 8,
    "indel": ["insertion", "deletion"] * 4,
    "mixed_two_blocks": ["same", "mismatch", "insertion", "deletion"] * 3,
}


@pytest.mark.parametrize("trace", [False, True], ids=["score", "trace"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_block_counters_match_a_plain_loop(case, trace):
    pats, txts = _pairs(CASES[case])
    P, plen = pack_batch(pats)
    T, tlen = pack_batch(txts)
    bp = 8
    P2, T2, plen2, tlen2, _ = kops._prep(P, T, plen, tlen, bp)
    out = kops.wfa_pallas(P2, T2, plen2, tlen2, pen=DEFAULT, s_max=S_MAX,
                          k_pad=_k_pad(K_MAX), block_pairs=bp,
                          interpret=True, trace=trace)
    steps, trips = np.asarray(out[1])[:, 0], np.asarray(out[2])[:, 0]
    blocks, _, _ = _reference(pats, txts)
    assert len(steps) == len(blocks) * bp
    for j, (want_s, want_t) in enumerate(blocks):
        # one value per block, repeated over its rows
        assert set(steps[j * bp:(j + 1) * bp]) == {want_s}, j
        assert set(trips[j * bp:(j + 1) * bp]) == {want_t}, j


def test_zero_edit_block_takes_one_step_and_one_run_of_trips():
    """Identical pairs finish at s = 0: one score row, and the extension
    walks the whole read once (one trip per base, plus the trip that
    finds nothing left to match)."""
    pats, txts = _pairs(CASES["zero_edits"])
    blocks, _, _ = _reference(pats, txts)
    assert blocks == [(1, len(pats[0]) + 1)]


@pytest.mark.parametrize("output", ["score", "cigar"])
def test_kernel_backend_reports_its_real_counts(output):
    """``n_steps`` is the kernel's summed block steps (not ``s_max``), and
    ``n_ext_trips`` its summed trips, through the backend and the
    engine."""
    pats, txts = _pairs(CASES["mixed_two_blocks"] + ["mismatch"] * 4,
                        seed=3)
    _, want_steps, want_trips = _reference(pats, txts)
    P, plen = pack_batch(pats)
    T, tlen = pack_batch(txts)
    spec = get_backend("kernel")
    fn = spec.variant(output, "affine")
    res = fn(P, T, plen, tlen, pen=DEFAULT, s_max=S_MAX, k_max=K_MAX)
    assert int(res.n_steps) == want_steps
    assert int(res.n_ext_trips) == want_trips

    eng = AlignmentEngine(DEFAULT, backend="kernel", s_max=S_MAX,
                          k_max=K_MAX)
    er = eng.align(pats, txts, output=output)
    assert (er.scores >= 0).all()
    assert er.n_steps == want_steps != er.s_max
    assert er.stats.n_ext_trips == want_trips


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
