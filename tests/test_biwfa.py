"""BiWFA parity suite: ``trace_variant="bidir"`` must produce CIGARs that
re-score *exactly* to the forward (packed-backtrace) optimum — across all
three penalty models, the ref and ring backends, and a divergence grid —
including affine alignments whose optimal breakpoint falls inside a gap
run (the open/extend joint-state correction), empty/one-sided edges, and
budget-forced deep recursion."""
import numpy as np
import pytest

from repro.core import gotoh
from repro.core.engine import AlignmentEngine
from repro.core.scoring import Edit, GapAffine, GapLinear

ALPHA = np.frombuffer(b"ACGT", np.uint8)
MODELS = [GapAffine(4, 6, 2), GapLinear(4, 2), Edit()]
MODEL_IDS = ["affine", "linear", "edit"]
BACKENDS = ["ref", "ring"]


def _divergent_pairs(rng, n, L, div):
    """Pairs at ~``div`` divergence with multi-base insertion bursts and
    deletions — indel-heavy on purpose, so meets land inside gap runs."""
    ps, ts = [], []
    for _ in range(n):
        p = rng.choice(ALPHA, size=L).astype(np.uint8)
        t = []
        for c in p:
            r = rng.random()
            if r < div * 0.5:
                t.append(int(rng.choice(ALPHA)))
            elif r < div * 0.75:
                t.append(int(c))
                for _ in range(int(rng.integers(1, 4))):
                    t.append(int(rng.choice(ALPHA)))
            elif r < div:
                continue
            else:
                t.append(int(c))
        ps.append(p)
        ts.append(np.asarray(t, np.uint8))
    return ps, ts


def _assert_bidir_exact(eng, pen, ps, ts):
    """bidir scores == packed scores, and every bidir CIGAR re-scores to
    exactly that cost while consuming both sequences in full."""
    ref = eng.align(ps, ts, output="cigar")
    res = eng.align(ps, ts, output="cigar", trace_variant="bidir")
    np.testing.assert_array_equal(res.scores, ref.scores)
    for i, (p, t) in enumerate(zip(ps, ts)):
        p = np.frombuffer(p.encode(), np.uint8) if isinstance(p, str) else p
        t = np.frombuffer(t.encode(), np.uint8) if isinstance(t, str) else t
        cost, ci, cj, ok = gotoh.score_cigar(res.cigars[i], p, t, pen)
        assert ok, i
        assert ci == len(p) and cj == len(t), (i, ci, cj)
        assert cost == res.scores[i], (i, cost, res.scores[i])
    return res


# ------------------------------------------- model x backend x divergence --


# higher-divergence and ref-backend combos are exhaustive-coverage tier
# (the executable-cache misses dominate); the quick tier keeps ring x 3
# models x 2%, which already exercises every code path
_GRID = [pytest.param(d, p, b,
                      marks=([pytest.mark.slow]
                             if (b == "ref" or d > 0.02) else []),
                      id=f"{d}-{mid}-{b}")
         for d in (0.02, 0.10, 0.25)
         for p, mid in zip(MODELS, MODEL_IDS)
         for b in BACKENDS]


@pytest.mark.parametrize("div,pen,backend", _GRID)
def test_bidir_parity_recursive(rng, pen, backend, div):
    # trace_budget far below s*(n+m) forces the meet-and-recurse path on
    # every pair; zero driver fallbacks allowed — exactness must come from
    # the breakpoint math, not the packed safety net
    ps, ts = _divergent_pairs(rng, 6, 240, div)
    eng = AlignmentEngine(pen, backend=backend, trace_budget=1500)
    res = _assert_bidir_exact(eng, pen, ps, ts)
    assert res.stats.n_bidir_fallback == 0
    assert res.stats.n_meet_unmet == 0


@pytest.fixture(scope="module")
def ring_meets():
    """An indel-heavy affine batch whose costs need meet waves under a
    trace budget of 1,000 cells, and the ring backend's bidir result."""
    pen = GapAffine(4, 6, 2)
    ps, ts = _divergent_pairs(np.random.default_rng(16), 6, 160, 0.10)
    want = AlignmentEngine(pen, backend="ring", trace_budget=1000).align(
        ps, ts, output="cigar", trace_variant="bidir")
    return pen, ps, ts, want


@pytest.mark.parametrize("backend", ["ref", "kernel", "shardmap"])
def test_bidir_meet_waves_on_every_backend(ring_meets, backend):
    # every backend's meet waves run the one jnp meet solver and its
    # children trace to the same codes, so a small budget gives ring's
    # CIGARs op for op, with every meet found
    pen, ps, ts, want = ring_meets
    eng = AlignmentEngine(pen, backend=backend, trace_budget=1000)
    got = _assert_bidir_exact(eng, pen, ps, ts)
    assert any("bidir_meet" in key for key in eng._cache)
    assert want.stats.n_meet_unmet == got.stats.n_meet_unmet == 0
    np.testing.assert_array_equal(got.scores, want.scores)
    for a, b in zip(got.cigars, want.cigars):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("pen", MODELS, ids=MODEL_IDS)
def test_bidir_base_case_direct(rng, pen):
    # default budget: short pairs fit the packed traceback outright, so the
    # driver must base-case without any meet round and still match
    ps, ts = _divergent_pairs(rng, 8, 80, 0.10)
    eng = AlignmentEngine(pen, backend="ring")
    res = _assert_bidir_exact(eng, pen, ps, ts)
    assert res.stats.n_meet_unmet == 0


# ------------------------------------------------------- affine gap joins --


def test_affine_split_inside_gap_run(rng):
    # one long deletion dead-center: the midpoint meet lands *inside* the
    # run, so the I/D joint state must carry across the split (charging the
    # gap open exactly once) or the stitched cost comes out o too high
    pen = GapAffine(4, 6, 2)
    p = rng.choice(ALPHA, size=300).astype(np.uint8)
    t = np.concatenate([p[:140], p[200:]])           # 60-base deletion
    p2 = np.concatenate([p[:150], rng.choice(ALPHA, size=70).astype(np.uint8),
                         p[150:]])                   # 70-base insertion (text side)
    eng = AlignmentEngine(pen, backend="ring", trace_budget=900)
    res = _assert_bidir_exact(eng, pen, [p, p2], [t, p])
    assert res.stats.n_bidir_fallback == 0


def test_affine_gap_at_edges(rng):
    # leading/trailing gap runs exercise the begin/end boundary-state
    # seeding (open already charged by the parent on one side only)
    pen = GapAffine(4, 6, 2)
    core = rng.choice(ALPHA, size=200).astype(np.uint8)
    pad = rng.choice(ALPHA, size=40).astype(np.uint8)
    ps = [np.concatenate([pad, core]), core]
    ts = [core, np.concatenate([core, pad])]
    eng = AlignmentEngine(pen, backend="ring", trace_budget=700)
    _assert_bidir_exact(eng, pen, ps, ts)


# ------------------------------------------------------------------ edges --


@pytest.mark.parametrize("backend", BACKENDS)
def test_bidir_empty_and_one_sided(backend):
    pen = GapAffine(4, 6, 2)
    ps = ["", "ACGTACGTAC", "", "ACGT", "GATTACAGATTACA"]
    ts = ["", "", "TTTTTTTT", "ACGT", "GATTACAGATTACA"]
    eng = AlignmentEngine(pen, backend=backend, trace_budget=40)
    _assert_bidir_exact(eng, pen, ps, ts)


def test_bidir_streamed_submit(rng):
    # the per-submit seam: packed and bidir tickets interleaved in one
    # session, retired out of order via as_completed()
    pen = GapAffine(4, 6, 2)
    ps, ts = _divergent_pairs(rng, 10, 150, 0.10)
    eng = AlignmentEngine(pen, backend="ring", trace_budget=1200)
    with eng.stream(max_inflight_waves=2) as sess:
        tk_b = sess.submit(ps[:5], ts[:5], output="cigar",
                           trace_variant="bidir")
        tk_p = sess.submit(ps[5:], ts[5:], output="cigar")
        done = {t.index: t for t in sess.as_completed()}
    assert set(done) == {tk_b.index, tk_p.index}
    res_b, res_p = done[tk_b.index].result(), done[tk_p.index].result()
    for i in range(5):
        c, ci, cj, ok = gotoh.score_cigar(res_b.cigars[i], ps[i], ts[i], pen)
        assert ok and c == res_b.scores[i]
        assert ci == len(ps[i]) and cj == len(ts[i])
    oracle = [int(gotoh.gotoh_score_vec(p, t, pen.as_penalties()))
              for p, t in zip(ps, ts)]
    np.testing.assert_array_equal(res_b.scores, oracle[:5])
    np.testing.assert_array_equal(res_p.scores, oracle[5:])


def test_bidir_score_output_ignores_variant(rng):
    # trace_variant only governs tracebacks: score-only calls take the
    # plain wavefront path bit-for-bit
    ps, ts = _divergent_pairs(rng, 6, 120, 0.10)
    eng = AlignmentEngine(GapAffine(4, 6, 2), backend="ring",
                          trace_variant="bidir")
    a = eng.align(ps, ts, output="score")
    b = eng.align(ps, ts, output="score", trace_variant="packed")
    np.testing.assert_array_equal(a.scores, b.scores)


# -------------------------------------------------------- trace memory ----


@pytest.mark.slow
def test_bidir_trace_memory_below_packed(rng):
    # the headline: recursion keeps the resident trace high-water mark
    # well under the packed O(s^2) backtrace on a divergent-ish pair
    pen = GapAffine(4, 6, 2)
    ps, ts = _divergent_pairs(rng, 2, 1500, 0.08)
    eng = AlignmentEngine(pen, backend="ring", trace_budget=30000)
    ref = eng.align(ps, ts, output="cigar")
    res = _assert_bidir_exact(eng, pen, ps, ts)
    assert res.stats.peak_trace_bytes > 0
    assert res.stats.peak_trace_bytes < ref.stats.peak_trace_bytes / 4


# ------------------------------------------------- long-read sampler ------


def test_sampler_long_read_profile():
    from repro.data.reads import sample_from_reference
    ref = np.random.default_rng(11).choice(ALPHA, size=100000)
    kw = dict(read_len=5000, edit_frac=0.1, length_dist="lognormal",
              error_profile="ont", seed=5)
    a = sample_from_reference(ref, 30, **kw)
    b = sample_from_reference(ref, 30, **kw)
    for x, y in zip(a, b):             # deterministic per seed
        assert np.array_equal(x.read, y.read)
        assert (x.pos, x.strand, x.win_len) == (y.pos, y.strand, y.win_len)
    lens = np.array([r.win_len for r in a])
    assert lens.min() != lens.max()    # lognormal actually spreads
    for r in a:                        # ground truth window matches read len
        assert 0 <= r.pos <= len(ref) - r.win_len
    with pytest.raises(ValueError):
        sample_from_reference(ref, 1, error_profile="hifi")
    with pytest.raises(ValueError):
        sample_from_reference(ref, 1, length_dist="uniform")


def test_sampler_fixed_length_unchanged():
    from repro.data.reads import sample_from_reference
    ref = np.random.default_rng(12).choice(ALPHA, size=5000)
    reads = sample_from_reference(ref, 20, read_len=100, seed=3)
    assert all(r.win_len == 100 for r in reads)
