"""AlignmentEngine: backend registry, bucketed batching, executable cache,
adaptive two-pass overflow recovery — all against the Gotoh oracle."""
import numpy as np
import pytest
from conftest import gotoh_oracle as _oracle
from conftest import random_pairs as _random_pairs

from repro.core.backends import (available_backends, get_backend,
                                 register_backend, unregister_backend)
from repro.core.engine import AlignmentEngine, pack_batch
from repro.core.penalties import DEFAULT, Penalties
from repro.core.wavefront import WFAResult, wfa_scores


# ------------------------------------------------------------ registry ----


def test_builtin_backends_registered():
    for name in ("ref", "ring", "kernel", "shardmap"):
        assert name in available_backends()
        # every built-in serves output="cigar" via a trace variant
        assert get_backend(name).supports_cigar, name
    assert get_backend("shardmap").needs_mesh


def test_unknown_backend_raises():
    with pytest.raises(KeyError, match="unknown alignment backend"):
        get_backend("nope")
    with pytest.raises(KeyError):
        AlignmentEngine(backend="nope")


def test_plugin_backend_dispatches():
    calls = []

    @register_backend("test-plugin", doc="ring + call counter")
    def _plugin(pattern, text, plen, tlen, *, pen, s_max, k_max):
        calls.append(1)   # trace-time; engine jits around this
        return wfa_scores(pattern, text, plen, tlen, pen=pen,
                          s_max=s_max, k_max=k_max)

    try:
        eng = AlignmentEngine(backend="test-plugin", edit_frac=0.1)
        res = eng.align(["ACGTACGT"], ["ACGAACGT"])
        assert res.scores[0] == DEFAULT.x
        assert calls   # plugin actually traced
    finally:
        unregister_backend("test-plugin")
    assert "test-plugin" not in available_backends()


def test_cigar_needs_capable_backend():
    # a plug-in without a trace variant is score-only: CIGAR output must be
    # rejected at construction (default output) and per call
    @register_backend("score-only")
    def _scores(pattern, text, plen, tlen, *, pen, s_max, k_max):
        return wfa_scores(pattern, text, plen, tlen, pen=pen,
                          s_max=s_max, k_max=k_max)

    try:
        with pytest.raises(ValueError, match="score-only"):
            AlignmentEngine(backend="score-only", with_cigar=True)
        with pytest.raises(ValueError, match="score-only"):
            AlignmentEngine(backend="score-only", output="cigar")
        eng = AlignmentEngine(backend="score-only", edit_frac=0.1)
        with pytest.raises(ValueError, match="score-only"):
            eng.align(["ACGT"], ["ACGT"], output="cigar")
    finally:
        unregister_backend("score-only")
    with pytest.raises(ValueError, match="output mode"):
        AlignmentEngine(backend="ring", output="sideways")


# ------------------------------------------------- bucketing + oracle ----


def test_mixed_length_batch_matches_gotoh(rng):
    pats, txts = _random_pairs(rng, 80, lo=5, hi=250)
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    res = eng.align(pats, txts)
    assert res.stats.n_buckets >= 2          # genuinely bucketed run
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts))
    # every pair resolved: the recovery pass leaves no -1 behind
    assert (res.scores >= 0).all()


def test_bucketed_equals_unbucketed(rng):
    pats, txts = _random_pairs(rng, 40, lo=5, hi=150)
    kw = dict(backend="ring", edit_frac=0.05)
    bucketed = AlignmentEngine(bucket_by_length=True, **kw).align(pats, txts)
    flat = AlignmentEngine(bucket_by_length=False, **kw).align(pats, txts)
    np.testing.assert_array_equal(bucketed.scores, flat.scores)
    assert flat.stats.n_buckets == 1


def test_ref_backend_bucketed_cigars(rng):
    pen = Penalties(x=3, o=4, e=1)
    pats, txts = _random_pairs(rng, 20, lo=4, hi=120)
    # with_cigar is the deprecated spelling of the default output mode
    eng = AlignmentEngine(pen, backend="ref", edit_frac=0.1, with_cigar=True)
    assert eng.with_cigar and eng.default_output == "cigar"
    res = eng.align(pats, txts)
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts, pen))
    from repro.core.gotoh import score_cigar
    for i, (p, t) in enumerate(zip(pats, txts)):
        cost, ci, cj, ok = score_cigar(
            res.cigars[i], np.frombuffer(p.encode(), np.uint8),
            np.frombuffer(t.encode(), np.uint8), pen)
        assert ok and cost == res.scores[i]
        assert ci == len(p) and cj == len(t)


# ------------------------------------------------- adaptive two-pass ----


def test_large_len_diff_recovers_with_stable_bucket_bounds(rng):
    # one pair's length diff exceeds the E-derived band: pass-1 bounds must
    # stay data-independent (same cache key), the pair recovers in pass 2
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    near = _random_pairs(rng, 8, lo=100, hi=120)
    base = eng.align(*near)
    k1 = [(b.lmax, b.s_max, b.k_max) for b in base.stats.buckets
          if not b.recovery]
    pats = list(near[0]) + ["A" * 120]
    txts = list(near[1]) + ["A" * 40]       # diff 80 >> band
    res = eng.align(pats, txts)
    k2 = [(b.lmax, b.s_max, b.k_max) for b in res.stats.buckets
          if not b.recovery]
    assert k1 == k2                          # outlier didn't reshape pass 1
    assert res.stats.n_overflow >= 1 and res.stats.n_recovered >= 1
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts))


def test_overflow_pairs_get_real_scores_on_second_pass():
    # wildly divergent pairs: far beyond the 2% budget of pass 1
    pats = ["A" * 40, "ACGT" * 10, "G" * 30]
    txts = ["T" * 40, "TGCA" * 10, "C" * 35]
    eng = AlignmentEngine(backend="ring", edit_frac=0.02)
    res = eng.align(pats, txts)
    assert res.stats.n_overflow == 3
    assert res.stats.n_recovered == 3
    assert any(b.recovery for b in res.stats.buckets)
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts))


def test_adaptive_off_leaves_overflow_unresolved():
    eng = AlignmentEngine(backend="ring", edit_frac=0.02, adaptive=False)
    res = eng.align(["A" * 40], ["T" * 40])
    assert res.scores[0] == -1
    assert res.stats.n_overflow == 1        # counted, but no recovery ran
    assert res.stats.n_recovered == 0
    assert not any(b.recovery for b in res.stats.buckets)


def test_reregistered_backend_invalidates_cache():
    from repro.core.wavefront import wfa_scores as _ws

    @register_backend("swap-test")
    def _v1(pattern, text, plen, tlen, *, pen, s_max, k_max):
        return _ws(pattern, text, plen, tlen, pen=pen, s_max=s_max,
                   k_max=k_max)

    try:
        eng = AlignmentEngine(backend="swap-test", edit_frac=0.1)
        eng.align(["ACGTACGT"], ["ACGAACGT"])

        @register_backend("swap-test")
        def _v2(pattern, text, plen, tlen, *, pen, s_max, k_max):
            res = _ws(pattern, text, plen, tlen, pen=pen, s_max=s_max,
                      k_max=k_max)
            return WFAResult(res.score * 0 + 99, None, None, None,
                             res.n_steps)

        res = eng.align(["ACGTACGT"], ["ACGAACGT"])
        # new fn used, not a stale executable; the engine ran the default
        # 4/6/2 as 2/3/1, so the backend's score comes back doubled
        assert res.scores[0] == 99 * 2
    finally:
        unregister_backend("swap-test")


def test_explicit_s_max_pins_cap_no_recovery():
    eng = AlignmentEngine(backend="ring", s_max=3)
    res = eng.align(["AAAA"], ["TTTT"])
    assert res.scores[0] == -1
    assert not any(b.recovery for b in res.stats.buckets)


# ------------------------------------------------- executable cache ----


def test_cache_hits_on_repeated_same_bucket_calls(rng):
    pats, txts = _random_pairs(rng, 30, lo=40, hi=120)
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    first = eng.align(pats, txts)
    assert first.stats.cache_misses > 0 and first.stats.cache_hits == 0
    assert first.stats.n_traces == first.stats.cache_misses

    second = eng.align(pats, txts)
    assert second.stats.cache_misses == 0
    assert second.stats.cache_hits == first.stats.cache_misses
    assert second.stats.n_traces == 0       # zero re-traces at serving time
    np.testing.assert_array_equal(first.scores, second.scores)

    # same buckets, different data: still fully cached
    pats2, txts2 = _random_pairs(rng, 30, lo=40, hi=120)
    third = eng.align(pats2, txts2)
    assert third.stats.n_traces == 0 and third.stats.cache_misses == 0


def test_pair_count_quantization_shares_executables(rng):
    # 17 and 23 pairs both pad to the same quantized pair count (24)
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    p1, t1 = _random_pairs(rng, 17, lo=50, hi=60)
    p2, t2 = _random_pairs(rng, 23, lo=50, hi=60)
    eng.align(p1, t1)
    res = eng.align(p2, t2)
    assert res.stats.cache_hits > 0 and res.stats.n_traces == 0


# ------------------------------------------------- wrappers / shims ----


def test_wfaligner_shim_matches_engine(rng):
    from repro.core.aligner import WFAligner
    pats, txts = _random_pairs(rng, 25, lo=5, hi=100)
    shim = WFAligner(backend="ring", edit_frac=0.05).align(pats, txts)
    eng = AlignmentEngine(backend="ring", edit_frac=0.05).align(pats, txts)
    np.testing.assert_array_equal(shim.scores, eng.scores)


def test_pim_shim_returns_stats(rng):
    from repro.core.aligner import WFAligner
    from repro.core.pim import PIMBatchAligner
    pats, txts = _random_pairs(rng, 12, lo=20, hi=60)
    p, plen = pack_batch(pats)
    t, tlen = pack_batch(txts)
    ex = PIMBatchAligner(WFAligner(backend="ring", edit_frac=0.05),
                         chunk_pairs=8)
    scores, stats = ex.run_arrays(p, plen, t, tlen)
    assert stats.n_pairs == 12
    assert stats.bytes_in > 0 and stats.bytes_out >= 12 * 4
    assert stats.t_total >= stats.t_kernel
    np.testing.assert_array_equal(scores, _oracle(pats, txts))


def test_kernel_backend_through_engine():
    eng = AlignmentEngine(backend="kernel", edit_frac=0.1,
                          min_bucket_len=16)
    pats = ["ACGTACGTAC", "TTTTGGGG"]
    txts = ["ACGAACGTAC", "TTTTGGGA"]
    res = eng.align(pats, txts)
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts))
