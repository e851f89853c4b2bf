"""Fused-grid kernel features: heuristics under trace, compacting band,
the packed fetch against the oracle, BiWFA meet waves on the kernel
backend, and engine ``backend_opts`` plumbing.

Everything here is exact-equality: scores are integers and the compacting
band / blocking knobs are all contracted to be bit-identical to the
full-width reference whenever the live span fits the band.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cigar as cigar_mod
from repro.core import wavefront as wf
from repro.core.engine import AlignmentEngine, problem_bounds
from repro.core.gotoh import score_cigar
from repro.core.penalties import DEFAULT, Penalties
from repro.core.scoring import (AdaptiveBand, Edit, GapAffine, GapLinear,
                                ZDrop, as_model)
from repro.data.reads import ReadPairSpec, generate_pairs
from repro.kernels.wfa import ops as kops
from repro.kernels.wfa import ref_scores

HEURS = [AdaptiveBand(min_wf_len=4, max_distance_diff=10), ZDrop(zdrop=12)]
MODELS = [DEFAULT, Edit()]           # affine + linear recurrences
_hid = lambda h: type(h).__name__
_mid = lambda m: as_model(m).kind


def _pairs(n, L, E, seed):
    P, plen, T, tlen = generate_pairs(
        ReadPairSpec(n_pairs=n, read_len=L, edit_frac=E, seed=seed))
    # exact worst-case bounds: s_max large enough that the heuristic (not
    # the score budget) is what limits the wavefront
    s_max, k_max = problem_bounds(DEFAULT, plen, tlen, None)
    return P, plen, T, tlen, s_max, k_max


def _jnp_cigars(P, T, plen, tlen, pen, s_max, k_max, heur=None,
                band_cap=None):
    res = wf.wfa_scores_packed(jnp.asarray(P), jnp.asarray(T),
                               jnp.asarray(plen), jnp.asarray(tlen),
                               pen=pen, s_max=s_max, k_max=k_max, heur=heur,
                               band_cap=band_cap)
    return np.asarray(res.score), cigar_mod.traceback_packed_batch(
        res, pen, P, T, plen, tlen)


def _kernel_cigars(P, T, plen, tlen, pen, s_max, k_max, heur=None, **kw):
    score, m_bt, i_bt, d_bt = kops.wfa_align_trace(
        P, T, plen, tlen, pen=pen, s_max=s_max, k_max=k_max, heur=heur,
        **kw)
    res = wf.WFAResult(score, None, None, None, jnp.int32(s_max),
                       m_bt, i_bt, d_bt)
    return np.asarray(score), cigar_mod.traceback_packed_batch(
        res, pen, P, T, plen, tlen)


# -- heuristics through the kernel trace path -------------------------------


@pytest.mark.parametrize("pen", MODELS, ids=_mid)
@pytest.mark.parametrize("heur", HEURS, ids=_hid)
def test_kernel_heuristic_trace_parity(heur, pen):
    """AdaptiveBand/ZDrop x linear/affine, trace=True: the kernel's pruned
    scores AND CIGARs must match the jnp solver's exactly."""
    P, plen, T, tlen, s_max, k_max = _pairs(12, 72, 0.08, 21)
    ref_s, ref_c = _jnp_cigars(P, T, plen, tlen, pen, s_max, k_max, heur)
    got_s, got_c = _kernel_cigars(P, T, plen, tlen, pen, s_max, k_max, heur)
    np.testing.assert_array_equal(ref_s, got_s)
    for i, (a, b) in enumerate(zip(ref_c, got_c)):
        np.testing.assert_array_equal(a, b, err_msg=f"pair {i}")


@pytest.mark.parametrize("pen", MODELS, ids=_mid)
@pytest.mark.parametrize("heur", HEURS, ids=_hid)
def test_kernel_heuristic_scores_vs_ref(heur, pen):
    P, plen, T, tlen, s_max, k_max = _pairs(16, 64, 0.10, 22)
    ref = np.asarray(ref_scores(P, T, plen, tlen, pen=pen, s_max=s_max,
                                k_max=k_max, heur=heur))
    got = np.asarray(kops.wfa_align(P, T, plen, tlen, pen=pen, s_max=s_max,
                                    k_max=k_max, heur=heur))
    np.testing.assert_array_equal(ref, got)


# -- compacting band: bit-identical when the live span fits -----------------


@pytest.mark.parametrize("pen", MODELS, ids=_mid)
@pytest.mark.parametrize("heur", HEURS, ids=_hid)
def test_band_compaction_jnp_identical(heur, pen):
    """Full-width vs compacting-band jnp solve: same scores, same CIGARs.
    The heuristic's own band_cap bounds its live span, so compaction is a
    pure re-indexing (per-pair offset), not an approximation."""
    P, plen, T, tlen, s_max, k_max = _pairs(12, 72, 0.08, 23)
    cap = heur.band_cap(2 * k_max + 1)
    assert cap is not None and cap < 2 * k_max + 1
    full_s, full_c = _jnp_cigars(P, T, plen, tlen, pen, s_max, k_max, heur)
    band_s, band_c = _jnp_cigars(P, T, plen, tlen, pen, s_max, k_max, heur,
                                 band_cap=cap)
    np.testing.assert_array_equal(full_s, band_s)
    for i, (a, b) in enumerate(zip(full_c, band_c)):
        np.testing.assert_array_equal(a, b, err_msg=f"pair {i}")


@pytest.mark.parametrize("heur", HEURS, ids=_hid)
def test_band_compaction_kernel_identical(heur):
    P, plen, T, tlen, s_max, k_max = _pairs(12, 72, 0.08, 24)
    cap = heur.band_cap(2 * k_max + 1)
    full_s, full_c = _kernel_cigars(P, T, plen, tlen, DEFAULT, s_max, k_max,
                                    heur)
    band_s, band_c = _kernel_cigars(P, T, plen, tlen, DEFAULT, s_max, k_max,
                                    heur, band_cap=cap)
    np.testing.assert_array_equal(full_s, band_s)
    for i, (a, b) in enumerate(zip(full_c, band_c)):
        np.testing.assert_array_equal(a, b, err_msg=f"pair {i}")


def test_band_scores_offset_correctness():
    """Score-only band path on ragged lengths: the per-pair offset must
    track fronts centered far from k=0 (tlen != plen)."""
    rng = np.random.default_rng(9)
    n = 10
    plen = rng.integers(20, 90, size=n).astype(np.int32)
    tlen = np.clip(plen + rng.integers(-15, 16, size=n), 4,
                   None).astype(np.int32)
    P = rng.integers(65, 69, size=(n, int(plen.max()))).astype(np.int32)
    T = rng.integers(65, 69, size=(n, int(tlen.max()))).astype(np.int32)
    s_max, k_max = problem_bounds(DEFAULT, plen, tlen, None)
    heur = ZDrop(zdrop=40)
    cap = heur.band_cap(2 * k_max + 1)
    full = np.asarray(wf.wfa_scores(P, T, plen, tlen, pen=DEFAULT,
                                    s_max=s_max, k_max=k_max,
                                    heur=heur).score)
    band = np.asarray(wf.wfa_scores(P, T, plen, tlen, pen=DEFAULT,
                                    s_max=s_max, k_max=k_max, heur=heur,
                                    band_cap=cap).score)
    np.testing.assert_array_equal(full, band)


@pytest.mark.parametrize("band", [False, True], ids=["full", "band"])
@pytest.mark.parametrize("pen", [GapAffine(4, 6, 2), GapLinear(4, 2), Edit()],
                         ids=["affine", "linear", "edit"])
def test_ring_entry_points_agree(pen, band):
    """``wfa_scores`` and ``wfa_scores_packed`` share one score loop: under
    AdaptiveBand, at full width and under a compacting band narrower than
    K, they return the same scores and step counts, and the packed planes
    decode to alignments of those scores."""
    heur = AdaptiveBand(min_wf_len=4, max_distance_diff=10)
    P, plen, T, tlen, s_max, k_max = _pairs(12, 72, 0.08, 31)
    cap = heur.band_cap(2 * k_max + 1) if band else None
    assert cap is None or cap < 2 * k_max + 1
    args = (jnp.asarray(P), jnp.asarray(T), jnp.asarray(plen),
            jnp.asarray(tlen))
    kw = dict(pen=pen, s_max=s_max, k_max=k_max, heur=heur, band_cap=cap)
    score = wf.wfa_scores(*args, **kw)
    packed = wf.wfa_scores_packed(*args, **kw)
    np.testing.assert_array_equal(np.asarray(score.score),
                                  np.asarray(packed.score))
    assert int(score.n_steps) == int(packed.n_steps)
    got = np.asarray(packed.score)
    assert (got >= 0).all()
    ops = cigar_mod.traceback_packed_batch(packed, pen, P, T, plen, tlen)
    for i, c in enumerate(ops):
        cost, ci, cj, ok = score_cigar(c, P[i, :plen[i]], T[i, :tlen[i]],
                                       as_model(pen))
        assert ok and (ci, cj) == (plen[i], tlen[i]), i
        assert cost == got[i], (i, cost, got[i])


# -- the one fetch against the oracle ---------------------------------------


# ``trips``: the kernel's extend trips on the 100 bp, E = 4% batch below
# (seed 4, 24 pairs, 16 characters per trip).  The count is the
# algorithm's, so no way of fetching the words may change it.
@pytest.mark.parametrize("pen,trips", [(DEFAULT, 147),
                                       (Penalties(1, 0, 1), 71),
                                       (GapLinear(4, 2), 113), (Edit(), 71)],
                         ids=["affine", "linear", "gap-linear", "edit"])
def test_gather_mode_invariance(pen, trips):
    """The kernel's one character fetch, the one the chip runs, returns
    ``wfa_forward``'s scores at 16 characters per trip and at one; and on
    a seeded 100 bp, E = 4% batch the kernel's scores, score steps and
    packed trace planes equal the ring loop's (``wfa_scores`` /
    ``wfa_scores_packed``, run per 8-pair block as the kernel's grid runs
    them), with the extend trips pinned."""
    P, plen, T, tlen, s_max, k_max = _pairs(8, 32, 0.06, 25)
    want = np.asarray(wf.wfa_forward(P, T, plen, tlen, pen=pen, s_max=s_max,
                                     k_max=k_max, keep_history=False).score)
    assert (want >= 0).all()
    for char_bits in (2, 32):
        got = np.asarray(kops.wfa_align(P, T, plen, tlen, pen=pen,
                                        s_max=s_max, k_max=k_max,
                                        char_bits=char_bits))
        np.testing.assert_array_equal(got, want, err_msg=f"{char_bits}")

    P, plen, T, tlen = generate_pairs(
        ReadPairSpec(n_pairs=24, read_len=100, edit_frac=0.04, seed=4))
    s_max, k_max = problem_bounds(pen, plen, tlen, 0.04)
    score, steps, k_trips = kops.wfa_align(P, T, plen, tlen, pen=pen,
                                           s_max=s_max, k_max=k_max,
                                           char_bits=2, counts=True)
    t_score, *planes, t_steps, t_trips = kops.wfa_align_trace(
        P, T, plen, tlen, pen=pen, s_max=s_max, k_max=k_max, char_bits=2,
        counts=True)
    planes = [np.asarray(p) for p in planes if p is not None]
    c = planes[0].shape[2] // 2
    ring_steps = 0
    for b in range(0, len(plen), 8):
        blk = [jnp.asarray(a[b:b + 8]) for a in (P, T, plen, tlen)]
        ring = wf.wfa_scores(*blk, pen=pen, s_max=s_max, k_max=k_max)
        packed = wf.wfa_scores_packed(*blk, pen=pen, s_max=s_max,
                                      k_max=k_max)
        np.testing.assert_array_equal(np.asarray(score)[b:b + 8],
                                      np.asarray(ring.score))
        np.testing.assert_array_equal(np.asarray(t_score)[b:b + 8],
                                      np.asarray(packed.score))
        assert int(ring.n_steps) == int(packed.n_steps)
        ring_steps += int(ring.n_steps)
        want_planes = [np.asarray(p) for p in
                       (packed.m_bt, packed.i_bt, packed.d_bt)
                       if p is not None]
        assert len(want_planes) == len(planes)
        for g, w in zip(planes, want_planes):
            np.testing.assert_array_equal(
                g[:, b:b + 8, c - k_max:c + k_max + 1], w)
    assert int(steps) == int(t_steps) == ring_steps
    assert int(k_trips) == int(t_trips) == trips


# -- engine backend_opts plumbing -------------------------------------------


def _strs(P, lens):
    return ["".join(chr(c) for c in row[:n]) for row, n in zip(P, lens)]


@pytest.fixture(scope="module")
def seqs():
    P, plen, T, tlen = generate_pairs(
        ReadPairSpec(n_pairs=12, read_len=64, edit_frac=0.06, seed=29))
    return _strs(P, plen), _strs(T, tlen)


def test_engine_rejects_unknown_backend_opt():
    with pytest.raises(ValueError, match="bogus"):
        AlignmentEngine(backend="ring", backend_opts={"bogus": 1})
    with pytest.raises(ValueError, match="block_pairs"):
        # kernel-only knob on the ring backend: rejected at construction
        AlignmentEngine(backend="ring", backend_opts={"block_pairs": 4})


def test_engine_block_pairs_parity(seqs):
    pats, txts = seqs
    base = AlignmentEngine(backend="kernel").align(pats, txts,
                                                   output="cigar")
    bp = AlignmentEngine(backend="kernel",
                         backend_opts={"block_pairs": 4}).align(
        pats, txts, output="cigar")
    np.testing.assert_array_equal(base.scores, bp.scores)
    for a, b in zip(base.cigars, bp.cigars):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("backend", ["ring", "kernel"])
def test_engine_band_cap_auto(seqs, backend):
    """band_cap='auto' resolves through the heuristic's radius and stays
    score-identical to the full-width heuristic run (related pairs: the
    live span fits the band)."""
    pats, txts = seqs
    heur = AdaptiveBand()
    full = AlignmentEngine(backend=backend, heuristic=heur).align(pats, txts)
    band = AlignmentEngine(backend=backend, heuristic=heur,
                           backend_opts={"band_cap": "auto"}).align(
        pats, txts)
    assert band.approximate
    np.testing.assert_array_equal(full.scores, band.scores)


def test_engine_band_cap_auto_exact_is_noop(seqs):
    """Exact alignment has no pruning radius: 'auto' must stay full width
    (and in particular must not raise or change scores)."""
    pats, txts = seqs
    plain = AlignmentEngine(backend="ring").align(pats, txts)
    auto = AlignmentEngine(backend="ring",
                           backend_opts={"band_cap": "auto"}).align(
        pats, txts)
    np.testing.assert_array_equal(plain.scores, auto.scores)


@pytest.mark.parametrize("pen", [GapAffine(4, 6, 2), GapLinear(4, 2),
                                 Edit()], ids=["affine", "linear", "edit"])
def test_engine_kernel_bidir_meet_variant(pen):
    """trace_variant='bidir' on the kernel backend: its meet waves run the
    jnp meet solver, as on every backend, so its CIGARs are the ring
    backend's op for op and its scores the packed trace's.  Pairs at 10%
    divergence and a small trace budget make the driver meet before it
    traces."""
    P, plen, T, tlen = generate_pairs(
        ReadPairSpec(n_pairs=6, read_len=100, edit_frac=0.10, seed=30))
    pats, txts = _strs(P, plen), _strs(T, tlen)
    packed = AlignmentEngine(pen, backend="kernel").align(pats, txts,
                                                          output="cigar")
    ring = AlignmentEngine(pen, backend="ring", trace_budget=600).align(
        pats, txts, output="cigar", trace_variant="bidir")
    eng = AlignmentEngine(pen, backend="kernel", trace_budget=600)
    bidir = eng.align(pats, txts, output="cigar", trace_variant="bidir")
    assert any("bidir_meet" in key for key in eng._cache)
    assert bidir.stats.n_meet_unmet == 0
    np.testing.assert_array_equal(packed.scores, bidir.scores)
    for a, b in zip(ring.cigars, bidir.cigars):
        np.testing.assert_array_equal(a, b)
