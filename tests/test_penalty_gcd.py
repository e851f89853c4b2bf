"""Executables run the penalty model divided by the gcd of its penalties.

The paper's 4/6/2 runs as 2/3/1: every wavefront at an odd 4/6/2 score
is empty, so the reduced model computes the same fronts in half the
score steps.  Scores, bounds and CIGARs come back in the caller's units.
These tests hold the engine at 4/6/2 to the Gotoh oracle on every
backend, to direct backend calls under the unreduced model (exact,
pruned and traced), and to itself with the reduction switched off
(BiWFA); they show the bound keeps sending the same pairs to recovery,
that the kernel's own loop counters fall as predicted, and that a model
whose penalties share no factor builds exactly the executable it did.

Pairs come from the benchmark's traffic generator (100 bp reads,
substitution/insertion/deletion 0.6/0.2/0.2), with the kernel
interpreted on the CPU; its step and trip counts are the algorithm's, the
same compiled on the chip.
"""
import json
import math
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "chipbench") not in sys.path:
    sys.path.insert(0, str(ROOT / "chipbench"))

import traffic  # noqa: E402

from repro.core import cigar as cigar_mod  # noqa: E402
from repro.core import scoring  # noqa: E402
from repro.core import session as session_mod  # noqa: E402
from repro.core import wavefront as wf  # noqa: E402
from repro.core.backends import (ALL_MODELS, get_backend,  # noqa: E402
                                 register_backend, unregister_backend)
from repro.core.engine import AlignmentEngine  # noqa: E402
from repro.core.gotoh import gotoh_score_vec, score_cigar  # noqa: E402
from repro.core.scoring import (AdaptiveBand, Edit, GapAffine,  # noqa: E402
                                GapLinear, ZDrop)
from repro.obs.metrics import REGISTRY  # noqa: E402

PAPER = GapAffine(4, 6, 2)
SEED = 2147490010
PAIRS = 64


def _pairs(edit_frac, n=PAIRS, seed=SEED):
    return traffic.generate_pairs(n, 100, edit_frac, 0.6, 0.2, seed)


def _gotoh(P, plen, T, tlen, pen=PAPER):
    return np.asarray([gotoh_score_vec(P[i, :plen[i]], T[i, :tlen[i]],
                                       pen.as_penalties())
                       for i in range(len(plen))], np.int32)


def _only_exe(eng):
    (exe,) = eng._cache.values()
    return exe


@pytest.fixture
def unreduced(monkeypatch):
    """Executables built while this is active run the caller's model."""
    monkeypatch.setattr(GapAffine, "reduced", scoring.PenaltyModel.reduced)


def _counter(name):
    c = REGISTRY.get(name)
    return 0 if c is None else c.value


# --- the model's reduction ---------------------------------------------------


@pytest.mark.parametrize("model,want", [
    (GapAffine(4, 6, 2), (GapAffine(2, 3, 1), 2)),
    (GapAffine(6, 9, 3), (GapAffine(2, 3, 1), 3)),
    (GapAffine(4, 0, 2), (GapAffine(2, 0, 1), 2)),
    (GapAffine(5, 3, 2), (GapAffine(5, 3, 2), 1)),
    (GapLinear(4, 2), (GapLinear(2, 1), 2)),
    (GapLinear(3, 2), (GapLinear(3, 2), 1)),
    (Edit(), (Edit(), 1)),
], ids=lambda v: str(v))
def test_reduced_divides_every_penalty_by_their_gcd(model, want):
    got, g = model.reduced()
    assert (got, g) == want
    assert got.kind == model.kind
    assert (got.x * g, got.o * g, got.e * g) == (model.x, model.o, model.e)


# --- correctness against the oracle and the unreduced backends -------------


@pytest.mark.parametrize("backend", ["ref", "ring", "kernel"])
@pytest.mark.parametrize("edit_frac", [0.02, 0.04])
def test_scores_match_gotoh(backend, edit_frac):
    P, plen, T, tlen = _pairs(edit_frac)
    eng = AlignmentEngine(PAPER, backend=backend, edit_frac=edit_frac,
                          chunk_pairs=PAIRS)
    res = eng.align_packed(P, plen, T, tlen)
    exe = _only_exe(eng)
    assert (exe.score_scale, exe.run_pen) == (2, GapAffine(2, 3, 1))
    assert res.s_max == exe.s_max == PAPER.score_bound(
        128, edit_frac, len_diff=math.ceil(edit_frac * 128))
    np.testing.assert_array_equal(res.scores, _gotoh(P, plen, T, tlen))


@pytest.mark.parametrize("backend", ["ref", "ring", "kernel"])
def test_cigars_are_the_unreduced_backends(backend):
    """Each CIGAR rescores under 4/6/2 to the returned (Gotoh) score, and
    is op for op the one a direct trace call under 4/6/2 decodes to."""
    P, plen, T, tlen = _pairs(0.04)
    eng = AlignmentEngine(PAPER, backend=backend, edit_frac=0.04,
                          chunk_pairs=PAIRS)
    res = eng.align_packed(P, plen, T, tlen, output="cigar")
    exe = _only_exe(eng)
    assert exe.score_scale == 2
    want = _gotoh(P, plen, T, tlen)
    np.testing.assert_array_equal(res.scores, want)
    direct = get_backend(backend).variant("cigar", "affine")(
        P, T, plen, tlen, pen=PAPER, s_max=exe.s_max, k_max=exe.k_max)
    np.testing.assert_array_equal(np.asarray(direct.score), want)
    ops = cigar_mod.traceback_result(direct, PAPER, pattern=P, text=T,
                                     plen=plen, tlen=tlen, k_max=exe.k_max)
    for i in range(PAIRS):
        cost, ci, cj, ok = score_cigar(res.cigars[i], P[i, :plen[i]],
                                       T[i, :tlen[i]], PAPER.as_penalties())
        assert ok and (cost, ci, cj) == (want[i], plen[i], tlen[i]), i
        np.testing.assert_array_equal(res.cigars[i], ops[i])


# pruning tight enough to change some of the sample's scores
HEURISTICS = {"adaptive": AdaptiveBand(min_wf_len=1, max_distance_diff=1),
              "zdrop": ZDrop(zdrop=2)}


@pytest.mark.parametrize("band_cap", [None, "auto"])
@pytest.mark.parametrize("heur", sorted(HEURISTICS))
@pytest.mark.parametrize("backend", ["ring", "kernel"])
def test_pruned_scores_are_the_unreduced_backends(backend, heur, band_cap):
    """Heuristics prune in bases and diagonals: on the empty steps the
    reduction skips there is nothing to prune, so pruned scores (and the
    compacting band's windows) are those of the unreduced model."""
    h = HEURISTICS[heur]
    P, plen, T, tlen = _pairs(0.15)
    eng = AlignmentEngine(PAPER, backend=backend, edit_frac=0.15,
                          chunk_pairs=PAIRS, heuristic=h,
                          backend_opts={"band_cap": band_cap})
    res = eng.align_packed(P, plen, T, tlen)
    exe = _only_exe(eng)
    assert exe.score_scale == 2 and res.approximate
    cap = None if band_cap is None else h.band_cap(2 * exe.k_max + 1)
    direct = get_backend(backend).fn(P, T, plen, tlen, pen=PAPER,
                                     s_max=exe.s_max, k_max=exe.k_max,
                                     heur=h, band_cap=cap)
    np.testing.assert_array_equal(res.scores, np.asarray(direct.score))
    assert (res.scores != _gotoh(P, plen, T, tlen)).any()


def test_bidir_cigars_are_unchanged(unreduced, monkeypatch):
    """A BiWFA ticket's score wave runs reduced, its breakpoint waves at
    the caller's units; its CIGARs are those of the unreduced engine."""
    P, plen, T, tlen = _pairs(0.15, n=16)   # costs past the base case
    want = _gotoh(P, plen, T, tlen)

    def run():
        eng = AlignmentEngine(PAPER, backend="ring", edit_frac=0.15,
                              trace_budget=1500)
        res = eng.align_packed(P, plen, T, tlen, output="cigar",
                               trace_variant="bidir")
        np.testing.assert_array_equal(res.scores, want)
        scales = {}
        for key, exe in eng._cache.items():
            scales.setdefault(key[7], set()).add(exe.score_scale)
        return res, scales

    base, base_scales = run()                 # the reduction switched off
    assert base_scales["bidir_meet"] == {1}
    monkeypatch.undo()
    res, scales = run()
    assert scales["score"] == {2}             # the top-level score wave
    assert scales["bidir_meet"] == {1}        # the recursion's meet waves
    assert res.stats.n_bidir_fallback == base.stats.n_bidir_fallback == 0
    for a, b in zip(res.cigars, base.cigars):
        np.testing.assert_array_equal(a, b)


def _pair_of_score(target, rng, length=100):
    """A pattern and a text whose 4/6/2 cost is ``target`` (even, >= 8):
    spaced mismatches (4 each) and, where ``target % 4 == 2``, one
    two-base deletion (10)."""
    p = rng.choice(list("ACGT"), size=length)
    t = list(p)
    n_sub = target // 4 if target % 4 == 0 else (target - 10) // 4
    for j in range(n_sub):
        i = 5 + 5 * j
        t[i] = {"A": "C", "C": "G", "G": "T", "T": "A"}[t[i]]
    if target % 4:
        del t[length - 6:length - 4]
    return "".join(p), "".join(t)


def test_same_pairs_go_to_recovery():
    """The pass-1 bound s_max keeps its meaning: a pair at the largest
    cost within it resolves in pass 1, one just above comes back
    unresolved and goes through the exact-bound recovery."""
    eng = AlignmentEngine(PAPER, backend="ring", edit_frac=0.04)
    s1, _ = eng._bounds_for_bucket(128, np.array([100]), np.array([100]),
                                   exact=False)
    lo = s1 - s1 % 2                          # 4/6/2 costs are even
    hi = lo + 2
    rng = np.random.default_rng(7)
    pats, txts = zip(*(_pair_of_score(c, rng) for c in (lo, hi)))
    assert list(_gotoh(*_packed(pats, txts))) == [lo, hi]
    res = eng.align(list(pats), list(txts))
    assert list(res.scores) == [lo, hi]
    assert res.stats.n_overflow == 1 and res.stats.n_recovered == 1
    assert [b.recovery for b in res.stats.buckets] == [False, True]
    assert res.s_max > s1                     # the recovery pass's bound
    assert {e.score_scale for e in eng._cache.values()} == {2}


def _packed(pats, txts):
    from repro.core.engine import pack_batch
    P, plen = pack_batch(pats)
    T, tlen = pack_batch(txts)
    return P, plen, T, tlen


# --- the mechanism and its bypass ------------------------------------------


def test_kernel_loop_counters_fall(unreduced, monkeypatch):
    """16,384 pairs at E = 4%: the kernel's own counters per pair fall
    from 2.936 to 1.530 score steps and from 6.534 to 5.128 extend
    trips.  Per 8-pair block the 4/6/2 loop runs 2n - 1 rows where 2/3/1
    runs n, and each row it adds is one empty trip."""
    n = 16384
    P, plen, T, tlen = _pairs(0.04, n=n)

    def counts():
        names = ("kernel_pairs_total", "kernel_score_steps_total",
                 "kernel_extend_trips_total", session_mod.GCD_PAIRS)
        before = [_counter(c) for c in names]
        eng = AlignmentEngine(PAPER, backend="kernel", edit_frac=0.04,
                              chunk_pairs=n)
        res = eng.align_packed(P, plen, T, tlen)
        assert (res.scores >= 0).all()
        return [_counter(c) - b for c, b in zip(names, before)]

    pairs, steps_0, trips_0, gcd_0 = counts()        # the reduction off
    monkeypatch.undo()
    assert counts()[0] == pairs == n
    _, steps, trips, gcd = counts()
    blocks = n // 8
    assert (gcd_0, gcd) == (0, n)
    assert steps_0 == 2 * steps - blocks
    assert trips_0 == trips + steps - blocks
    assert steps_0 / n == pytest.approx(2.936, abs=5e-4)
    assert trips_0 / n == pytest.approx(6.534, abs=5e-4)
    assert steps / n == pytest.approx(1.530, abs=5e-4)
    assert trips / n == pytest.approx(5.128, abs=5e-4)


@pytest.mark.parametrize("model,engaged", [
    (PAPER, True), (GapLinear(4, 2), True), (Edit(), False),
    (GapAffine(5, 3, 2), False), (GapLinear(3, 2), False)],
    ids=lambda v: str(v))
def test_gcd_counter_counts_the_reduced_waves(model, engaged):
    P, plen, T, tlen = _pairs(0.02, n=48)
    names = ("kernel_pairs_total", session_mod.GCD_PAIRS)
    before = [_counter(c) for c in names]
    eng = AlignmentEngine(model, backend="ring", edit_frac=0.02)
    res = eng.align_packed(P, plen, T, tlen)
    np.testing.assert_array_equal(res.scores, _gotoh(P, plen, T, tlen,
                                                     model))
    pairs, gcd = (_counter(c) - b for c, b in zip(names, before))
    assert pairs == 48 and gcd == (48 if engaged else 0)


@pytest.mark.parametrize("model", [PAPER, Edit(), GapAffine(5, 3, 2),
                                   GapLinear(3, 2), GapLinear(4, 2)],
                         ids=lambda v: str(v))
def test_backend_is_called_with_the_reduced_model_only_where_g_exceeds_1(
        model):
    seen = []

    def probe(pattern, text, plen, tlen, *, pen, s_max, k_max):
        seen.append((pen, s_max, k_max))
        return wf.wfa_scores(pattern, text, plen, tlen, pen=pen,
                             s_max=s_max, k_max=k_max)

    register_backend("gcd-probe", probe, models=ALL_MODELS)
    try:
        eng = AlignmentEngine(model, backend="gcd-probe", s_max=40, k_max=9)
        eng.align(["ACGTACGTAC"], ["ACGAACGTC"])
        exe = _only_exe(eng)
    finally:
        unregister_backend("gcd-probe")
    run_pen, g = model.reduced()
    assert (exe.run_pen, exe.score_scale) == (run_pen, g)
    assert (exe.s_max, exe.k_max) == (40, 9)
    if g == 1:
        assert run_pen is model
        assert seen == [(model, 40, 9)]
    else:
        assert seen == [(run_pen, 40 // g, 9)]


def test_meet_waves_children_and_tiny_bounds_run_the_callers_model():
    eng = AlignmentEngine(PAPER, backend="ring")
    shape = (8, 32)
    cases = {"score": {}, "bidir_meet": {"output": "bidir_meet"},
             "child": {"output": "cigar", "states": ("I", "M")},
             "tiny": {"s_max": 1}}
    for name, kw in cases.items():
        kw = {"s_max": 20, **kw}
        exe, _ = eng._executable_for(shape, shape, kw.pop("s_max"), 8, **kw)
        want = (GapAffine(2, 3, 1), 2) if name == "score" else (PAPER, 1)
        assert (exe.run_pen, exe.score_scale) == want, name


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path.insert(0, "chipbench")
import traffic
from repro.core.engine import AlignmentEngine
from repro.core.scoring import GapAffine

out = {}
for e in (0.02, 0.04):
    P, plen, T, tlen = traffic.generate_pairs(%(pairs)d, 100, e, 0.6, 0.2,
                                              %(seed)d)
    eng = AlignmentEngine(GapAffine(4, 6, 2), backend="shardmap",
                          edit_frac=e, chunk_pairs=%(pairs)d)
    res = eng.align_packed(P, plen, T, tlen, output="cigar")
    (exe,) = eng._cache.values()
    out[str(e)] = {"workers": eng.n_workers, "scale": exe.score_scale,
                   "scores": res.scores.tolist(),
                   "cigars": [c.tolist() for c in res.cigars]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_FLIGHTREC_DIR"] = str(tmp_path_factory.mktemp("flightrec"))
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"pairs": PAIRS, "seed": SEED}],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("edit_frac", [0.02, 0.04])
def test_shardmap_on_four_devices_matches_gotoh(sharded, edit_frac):
    got = sharded[str(edit_frac)]
    assert got["workers"] == 4 and got["scale"] == 2
    P, plen, T, tlen = _pairs(edit_frac)
    want = _gotoh(P, plen, T, tlen)
    assert got["scores"] == want.tolist()
    for i in range(PAIRS):
        cost, ci, cj, ok = score_cigar(np.asarray(got["cigars"][i]),
                                       P[i, :plen[i]], T[i, :tlen[i]],
                                       PAPER.as_penalties())
        assert ok and (cost, ci, cj) == (want[i], plen[i], tlen[i]), i
