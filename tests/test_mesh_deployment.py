"""The four-chip deployment on four forced CPU devices: the ``shardmap``
backend with no mesh given spans every device, runs the interpreted
Pallas kernel per shard, and gives the Gotoh oracle's scores and CIGARs
of the same cost; its per-shard counters add up to the one-device
kernel's; the benchmark's four-chip cell runs correct and its control
fails.

JAX fixes its device count when it starts, so one subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` does the work and
prints what it found; the tests below check it.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.gotoh import gotoh_score_vec, score_cigar
from repro.core.scoring import GapAffine, GapLinear
from repro.data.reads import ReadPairSpec, generate_pairs

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
PAIRS = 64
MODELS = {"affine": GapAffine(4, 6, 2), "linear": GapLinear(4, 2)}
CASES = [(e, m) for e in (0.02, 0.04) for m in MODELS]
SEED = 2147483911

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, time
import jax
import numpy as np
sys.path.insert(0, "chipbench")
import harness
from repro.core import session
from repro.core.engine import AlignmentEngine
from repro.core.scoring import GapAffine, GapLinear, ZDrop
from repro.data.reads import ReadPairSpec, generate_pairs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

PAIRS, SEED = %(pairs)d, %(seed)d
MODELS = {"affine": GapAffine(4, 6, 2), "linear": GapLinear(4, 2)}
out = {"devices": jax.device_count()}

def pairs(edit_frac):
    return generate_pairs(ReadPairSpec(n_pairs=PAIRS, read_len=100,
                                       edit_frac=edit_frac, seed=SEED))

# scores and CIGARs, E = 2%% and 4%%, affine and linear
for e in (0.02, 0.04):
    P, plen, T, tlen = pairs(e)
    for name, pen in MODELS.items():
        eng = AlignmentEngine(pen, backend="shardmap", edit_frac=e,
                              chunk_pairs=PAIRS)
        res = eng.align_packed(P, plen, T, tlen, output="cigar")
        out[f"{e}-{name}"] = {
            "mesh": dict(eng.mesh.shape), "workers": eng.n_workers,
            "scores": res.scores.tolist(),
            "cigars": [c.tolist() for c in res.cigars]}

# one wave: per-shard counters against the one-device kernel's
P, plen, T, tlen = pairs(0.02)
pen = MODELS["affine"]
obs_metrics.REGISTRY._metrics.clear()
obs_trace.reset()
obs_trace.enable()
eng = AlignmentEngine(pen, backend="shardmap", edit_frac=0.04,
                      chunk_pairs=PAIRS)
with eng.stream() as sess:
    sharded = sess.submit_packed(P, plen, T, tlen).result()
    n_waves = sess.stats.n_waves
obs_trace.disable()
events = obs_trace.events()
reg = {n: obs_metrics.REGISTRY.get(n).value
       for n in session.SHARD_COUNTERS}
(exe,) = eng._cache.values()
per_shard = exe.call(*eng._device_put(P, T, plen, tlen))
one = AlignmentEngine(pen, backend="kernel", edit_frac=0.04,
                      chunk_pairs=PAIRS).align_packed(P, plen, T, tlen)
spans = lambda name: [[ev["tid"], ev["ts"], ev["ts"] + ev["dur"]]
                      for ev in events if ev.get("name") == name]
out["wave"] = {
    "waves": n_waves, "steps": int(sharded.n_steps),
    "trips": int(sharded.stats.n_ext_trips),
    "shard_steps": np.asarray(per_shard.n_steps).tolist(),
    "shard_trips": np.asarray(per_shard.n_ext_trips).tolist(),
    "kernel_steps": int(one.n_steps),
    "kernel_trips": int(one.stats.n_ext_trips),
    "registry": reg, "put": spans("wave.put"),
    "dispatch": spans("wave.dispatch")}

# the benchmark's four-chip cell, small, with the kernel interpreted
cell = harness.load_cell("wfa100.e2.score.x4")
cell.config["wave_pairs"] = PAIRS
cell.per_layer = []
def run(pool_pairs, seconds, **kw):
    cell.traffic["pool_pairs"] = pool_pairs
    return harness.run_cell(cell, SEED, seconds, False,
                            t_proc=time.perf_counter(), require_tpu=False,
                            **kw)["line"]
out["cell"] = run(4 * PAIRS, 0.6)
out["control"] = run(16 * PAIRS, 1.0, heuristic=ZDrop(zdrop=1))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def found(tmp_path_factory):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_FLIGHTREC_DIR"] = str(tmp_path_factory.mktemp("flightrec"))
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"pairs": PAIRS, "seed": SEED}],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("edit_frac,model", CASES,
                         ids=[f"e{round(e * 100)}-{m}" for e, m in CASES])
def test_sharded_kernel_matches_gotoh(found, edit_frac, model):
    """No mesh given: the engine spans all four devices; every score is
    Gotoh's and every CIGAR rescores to it over both whole sequences."""
    got = found[f"{edit_frac}-{model}"]
    assert found["devices"] == 4
    assert got["mesh"] == {"pairs": 4} and got["workers"] == 4
    pen = MODELS[model].as_penalties()
    P, plen, T, tlen = generate_pairs(ReadPairSpec(
        n_pairs=PAIRS, read_len=100, edit_frac=edit_frac, seed=SEED))
    for i in range(PAIRS):
        p, t = P[i, :plen[i]], T[i, :tlen[i]]
        want = gotoh_score_vec(p, t, pen)
        assert got["scores"][i] == want, i
        cost, ci, cj, ok = score_cigar(np.asarray(got["cigars"][i]), p, t,
                                       pen)
        assert ok and (cost, ci, cj) == (want, plen[i], tlen[i]), i


def test_shard_counters_add_up_to_the_one_device_kernel(found):
    """One wave over four shards: each shard returns its own loop
    counters, their sums are what one device's kernel counts on the same
    pairs, and the session adds the slowest shard's and the mean trips."""
    w = found["wave"]
    assert w["waves"] == 1
    assert len(w["shard_steps"]) == len(w["shard_trips"]) == 4
    assert sum(w["shard_steps"]) == w["steps"] == w["kernel_steps"] > 0
    assert sum(w["shard_trips"]) == w["trips"] == w["kernel_trips"] > 0
    reg = w["registry"]
    assert reg["kernel_shard_trips_max_total"] == max(w["shard_trips"])
    assert reg["kernel_shard_trips_mean_total"] == pytest.approx(
        np.mean(w["shard_trips"]))
    assert (reg["kernel_shard_trips_max_total"]
            >= reg["kernel_shard_trips_mean_total"])


def test_put_span_nests_inside_the_dispatch(found):
    w = found["wave"]
    assert len(w["put"]) == w["waves"]
    for tid, lo, hi in w["put"]:
        assert any(t == tid and a <= lo and hi <= b
                   for t, a, b in w["dispatch"])


def test_four_chip_cell_is_correct_and_its_control_fails(found):
    cell, control = found["cell"], found["control"]
    assert cell["correct"] and cell["failed"] == 0 and cell["attempted"] > 0
    assert cell["checks"]["missing_pairs"]["value"] == 0
    assert not control["correct"]
    assert control["checks"]["wrong_scores"]["value"] > 0
