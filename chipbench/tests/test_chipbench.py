"""CPU tests of the chip benchmark's own parts; none needs a chip.

The harness runs here with the Pallas kernel interpreted and at tiny
sizes: enough to drive every path, never a measurement.
"""
from __future__ import annotations

import json
import math
import pathlib
import re
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
import tracered  # noqa: E402
import work  # noqa: E402

PEN = (4, 6, 2)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# -- plain oracles of the tests' own ------------------------------------------

def levenshtein(a, b) -> int:
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def gotoh(a, b, x, o, e) -> int:
    inf = 1 << 30
    n, m = len(a), len(b)
    H = [[inf] * (m + 1) for _ in range(n + 1)]
    I = [[inf] * (m + 1) for _ in range(n + 1)]
    D = [[inf] * (m + 1) for _ in range(n + 1)]
    H[0][0] = 0
    for j in range(1, m + 1):
        H[0][j] = I[0][j] = o + j * e
    for i in range(1, n + 1):
        H[i][0] = D[i][0] = o + i * e
        for j in range(1, m + 1):
            I[i][j] = min(H[i][j - 1] + o + e, I[i][j - 1] + e)
            D[i][j] = min(H[i - 1][j] + o + e, D[i - 1][j] + e)
            H[i][j] = min(H[i - 1][j - 1] + (x if a[i - 1] != b[j - 1]
                                             else 0), I[i][j], D[i][j])
    return H[n][m]


def plain_wfa(p, t, x, o, e):
    """Gap-affine WFA, dict per wavefront -> (score, [M, I, D] cells
    visited inside the matrix, bases matched by extension)."""
    plen, tlen = len(p), len(t)
    neg = -(1 << 30)
    matched = [0]

    def extend(k, h):
        v = h - k
        while 0 <= v < plen and 0 <= h < tlen and p[v] == t[h]:
            h += 1
            v += 1
            matched[0] += 1
        return h

    def valid(k, h):
        return h >= 0 and 0 <= h - k <= plen and h <= tlen

    M = {0: {0: extend(0, 0)}}
    I, D = {}, {}
    visited = [1, 0, 0]
    s = 0
    while M.get(s, {}).get(tlen - plen, neg) < tlen:
        s += 1
        mo, ie, de, mx = (M.get(s - o - e), I.get(s - e), D.get(s - e),
                          M.get(s - x))

        def span(*fronts):
            ks = [k for f in fronts if f for k in f]
            return (min(ks), max(ks)) if ks else None

        ri = span(mo, ie)
        rd = span(mo, de)
        cur_i, cur_d, cur_m = {}, {}, {}
        if ri:
            for k in range(ri[0] + 1, ri[1] + 2):
                h = max((mo or {}).get(k - 1, neg),
                        (ie or {}).get(k - 1, neg)) + 1
                cur_i[k] = h if valid(k, h) else neg
        if rd:
            for k in range(rd[0] - 1, rd[1]):
                h = max((mo or {}).get(k + 1, neg),
                        (de or {}).get(k + 1, neg))
                cur_d[k] = h if valid(k, h) else neg
        rm = span(mx, cur_i, cur_d)
        if rm:
            for k in range(rm[0], rm[1] + 1):
                h = max((mx or {}).get(k, neg) + 1, cur_i.get(k, neg),
                        cur_d.get(k, neg))
                cur_m[k] = extend(k, h) if valid(k, h) else neg
        for c, front in enumerate((cur_m, cur_i, cur_d)):
            visited[c] += sum(1 for k in front if -plen <= k <= tlen)
        if cur_m:
            M[s] = cur_m
        if cur_i:
            I[s] = cur_i
        if cur_d:
            D[s] = cur_d
    return s, visited, matched[0]


# -- the generator ------------------------------------------------------------

def test_generator_bounds_and_determinism():
    L, E = 100, 0.04
    n_err = math.ceil(E * L)
    seed = 2 ** 31 + 12345
    P, plen, T, tlen = traffic.generate_pairs(300, L, E, 0.6, 0.2, seed)
    P2, _, T2, tlen2 = traffic.generate_pairs(300, L, E, 0.6, 0.2, seed)
    assert (P == P2).all() and (T == T2).all() and (tlen == tlen2).all()
    assert P.shape == T.shape == (300, L + n_err)
    assert (plen == L).all() and (np.abs(tlen - L) <= n_err).all()
    acgt = set(b"ACGT")
    for i in range(300):
        a, b = P[i, :plen[i]], T[i, :tlen[i]]
        assert set(a.tolist()) <= acgt and set(b.tolist()) <= acgt
        assert not P[i, plen[i]:].any() and not T[i, tlen[i]:].any()
        assert levenshtein(a.tolist(), b.tolist()) <= n_err


@pytest.mark.parametrize("sub,ins", [(1.0, 0.0), (0.0, 1.0), (0.0, 0.0)])
def test_generator_edit_mix(sub, ins):
    """Pure mixes: substitutions keep the length, insertions add bases,
    deletions remove them, each 0..ceil(E*L) times, about uniformly."""
    L, E, n = 100, 0.04, 4000
    n_err = math.ceil(E * L)
    P, plen, T, tlen = traffic.generate_pairs(n, L, E, sub, ins, seed=3)
    d = tlen.astype(int) - L
    if sub == 1.0:
        assert (d == 0).all()
        ham = (P[:, :L] != T[:, :L]).sum(axis=1)
        assert ham.max() <= n_err and ham.min() == 0
    else:
        sign = 1 if ins == 1.0 else -1
        counts = np.bincount(sign * d, minlength=n_err + 1)
        assert len(counts) == n_err + 1
        assert np.allclose(counts / n, 1 / (n_err + 1), atol=0.03)


def test_generator_default_mix_balances_indels():
    _, _, _, tlen = traffic.generate_pairs(20000, 100, 0.04, 0.6, 0.2, 5)
    d = tlen.astype(int) - 100
    # E[d] = 0 with insertions as likely as deletions; P(d == 0) is set
    # by the substitution share
    assert abs(d.mean()) < 0.05
    assert 0.45 < (d == 0).mean() < 0.6


# -- the reference ------------------------------------------------------------

def test_reference_matches_plain_gotoh():
    rng = np.random.default_rng(0)
    P, plen, T, tlen = traffic.generate_pairs(60, 40, 0.1, 0.6, 0.2, 1)
    plen = plen.copy()
    plen[:10] = rng.integers(0, 40, 10)          # ragged patterns too
    got = reference.gotoh_scores(P, plen, T, tlen, PEN)
    for i in range(60):
        assert got[i] == gotoh(P[i, :plen[i]].tolist(),
                               T[i, :tlen[i]].tolist(), *PEN)


# -- the work count -----------------------------------------------------------

def test_work_count_equals_plain_wfa():
    P, plen, T, tlen = traffic.generate_pairs(40, 30, 0.15, 0.5, 0.25, 2)
    ref = reference.gotoh_scores(P, plen, T, tlen, PEN)
    m, i, d = work.cells(plen, tlen, ref, PEN)
    ops = work.pair_ops(plen, tlen, ref, PEN)
    for r in range(40):
        s, visited, matched = plain_wfa(P[r, :plen[r]].tolist(),
                                        T[r, :tlen[r]].tolist(), *PEN)
        assert s == ref[r]
        assert [m[r], i[r], d[r]] == visited
        low = max(0, min(plen[r], tlen[r]) - s // min(PEN[0], PEN[2]))
        assert low <= matched
        assert ops[r] == 4 * m[r] + 2 * i[r] + d[r] + low


def test_work_bytes_and_bound():
    plen, tlen, score = np.array([100]), np.array([103]), np.array([20])
    assert work.pair_bytes(plen, tlen, "score")[0] == 215
    assert work.pair_bytes(plen, tlen, "cigar")[0] == 318
    peak = {"int32_ops_per_s": 1e12, "hbm_bytes_per_s": 1e15}
    t, bound = work.least_seconds(plen, tlen, score, PEN, "score", peak)
    assert bound == "ops" and t == work.pair_ops(plen, tlen, score,
                                                 PEN)[0] / 1e12
    t, bound = work.least_seconds(plen, tlen, score, PEN, "score",
                                  {"int32_ops_per_s": 1e18,
                                   "hbm_bytes_per_s": 1.0})
    assert bound == "bytes" and t == 215.0


# -- the trace reduction ------------------------------------------------------

def _synthetic():
    ms = 1e6
    return {"devices": {
        "/device:TPU:0": {
            "XLA Ops": [["%wfa_pallas.1 = (s32[8]) custom-call(...)",
                         10 * ms, 30 * ms],
                        ["%copy.3 = s32[8] copy(...)", 35 * ms, 10 * ms],
                        ["%wfa_pallas.1 = (s32[8]) custom-call(...)",
                         60 * ms, 30 * ms]],
            "XLA Modules": [["jit__run(1)", 10 * ms, 35 * ms],
                            ["jit__run(1)", 60 * ms, 30 * ms],
                            ["jit_other(2)", 95 * ms, 5 * ms]]},
        "/device:TPU:1": {
            "XLA Ops": [["%wfa_pallas.1 = x", 0 * ms, 50 * ms]],
            "XLA Modules": [["jit__run(1)", 0 * ms, 50 * ms]]}},
        "host": [["chipbench.window", 5 * ms, 95 * ms],
                 ["wfa.kernel.wait", 44 * ms, 20 * ms],
                 ["chipbench.submit", 90 * ms, 8 * ms]]}


def test_reduction_synthetic_exact():
    red = tracered.Reduction(_synthetic())
    assert red.window_s == pytest.approx(0.095)
    # device 0 busy: [10, 45] and [60, 90] = 65 ms; device 1: [5, 50]
    assert red.busy_s("/device:TPU:0") == pytest.approx(0.065)
    assert red.busy_s("/device:TPU:1") == pytest.approx(0.045)
    assert red.mean_busy_s == pytest.approx(0.055)
    assert red.idle_share == pytest.approx(1 - 0.055 / 0.095)
    assert red.module_seconds(harness.ALIGN_MODULE) == pytest.approx(
        0.035 + 0.030 + 0.045)
    ops = dict((n, s) for n, s in red.top_ops())
    assert ops == pytest.approx({"wfa_pallas": 0.105, "copy": 0.010})
    gaps = red.top_gaps()
    # device 1 [50, 100] under the window, device 0 [45, 60] under the
    # kernel wait, [90, 100] under the submit, [5, 10] under nothing else
    assert [n for n, _ in gaps] == ["chipbench.window", "wfa.kernel.wait",
                                   "chipbench.submit", "chipbench.window"]
    assert [s for _, s in gaps] == pytest.approx([0.05, 0.015, 0.01,
                                                  0.005])


def test_reduction_recorded_chip_trace():
    """A trace recorded on one TPU v5 lite (half a second of open-loop
    8-pair requests to a ``ServeLoop``), against a timeline of the tests'
    own."""
    trace = json.loads((HERE / "data" / "trace_svc100.json").read_text())
    red = tracered.Reduction(trace)
    dev = red.devices
    assert dev == ["/device:TPU:0"]
    # busy, from a 100 ns grid over the window
    res = 100.0
    grid = np.zeros(int(round((red.hi - red.lo) / res)) + 1, bool)
    for _, start, dur in trace["devices"][dev[0]]["XLA Ops"]:
        a = int(np.floor((max(start, red.lo) - red.lo) / res))
        b = int(np.ceil((min(start + dur, red.hi) - red.lo) / res))
        grid[max(a, 0):max(b, 0)] = True
    busy = red.busy_s(dev[0])
    assert busy == pytest.approx(grid.sum() * res * 1e-9, rel=1e-3)
    assert red.idle_share == pytest.approx(1 - busy / red.window_s)
    kernel = red.module_seconds(harness.ALIGN_MODULE)
    assert busy <= kernel <= red.window_s
    ops = red.top_ops()
    assert ops[0][0] == "wfa_pallas" and ops[0][1] <= busy
    expect = json.loads((HERE / "data" / "trace_svc100.expect.json")
                        .read_text())
    assert red.window_s == pytest.approx(expect["window_s"], rel=1e-12)
    assert busy == pytest.approx(expect["busy_s"], rel=1e-12)
    assert kernel == pytest.approx(expect["kernel_s"], rel=1e-12)
    for got, want in ((ops, expect["device_ops"]),
                      (red.top_gaps(), expect["idle_gaps"])):
        assert [n for n, _ in got] == [n for n, _ in want]
        assert [v for _, v in got] == pytest.approx([v for _, v in want])
    # the device waits on arrivals: the longest gaps lie under the load
    # generator's sleep or under nothing narrower than the window
    assert {n for n, _ in red.top_gaps()} <= {"chipbench.wait_arrival",
                                              "chipbench.window"}


# -- the cells ----------------------------------------------------------------

def test_every_cell_resolves_to_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    configs = {c["name"]: c for c in bench["configs"]}
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in metrics])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.per_layer:
            harness.metric_reader(m["name"])


# -- whole runs on the CPU ----------------------------------------------------

# every cell of the plan, by its files: BENCHMARK.json lists those proven
# on the chip, and the harness runs any of them the same way
PLAN = {"wfa100.e4.score": ("wfa-pim-100bp", "pool.e4.score", 1)}


def tiny(name: str, pool_pairs: int = 256) -> harness.Cell:
    """A cell of the plan at a size the interpreter runs in a second or
    two."""
    cfg, tr, chips = PLAN[name]
    config = json.loads((BENCH / "configs" / f"{cfg}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{tr}.json").read_text())
    config["wave_pairs"] = 32
    traffic["pool_pairs"] = pool_pairs
    e2e = [{"name": "pairs_per_s", "unit": "x"},
           {"name": "setup_s", "unit": "s"}]
    return harness.Cell(name, config, traffic, chips, e2e, [])


def test_plan_matches_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cfg, tr, chips = PLAN[w["name"]]
        assert (w["config"], w["traffic"], w["chips"]) == (cfg, tr, chips)
        assert files[cfg] == f"chipbench/configs/{cfg}.json"
    for cfg, tr, _ in PLAN.values():
        assert (BENCH / "configs" / f"{cfg}.json").is_file()
        assert (BENCH / "traffic" / f"{tr}.json").is_file()


def test_server_and_idle_readers():
    red = tracered.Reduction(_synthetic())
    read = harness.metric_reader
    assert read("device_idle_share.batch")({"reduction": red}) == (
        pytest.approx(100 * red.idle_share))


def run(cell, seed=7, seconds=0.6, **kw):
    return harness.run_cell(cell, seed, seconds, False,
                            t_proc=time.perf_counter(), require_tpu=False,
                            **kw)["line"]


CELLS = sorted(PLAN)


@pytest.fixture(autouse=True)
def _flightrec(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_FLIGHTREC_DIR", str(tmp_path))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    cell = tiny(name)
    line = run(cell)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    """The control: the program's own pruning heuristic switched on,
    which breaks the configuration's exactness."""
    from repro.core.scoring import ZDrop
    line = run(tiny(name, pool_pairs=1024), seconds=1.0,
               heuristic=ZDrop(zdrop=1))
    assert not line["correct"]
    assert line["checks"]["wrong_scores"]["value"] > 0


def _alter_answer(monkeypatch, what):
    from repro.core import session
    orig = session.AlignmentSession._finalize

    def finalize(self, ticket):
        n = ticket.n_pairs
        if what == "score":
            ticket._scores[0] += 2
        elif what == "half":
            ticket._scores[n // 2:] = -1
        return orig(self, ticket)

    monkeypatch.setattr(session.AlignmentSession, "_finalize", finalize)


@pytest.mark.parametrize("name,what", [
    ("wfa100.e4.score", "score"), ("wfa100.e4.score", "half")])
def test_planted_fault_is_caught(monkeypatch, name, what):
    _alter_answer(monkeypatch, what)
    line = run(tiny(name))
    assert not line["correct"] and line["failed"] > 0


# -- the batch window's clock -------------------------------------------------

class DrainingSession:
    """Takes each submit in ``SUBMIT`` seconds and answers none until the
    drain, which yields one ticket every ``STEP`` seconds."""
    SUBMIT, STEP = 0.02, 0.05

    def __init__(self):
        self.open = []

    def submit_packed(self, p, plen, t, tlen, **kw):
        time.sleep(self.SUBMIT)
        n = len(plen)
        res = type("R", (), {"scores": np.zeros(n, np.int32)})()
        tk = type("T", (), {"index": len(self.open),
                            "result": lambda self: res})()
        self.open.append(tk)
        return tk

    def poll(self):
        return []

    def as_completed(self, timeout=None):
        while self.open:
            time.sleep(self.STEP)
            yield self.open.pop(0)

    def close(self):
        pass


def test_batch_rate_counts_the_drain():
    """Tickets still in flight when the window closes count at the time
    the drain hands each back, so the rate's time runs to the last one."""
    sess = DrainingSession()
    engine = type("E", (), {"stream": lambda self: sess,
                            "cache_traces": lambda self: 0})()
    cell = tiny("wfa100.e4.score")
    pool = traffic.build_pool(cell.traffic, cell.config, 1)
    marks = harness.Marks(harness.CompileLog(), engine)
    seconds = 0.3
    run_ = harness.batch_window(engine, cell, pool, seconds, None, marks,
                                lambda: None, {}, False)
    n = len(run_.scores)
    assert n * 32 == run_.n_submitted and n >= 5
    took = run_.t_last - marks.t_start
    # every ticket of the window waited for the drain
    assert took >= seconds + n * DrainingSession.STEP
    assert marks.t_end - run_.t_last < DrainingSession.STEP


@pytest.mark.parametrize("name", ["kernel_us_per_pair", "wfa_roofline"])
def test_kernel_readers_refuse_a_missing_module(name):
    """Pairs came back but no device time lies under the executable's
    module name: a renamed module fails the run, not the metric."""
    red = tracered.Reduction(_synthetic())
    ctx = {"reduction": red, "align_module": r"^jit__renamed\b",
           "device": {"kind": "TPU v5 lite"}, "peaks": harness.load_peaks(),
           "log": [],
           "pairs": {"plen": np.array([100]), "tlen": np.array([100]),
                     "score": np.array([8]), "output": "score", "pen": PEN}}
    read = harness.metric_reader(name)
    with pytest.raises(RuntimeError, match="jit__renamed"):
        read(ctx)
    assert read(dict(ctx, align_module=harness.ALIGN_MODULE)) > 0

