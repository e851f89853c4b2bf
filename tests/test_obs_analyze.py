"""repro.obs.analyze + repro.obs.record: phase accounting on a
hand-built synthetic trace (known durations, one deliberate bubble, one
cross-thread flow), critical paths, pipeline occupancy, trace/snapshot
diff attribution, flight-recorder ring/dump semantics (shed + timeout
hooks), nesting-safe capture_trace, and the obs_report CLI."""
import json

import numpy as np
import pytest

from repro import obs
from repro.core.engine import AlignmentEngine
from repro.core.session import AlignmentSession, run_streamed
from repro.data.reads import ReadPairSpec, generate_pairs
from repro.launch import obs_report
from repro.obs import analyze
from repro.obs import record as obs_record
from repro.obs import trace as obs_trace
from repro.serve import ServeLoop


# ------------------------------------------------------ synthetic trace ----
# Two waves with exact durations, one deliberate 20.5ms bubble between
# them, and one cross-thread flow (submit on tid 2 -> kernel/gather on
# tid 1).  All times in microseconds.


def _x(name, ts, dur, tid=1, args=None):
    return {"name": name, "cat": "wave", "ph": "X", "ts": ts, "dur": dur,
            "pid": 1, "tid": tid, "args": args or {}}


def _c(name, ts, value):
    return {"name": name, "cat": "repro", "ph": "C", "ts": ts, "pid": 1,
            "tid": 0, "args": {"value": value}}


def _f(ph, fid, ts, tid):
    ev = {"name": "flow", "cat": "flow", "ph": ph, "id": fid, "ts": ts,
          "pid": 1, "tid": tid}
    if ph == "f":
        ev["bp"] = "e"
    return ev


SYNTHETIC = [
    _x("session.submit", 0, 1_000, tid=2),
    _x("wave.pack", 0, 6_000, args={"wave": 0}),
    _x("wave.dispatch", 6_000, 4_000, args={"wave": 0}),
    _x("wave.wait", 10_000, 20_000, args={"wave": 0, "rows": 256}),
    _x("wave.gather", 30_000, 5_000, args={"wave": 0}),
    _x("wave.traceback", 35_000, 2_000, args={"wave": 0}),
    # deliberate bubble: nothing in flight 40_000 .. 60_500
    _x("wave.pack", 60_000, 2_500, args={"wave": 1}),
    _x("wave.dispatch", 62_500, 1_500, args={"wave": 1}),
    _x("wave.wait", 64_000, 6_000, args={"wave": 1, "rows": 64}),
    _x("wave.gather", 70_000, 1_000, args={"wave": 1}),
    _c("inflight_waves", 500, 1),
    _c("inflight_waves", 40_000, 0),
    _c("inflight_waves", 60_500, 1),
    _c("inflight_waves", 71_000, 0),
    # one cross-thread flow: submit (tid 2) -> kernel -> gather (tid 1)
    _f("s", 7, 500, tid=2),
    _f("t", 7, 11_000, tid=1),
    _f("f", 7, 30_500, tid=1),
]


@pytest.fixture
def synth():
    return analyze.Trace.from_events(SYNTHETIC)


def test_phase_accounting_exact_totals(synth):
    pt = analyze.phase_accounting(synth)
    assert pt.get("scatter").total_us == pytest.approx(14_000)
    assert pt.get("kernel").total_us == pytest.approx(26_000)
    assert pt.get("kernel").count == 2
    assert pt.get("kernel").mean_us == pytest.approx(13_000)
    assert pt.get("kernel").max_us == pytest.approx(20_000)
    assert pt.get("gather").total_us == pytest.approx(6_000)
    assert pt.get("traceback").total_us == pytest.approx(2_000)
    assert pt.accounted_us == pytest.approx(48_000)
    assert pt.share("kernel") == pytest.approx(26_000 / 48_000)
    # session.submit is not a wave phase: never in the table
    assert sum(s.total_us for s in pt.stats.values()) == \
        pytest.approx(48_000)
    assert not pt.is_empty()
    rows = pt.as_rows()
    names = [n for n, _, _ in rows]
    assert "phase/kernel_s" in names and "phase/scatter_share" in names
    vals = dict((n, v) for n, v, _ in rows)
    assert vals["phase/kernel_s"] == pytest.approx(26_000 / 1e6)
    # empty trace -> empty table (the CI smoke assertion path)
    assert analyze.phase_accounting(
        analyze.Trace.from_events([])).is_empty()


def test_pipeline_finds_the_deliberate_bubble(synth):
    rep = analyze.pipeline_analysis(synth)
    assert len(rep.bubbles) == 1
    assert rep.bubbles[0].ts == pytest.approx(40_000)
    assert rep.bubbles[0].dur_us == pytest.approx(20_500)
    assert rep.busy_us == pytest.approx(50_000)
    assert rep.span_us == pytest.approx(70_500)
    assert rep.occupancy == pytest.approx(50_000 / 70_500)
    assert rep.mean_inflight == pytest.approx(50_000 / 70_500)
    # host spans: [0,10k] [30k,37k] [60k,64k] [70k,71k] = 22ms, of which
    # 21ms overlaps the busy intervals ([500,40k] and [60.5k,71k])
    assert rep.host_busy_us == pytest.approx(22_000)
    assert rep.host_overlap_us == pytest.approx(21_000)
    assert rep.host_overlap_frac == pytest.approx(21_000 / 22_000)


def test_pipeline_falls_back_to_kernel_spans_without_counter():
    ev = [e for e in SYNTHETIC if e["ph"] != "C"]
    rep = analyze.pipeline_analysis(analyze.Trace.from_events(ev))
    # busy = union of kernel spans: [10k,30k] + [64k,70k]
    assert rep.busy_us == pytest.approx(26_000)
    assert len(rep.bubbles) == 1
    assert rep.bubbles[0].dur_us == pytest.approx(34_000)


def test_cross_thread_critical_path(synth):
    paths = analyze.critical_paths(synth)
    assert len(paths) == 1
    p = paths[0]
    assert p.id == 7
    assert [s.name for s in p.segments] == \
        ["session.submit", "wave.wait", "wave.gather"]
    assert {s.tid for s in p.segments} == {1, 2}    # crosses threads
    # kernel waited 9ms after submit ended (1_000 -> 10_000)
    assert p.segments[1].wait_us == pytest.approx(9_000)
    assert p.segments[2].wait_us == pytest.approx(0)
    assert p.latency_us == pytest.approx(35_000)    # 0 -> gather end
    assert p.busy_us == pytest.approx(26_000)
    assert p.wait_us == pytest.approx(9_000)


def test_slow_waves_orders_by_duration(synth):
    waves = analyze.slow_waves(synth, k=2)
    assert [w.dur for w in waves] == [20_000, 6_000]
    assert analyze.slow_waves(synth, k=1)[0].args["rows"] == 256


def test_diff_attributes_regression_to_suite_and_phase():
    a = {"serving/p99_ms": 10.0, "serving/pairs_per_s": 1000.0,
         "obs/on_ratio": 0.97, "phase/kernel_s": 1.0}
    b = dict(a, **{"phase/kernel_s": 3.0, "serving/p99_ms": 10.5})
    deltas = analyze.diff_rows(a, b)
    worst = deltas[0]
    assert (worst.suite, worst.phase) == ("phase", "kernel_s")
    assert worst.ratio == pytest.approx(3.0)
    # unchanged rows sort last
    assert deltas[-1].ratio == pytest.approx(1.0)
    # phase-table diff names the mover too
    ta = analyze.phase_accounting(analyze.Trace.from_events(SYNTHETIC))
    slowed = [dict(e, dur=e["dur"] * (4 if e["name"] == "wave.gather"
                                      else 1)) if e["ph"] == "X" else e
              for e in SYNTHETIC]
    tb = analyze.phase_accounting(analyze.Trace.from_events(slowed))
    pd = analyze.diff_phase_tables(ta, tb)
    assert pd[0].phase == "gather"
    assert pd[0].ratio == pytest.approx(4.0)


def test_trace_file_roundtrip(tmp_path, synth):
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": SYNTHETIC,
                                "displayTimeUnit": "ms"}))
    t2 = analyze.Trace.from_file(str(path))
    assert len(t2.spans) == len(synth.spans)
    assert analyze.phase_accounting(t2).accounted_us == \
        pytest.approx(48_000)
    # bare-list form loads too
    (tmp_path / "bare.json").write_text(json.dumps(SYNTHETIC))
    assert len(analyze.Trace.from_file(
        str(tmp_path / "bare.json")).flows) == 3


# --------------------------------------------------------- flight rec ----


@pytest.fixture
def flightrec(tmp_path):
    """Explicit recorder dumping into tmp with no cooldown; always torn
    down so the NULL-span disabled contract holds for other modules."""
    was_on = obs_trace.enabled()
    obs_trace.disable()
    rec = obs_record.enable(capacity=64, out_dir=str(tmp_path),
                            min_interval_s=0.0)
    yield rec
    obs_record.disable()
    (obs_trace.enable if was_on else obs_trace.disable)()
    obs_trace.reset()


def test_ring_is_bounded_and_tracer_stays_empty(flightrec):
    assert not obs_trace.enabled()
    for i in range(200):
        with obs_trace.span("w", args={"i": i}):
            pass
    assert obs_trace.events() == []          # full tracer still off
    assert len(flightrec) == 64              # ring kept only the newest
    assert flightrec.events()[-1]["args"]["i"] == 199


def test_dump_writes_postmortem_and_rate_limits(flightrec, tmp_path):
    with obs_trace.span("before_failure"):
        pass
    path = flightrec.dump("unit_test", {"k": 1})
    assert path is not None
    doc = json.load(open(path))
    assert doc["flightrec"]["reason"] == "unit_test"
    assert doc["flightrec"]["args"] == {"k": 1}
    names = [e["name"] for e in doc["traceEvents"]]
    assert "before_failure" in names
    assert any(n.startswith("flightrec.dump:") for n in names)
    assert "metrics" in doc
    # cooldown: min_interval_s=0 always dumps; a long interval suppresses
    flightrec.min_interval_s = 3600.0
    assert flightrec.dump("unit_test") is None
    assert flightrec.dump("other_reason") is not None   # per-reason


def test_module_dump_is_noop_when_inactive():
    assert obs_record.active() is None
    assert obs_record.dump("nothing") is None
    # and the disabled-mode zero-allocation contract holds
    obs_trace.disable()
    assert obs_trace.span("x") is obs_trace.NULL


def test_serveloop_dumps_on_shed(flightrec, tmp_path, rng):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05, chunk_pairs=8)
    P, plen, T, tlen = generate_pairs(ReadPairSpec(
        n_pairs=8, read_len=40, edit_frac=0.02, seed=3))
    loop = ServeLoop(eng, wave_pairs=8, form_deadline=0.01,
                     max_queue_depth=4)
    loop.start()
    loop.submit_packed(P, plen, T, tlen).result(timeout=30)
    loop.stop()
    # the queue is closed now: this offer is shed deterministically
    fut = loop.submit_packed(P, plen, T, tlen)
    with pytest.raises(Exception):
        fut.result(timeout=5)
    dumps = list(tmp_path.glob("flightrec_shed_*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["flightrec"]["reason"] == "shed"
    assert doc["flightrec"]["args"]["n_pairs"] == 8


def test_session_dumps_on_as_completed_timeout(flightrec, tmp_path,
                                               monkeypatch, rng):
    monkeypatch.setattr(AlignmentSession, "_wave_ready",
                        staticmethod(lambda w: False))
    eng = AlignmentEngine(backend="ring", edit_frac=0.05, chunk_pairs=8)
    P, plen, T, tlen = generate_pairs(ReadPairSpec(
        n_pairs=8, read_len=40, edit_frac=0.02, seed=4))
    with pytest.raises(TimeoutError):
        with eng.stream(max_inflight_waves=2) as sess:
            sess.submit_packed(P, plen, T, tlen)
            for _ in sess.as_completed(timeout=0.05):
                pass
    dumps = list(tmp_path.glob("flightrec_as_completed_timeout_*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert "detail" in doc["flightrec"]["args"]


# ------------------------------------------------- capture nesting ----


def test_capture_trace_is_nesting_safe(tmp_path):
    was_on = obs_trace.enabled()
    obs_trace.disable()
    obs_trace.reset()
    outer, inner = tmp_path / "outer.json", tmp_path / "inner.json"
    try:
        with obs.capture_trace(str(outer)):
            with obs_trace.span("before"):
                pass
            with obs.capture_trace(str(inner)):
                with obs_trace.span("inside"):
                    pass
            # the inner exit must NOT clobber the outer capture
            assert obs_trace.enabled()
            with obs_trace.span("after"):
                pass
        assert not obs_trace.enabled()
        names = {e["name"]
                 for e in json.load(open(outer))["traceEvents"]}
        assert {"before", "inside", "after"} <= names
    finally:
        (obs_trace.enable if was_on else obs_trace.disable)()
        obs_trace.reset()


def test_isolated_restores_outer_timeline():
    was_on = obs_trace.enabled()
    obs_trace.reset()
    obs_trace.enable()
    try:
        with obs_trace.span("outer_kept"):
            pass
        with obs_trace.isolated():
            obs_trace.disable()
            obs_trace.enable()
            with obs_trace.span("dropped"):
                pass
            assert {e["name"] for e in obs_trace.events()} == {"dropped"}
        assert obs_trace.enabled()              # switch restored
        names = {e["name"] for e in obs_trace.events()}
        assert names == {"outer_kept"}          # inner events dropped
    finally:
        (obs_trace.enable if was_on else obs_trace.disable)()
        obs_trace.reset()


# ------------------------------------------------------ live agreement ----


def test_live_phase_sums_agree_with_session_stats(tmp_path):
    """Acceptance: analyzer phase sums over a live streamed capture match
    the SessionStats wall-time accounting within 5%."""
    spec = ReadPairSpec(n_pairs=512, read_len=100, edit_frac=0.02, seed=7)
    P, plen, T, tlen = generate_pairs(spec)
    eng = AlignmentEngine(backend="ring", edit_frac=0.02)
    run_streamed(eng, P, plen, T, tlen, submit_pairs=128)   # warm cache
    was_on = obs_trace.enabled()
    obs_trace.reset()
    path = tmp_path / "live.json"
    try:
        with obs.capture_trace(str(path)):
            _, _, st, _ = run_streamed(eng, P, plen, T, tlen,
                                       submit_pairs=128)
    finally:
        (obs_trace.enable if was_on else obs_trace.disable)()
        obs_trace.reset()
    pt = analyze.phase_accounting(analyze.Trace.from_file(str(path)))
    tol = dict(rel=0.05, abs=2e-3)
    assert pt.total_s("scatter") == pytest.approx(st.t_scatter, **tol)
    assert pt.total_s("kernel") == pytest.approx(st.t_kernel, **tol)
    # traceback time is folded into t_gather by the session accounting
    assert pt.total_s("gather") + pt.total_s("traceback") == \
        pytest.approx(st.t_gather, **tol)
    assert not analyze.critical_paths(
        analyze.Trace.from_file(str(path))) == []


# ------------------------------------------------------------- CLI ----


def test_obs_report_cli_phase_table(tmp_path, capsys):
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": SYNTHETIC}))
    assert obs_report.main([str(path), "--assert-phases"]) == 0
    out = capsys.readouterr().out
    assert "phase table" in out
    assert "kernel (DPU)" in out                # paper mapping shown
    assert "bubbles: 1" in out
    assert "critical paths (1 flows)" in out


def test_obs_report_assert_phases_fails_on_empty(tmp_path, capsys):
    path = tmp_path / "empty.json"
    path.write_text(json.dumps({"traceEvents": []}))
    assert obs_report.main([str(path)]) == 0            # report-only: ok
    assert obs_report.main([str(path), "--assert-phases"]) == 1


def _bench_snapshot(path, kernel_s):
    rows = [{"name": "serving/p99_ms", "us_per_call": 10.0, "derived": ""},
            {"name": "phase/kernel_s", "us_per_call": kernel_s,
             "derived": ""}]
    path.write_text(json.dumps({"rows": rows}))


def test_obs_report_diff_names_suite_and_phase(tmp_path, capsys):
    a, b = tmp_path / "BENCH_a.json", tmp_path / "BENCH_b.json"
    _bench_snapshot(a, 1.0)
    _bench_snapshot(b, 3.0)
    assert obs_report.main(["--diff", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "biggest mover: suite=phase phase=kernel_s" in out


def test_snapshot_diff_helper_compares_two_newest(tmp_path, capsys):
    from benchmarks.common import snapshot_diff
    _bench_snapshot(tmp_path / "BENCH_1.json", 1.0)
    assert snapshot_diff(str(tmp_path / "BENCH_*.json")) == []  # need 2
    _bench_snapshot(tmp_path / "BENCH_2.json", 2.0)
    lines = snapshot_diff(str(tmp_path / "BENCH_*.json"))
    assert any("suite=phase phase=kernel_s" in ln for ln in lines)
