"""repro.obs: tracer thread-safety and disabled-mode cost, flow IDs
linking submit -> retire across out-of-order tickets, bounded-memory
histogram accuracy, Prometheus exposition, ServerStats/scrape percentile
parity, EngineStats.merge, and the overhead-gate CI wiring."""
import glob
import json
import os
import threading

import jax
import numpy as np
import pytest
from conftest import random_pairs as _random_pairs

from repro import obs
from repro.core.engine import AlignmentEngine, BucketInfo, EngineStats
from repro.core.session import WAVE_SPANS
from repro.data.reads import ArrivalSpec, generate_trace
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace
from repro.serve import ServeLoop, replay_trace


@pytest.fixture
def tracer():
    """Enabled tracer with a clean buffer; always disabled afterwards so
    test order can't leak trace state into other modules."""
    was_on = obs_trace.enabled()
    obs_trace.reset()
    obs_trace.enable()
    yield obs_trace
    (obs_trace.enable if was_on else obs_trace.disable)()
    obs_trace.reset()


# ------------------------------------------------------------ tracer ----


def test_disabled_mode_emits_nothing_and_allocates_nothing():
    obs_trace.disable()
    obs_trace.reset()
    # tracer, flight recorder and profile all off
    assert not obs_profile.active() and obs_trace._rec is None
    assert not obs_trace.recording()
    # the disabled span is THE shared singleton: no per-call allocation
    assert obs_trace.span("x") is obs_trace.NULL
    assert obs_trace.span("y", cat="c", args={"k": 1}) is obs_trace.NULL
    with obs_trace.span("z") as sp:
        sp.set(a=1).flow_start(7)
        sp.flow_step(7)
        sp.flow_end(7)
    obs_trace.instant("i", args={"k": 2})
    obs_trace.counter("c", 3)
    assert obs_trace.events() == []
    # the profile flag alone makes spans real (each carries a profiler
    # annotation), and still nothing reaches the tracer's buffer
    obs_profile._active = True
    try:
        assert obs_trace.recording()
        with obs_trace.span("p", args={"k": 3}) as sp:
            assert sp is not obs_trace.NULL
    finally:
        obs_profile._active = False
    assert obs_trace.span("x") is obs_trace.NULL
    assert obs_trace.events() == []


def test_concurrent_spans_produce_valid_ordered_trace(tracer, tmp_path):
    """>= 8 threads emitting nested spans -> loadable Chrome trace JSON
    with per-thread lanes and consistent, monotone timestamps."""
    n_threads, n_spans = 8, 40
    barrier = threading.Barrier(n_threads)

    def work(k):
        barrier.wait()
        for i in range(n_spans):
            with tracer.span(f"outer{k}", cat="test", args={"i": i}):
                with tracer.span(f"inner{k}", cat="test"):
                    pass
            tracer.instant(f"tick{k}", cat="test")

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    path = tracer.save(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    ev = doc["traceEvents"]
    assert ev[0]["ph"] == "M"                      # process_name metadata
    xs = [e for e in ev if e["ph"] == "X"]
    assert len(xs) == n_threads * n_spans * 2
    assert len([e for e in ev if e["ph"] == "i"]) == n_threads * n_spans
    assert len({e["tid"] for e in xs}) == n_threads
    by_tid = {}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        by_tid.setdefault(e["tid"], []).append(e)
    for lane in by_tid.values():
        # one thread's spans exit sequentially: buffer order == time order
        ends = [e["ts"] + e["dur"] for e in lane]
        assert all(a <= b + 1e-6 for a, b in zip(ends, ends[1:]))


def test_flow_ids_connect_submit_to_retire_out_of_order(tracer, rng):
    """Each ticket's self-allocated flow threads submit -> scatter ->
    kernel -> gather -> done even when waves retire out of order."""
    eng = AlignmentEngine(backend="ring", edit_frac=0.05, chunk_pairs=8)
    chunks = [_random_pairs(rng, 8, lo=5, hi=150) for _ in range(3)]
    with eng.stream(max_inflight_waves=2) as sess:
        tickets = [sess.submit(p, t) for p, t in chunks]
        for tk in tickets:
            tk.result()
    ev = tracer.events()
    names = {e["name"] for e in ev if e["ph"] == "X"}
    for expected in ("session.submit", "wave.pack", "wave.dispatch",
                     "wave.wait", "wave.gather", "session.ticket_done"):
        assert expected in names, f"missing span {expected}"
    flows = {}
    for e in ev:
        if e["ph"] in ("s", "t", "f"):
            flows.setdefault(e["id"], []).append(e)
    assert len(flows) == len(tickets)   # one self-allocated flow each
    for fid, chain in flows.items():
        phs = [e["ph"] for e in chain]
        assert phs[0] == "s" and phs[-1] == "f", fid
        assert phs.count("s") == 1 and phs.count("f") == 1
        assert "t" in phs                         # >= 1 wave step between
        ts = [e["ts"] for e in chain]
        assert ts[0] <= min(ts) and ts[-1] >= max(ts) - 1e-6


# ------------------------------------------------- profiler annotations ----


def _profiled_wave_spans(outdir):
    """[(name, start_ns, end_ns, stats)] of the ``wave.*`` events on the
    host plane of the profile written under ``outdir``, read with
    ``ProfileData`` as the benchmark's trace reduction reads them, and
    whether any python-tracer event (``$``-named) is there."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    spans, python_events = [], False
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                python_events |= ev.name.startswith("$")
                if ev.name.startswith("wave."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return sorted(spans, key=lambda sp: sp[1]), python_events


def _one_wave_tickets(eng, chunks):
    with eng.stream(max_inflight_waves=2) as sess:
        for p, t in chunks:
            sess.submit(p, t)
        for tk in sess.as_completed():
            tk.result()
    return sess.stats


def test_session_spans_land_in_the_profiler_trace(tmp_path, rng):
    """With the tracer off and ``obs.profile._active`` set over a
    ``jax.profiler`` capture (as the benchmark's traced window does), each
    wave puts one pack, dispatch, wait and gather span into the host
    plane, pack ending before dispatch starts, each carrying its ticket;
    a ``wave.compile`` sits inside the dispatch of each cache miss only."""
    obs_trace.disable()
    eng = AlignmentEngine(backend="ring", edit_frac=0.1, chunk_pairs=8)
    chunks = [_random_pairs(rng, 8, lo=40, hi=60) for _ in range(3)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    prev, obs_profile._active = obs_profile._active, True
    try:
        stats = _one_wave_tickets(eng, chunks)
    finally:
        obs_profile._active = prev
        jax.profiler.stop_trace()
    spans, _ = _profiled_wave_spans(str(tmp_path))
    assert {n for n, *_ in spans} <= set(WAVE_SPANS)
    count = {n: sum(1 for m, *_ in spans if m == n) for n in WAVE_SPANS}
    assert stats.n_waves >= 3
    for name in ("wave.pack", "wave.dispatch", "wave.wait", "wave.gather"):
        assert count[name] == stats.n_waves, name
    assert count["wave.traceback"] == 0              # scores only
    assert all("ticket" in st and "rows" in st for *_, st in spans)
    assert {st["ticket"] for *_, st in spans} == {0, 1, 2}
    packs = [sp for sp in spans if sp[0] == "wave.pack"]
    dispatches = [sp for sp in spans if sp[0] == "wave.dispatch"]
    for pack, disp in zip(packs, dispatches):
        assert pack[3]["ticket"] == disp[3]["ticket"]
        assert pack[2] <= disp[1]                   # pack ends, then dispatch
    compiles = [sp for sp in spans if sp[0] == "wave.compile"]
    assert len(compiles) == stats.cache_misses >= 1
    for c in compiles:
        assert any(d[1] <= c[1] and c[2] <= d[2]
                   and d[3]["ticket"] == c[3]["ticket"] for d in dispatches)


def test_profile_block_has_spans_but_no_python_tracer(tmp_path, rng):
    """``obs.profile.profile(dir)`` (the launchers' ``--profile DIR``)
    starts the capture with the python tracer off and the spans on; a
    warm engine's waves open no ``wave.compile``."""
    obs_trace.disable()
    eng = AlignmentEngine(backend="ring", edit_frac=0.1, chunk_pairs=8)
    chunks = [_random_pairs(rng, 8, lo=40, hi=60) for _ in range(2)]
    _one_wave_tickets(eng, chunks)                  # warm the cache
    with obs_profile.profile(str(tmp_path)):
        assert obs_profile.active()
        stats = _one_wave_tickets(eng, chunks)
    assert not obs_profile.active()
    spans, python_events = _profiled_wave_spans(str(tmp_path))
    assert not python_events
    assert stats.cache_misses == 0
    names = [n for n, *_ in spans]
    assert "wave.compile" not in names
    assert names.count("wave.wait") == stats.n_waves >= 2


def test_capture_trace_writes_and_restores(tracer, tmp_path):
    obs_trace.disable()
    path = tmp_path / "cap.json"
    with obs.capture_trace(str(path)):
        assert obs_trace.enabled()
        with obs_trace.span("inside"):
            pass
    assert not obs_trace.enabled()        # switch restored (was off)
    names = {e["name"] for e in json.load(open(path))["traceEvents"]}
    assert "inside" in names
    with obs.capture_trace(None):         # no-op path
        assert not obs_trace.enabled()


# ----------------------------------------------------------- metrics ----


def test_histogram_quantiles_within_one_bucket_of_exact(rng):
    h = obs_metrics.Histogram("lat", "test")
    samples = np.exp(rng.normal(-5.0, 1.5, size=2000))   # ~ms latencies
    for v in samples:
        h.observe(float(v))
    assert h.count == 2000
    assert h.sum == pytest.approx(samples.sum())
    assert h.max == samples.max()
    s = np.sort(samples)
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = s[int(np.ceil(q * len(s))) - 1]
        got = h.quantile(q)
        assert exact <= got <= exact * h.factor, q


def test_histogram_memory_is_bounded():
    h = obs_metrics.Histogram("lat", "test")
    before = h.nbytes()
    for i in range(10_000):
        h.observe(1e-6 * (i + 1))         # spans below-lo .. above cases
    h.observe(1e9)                        # saturates the top bucket
    assert h.nbytes() == before           # the bounded-memory contract
    assert h.n_buckets == len(h.counts())
    assert sum(h.counts()) == h.count == 10_001
    assert h.counts()[-1] == 1            # saturated into the top bucket
    # the saturated sample reports the top edge (clamped by max): no
    # sample is dropped, only its magnitude saturates
    assert h.quantile(1.0) == min(h.bucket_edge(h.n_buckets - 1), h.max)
    assert h.max == 1e9


def test_registry_get_or_create_attach_and_prometheus():
    reg = obs_metrics.Registry()
    c = reg.counter("hits_total", "help text")
    c.inc()
    c.inc(2)
    assert reg.counter("hits_total") is c and c.value == 3
    g = reg.gauge("depth")
    g.set(5)
    g.dec()
    assert g.value == 4
    with pytest.raises(TypeError):
        reg.gauge("hits_total")           # name/type conflicts are loud
    h = reg.histogram("lat_seconds")
    for v in (0.001, 0.002, 0.004, 0.2):
        h.observe(v)
    text = reg.render_prometheus()
    assert "# HELP hits_total help text" in text
    assert "# TYPE hits_total counter" in text
    assert "hits_total 3" in text
    assert "depth 4" in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text
    assert "lat_seconds_p99" in text
    # attach() replaces: a fresh per-instance histogram wins the name
    h2 = obs_metrics.Histogram("lat_seconds", "newest server")
    reg.attach(h2)
    assert reg.get("lat_seconds") is h2


def test_registry_snapshot_jsonl_roundtrip(tmp_path):
    reg = obs_metrics.Registry()
    reg.counter("a_total").inc(7)
    reg.histogram("h").observe(0.5)
    path = str(tmp_path / "metrics.jsonl")
    reg.write_jsonl(path)
    reg.counter("a_total").inc()
    reg.write_jsonl(path)
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2
    assert lines[0]["metrics"]["a_total"]["value"] == 7
    assert lines[1]["metrics"]["a_total"]["value"] == 8
    assert lines[1]["metrics"]["h"]["count"] == 1
    assert lines[1]["metrics"]["h"]["p50"] == pytest.approx(0.5, rel=0.2)


# ----------------------------------------------- serving integration ----


def test_serverstats_percentiles_match_prometheus_scrape(rng):
    """ServerStats and the /metrics exposition read the SAME histogram:
    identical p50/p99, and the memory stays bounded for a long run."""
    eng = AlignmentEngine(backend="ring", edit_frac=0.02)
    payloads, _ = generate_trace(ArrivalSpec(
        n_requests=150, pairs_per_request=1, read_len=30, seed=9))
    with ServeLoop(eng, wave_pairs=64, form_deadline=0.005) as server:
        nbytes0 = server._latency_hist.nbytes()
        replay_trace(server, payloads, np.zeros(150))
        st = server.stats()
    assert st.n_latency_samples == 150
    # bounded memory: 150 (or 150M) samples, same bucket array
    assert server._latency_hist.nbytes() == nbytes0
    scrape = {}
    for line in obs_metrics.render_prometheus().splitlines():
        if line.startswith("serve_request_latency_seconds_p"):
            k, v = line.split()
            scrape[k] = float(v)
    # %g exposition keeps 6 significant digits of the identical value
    assert scrape["serve_request_latency_seconds_p50"] \
        == pytest.approx(st.latency_p50, rel=1e-5)
    assert scrape["serve_request_latency_seconds_p99"] \
        == pytest.approx(st.latency_p99, rel=1e-5)


# -------------------------------------------------- EngineStats.merge ----


def test_engine_stats_merge_sums_and_maxes():
    a = EngineStats(n_pairs=10, n_workers=2, cache_hits=3, t_kernel=1.0,
                    rows_real=10, peak_trace_bytes=100,
                    buckets=[BucketInfo(64, 4, 8, 20)])
    b = EngineStats(n_pairs=5, n_workers=4, cache_hits=2, t_kernel=0.5,
                    rows_real=5, peak_trace_bytes=300,
                    buckets=[BucketInfo(128, 2, 4, 30)])
    out = a.merge(b)
    assert out is a                        # in-place, returns self
    assert a.n_pairs == 15 and a.cache_hits == 5 and a.rows_real == 15
    assert a.t_kernel == pytest.approx(1.5)
    assert a.n_workers == 4 and a.peak_trace_bytes == 300
    assert len(a.buckets) == 2
    # child tickets re-process parent-counted pairs: n_pairs untouched
    c = EngineStats(n_pairs=99, cache_misses=1)
    a.merge(c, count_pairs=False)
    assert a.n_pairs == 15 and a.cache_misses == 1


# ----------------------------------------------------- CI gate wiring ----


def test_obs_overhead_gate_detects_each_regression():
    """check() trips on disabled-path bloat and enabled-mode slowdowns,
    passes a healthy snapshot, and never passes on missing rows."""
    from benchmarks import obs_overhead

    def rows(frac=0.001, ratio=0.99):
        return [("obs/disabled_frac", frac, ""),
                ("obs/on_ratio", ratio, "")]

    assert obs_overhead.check(rows()) == []
    assert len(obs_overhead.check(rows(frac=0.05))) == 1
    assert len(obs_overhead.check(rows(ratio=0.5))) == 1
    assert len(obs_overhead.check(rows(0.5, 0.5))) == 2
    assert len(obs_overhead.check(rows(frac=float("nan")))) == 1
    with pytest.raises(KeyError):
        obs_overhead.check([])
