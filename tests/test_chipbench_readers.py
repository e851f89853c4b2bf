"""The benchmark's readers of the program's own spans and counters
(``chipbench/metrics``): each on a synthetic trace or a reset registry,
with its refusal when pairs came back but its span or counter is
missing, and its silence on a program that predates them."""
from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(ROOT / "chipbench"), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import tracered  # noqa: E402

from repro.core import session  # noqa: E402
from repro.core.engine import AlignmentEngine  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402

MS = 1e6            # ns
PAIRS = {"score": np.zeros(64, np.int32)}

COUNTER_READERS = ["score_steps_per_pair", "extend_trips_per_pair",
                   "compile_s"]
SPAN_READERS = ["dispatch_us_per_pair", "retire_us_per_pair"]
# readers of names a program may predate while it declares others
MESH_READERS = ["shard_trip_skew", "put_us_per_pair"]


@pytest.fixture
def registry(monkeypatch):
    """The program's global registry, emptied for the test."""
    monkeypatch.setattr(obs_metrics.REGISTRY, "_metrics", {})
    return obs_metrics.REGISTRY


def _count(registry, **values):
    for name, v in values.items():
        registry.counter(name).inc(v)


def test_counter_readers_divide_the_program_counters(registry):
    _count(registry, kernel_pairs_total=64, kernel_score_steps_total=24,
           kernel_extend_trips_total=560, engine_compile_seconds_total=1.5)
    read = harness.metric_reader
    ctx = {"pairs": PAIRS}
    assert read("score_steps_per_pair")(ctx) == pytest.approx(24 / 64)
    assert read("extend_trips_per_pair")(ctx) == pytest.approx(560 / 64)
    assert read("compile_s")(ctx) == pytest.approx(1.5)
    # nothing came back: nothing to read
    for name in COUNTER_READERS:
        assert read(name)({"pairs": {"score": np.zeros(0)}}) is None


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_readers_refuse_an_empty_registry(registry, name):
    with pytest.raises(RuntimeError, match="registry"):
        harness.metric_reader(name)({"pairs": PAIRS})


def test_trip_reader_is_silent_for_a_backend_without_trips(registry):
    _count(registry, kernel_pairs_total=64, kernel_score_steps_total=24)
    assert harness.metric_reader("extend_trips_per_pair")(
        {"pairs": PAIRS}) is None


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_readers_refuse_a_renamed_counter(registry, monkeypatch,
                                                  name):
    _count(registry, kernel_pairs_total=64, kernel_score_steps_total=24,
           kernel_extend_trips_total=560, engine_compile_seconds_total=1.5)
    monkeypatch.setattr(session, "WAVE_COUNTERS", ("renamed_total",))
    with pytest.raises(RuntimeError):
        harness.metric_reader(name)({"pairs": PAIRS})


@pytest.mark.parametrize("name", COUNTER_READERS + SPAN_READERS
                         + MESH_READERS)
def test_readers_are_silent_on_a_program_that_predates_them(
        registry, monkeypatch, name):
    monkeypatch.delattr(session, "WAVE_COUNTERS")
    monkeypatch.delattr(session, "WAVE_SPANS")
    ctx = {"pairs": PAIRS, "reduction": tracered.Reduction(_trace([]))}
    assert harness.metric_reader(name)(ctx) is None


def test_counter_readers_read_a_kernel_session(registry):
    """The names the readers look up are the ones the kernel backend's
    session writes."""
    rng = np.random.default_rng(5)
    pats = ["".join(rng.choice(list("ACGT"), size=40)) for _ in range(16)]
    eng = AlignmentEngine(backend="kernel", edit_frac=0.1)
    res = eng.align(pats, pats)
    read = harness.metric_reader
    ctx = {"pairs": {"score": res.scores}}
    assert read("score_steps_per_pair")(ctx) == pytest.approx(
        res.n_steps / 16)
    assert read("extend_trips_per_pair")(ctx) == pytest.approx(
        res.stats.n_ext_trips / 16)
    assert read("compile_s")(ctx) > 0
    # one device: every counter but the per-shard ones, which waves split
    # over several shards write
    assert (set(session.WAVE_COUNTERS) - set(registry._metrics)
            == set(session.SHARD_COUNTERS))
    with pytest.raises(RuntimeError, match="kernel_shard_trips"):
        read("shard_trip_skew")(ctx)


# -- span readers -------------------------------------------------------------

def _trace(host):
    return {"devices": {"/device:TPU:0": {
        "XLA Ops": [["%wfa_pallas.1 = x", 10 * MS, 80 * MS]],
        "XLA Modules": [["jit__run(1)", 10 * MS, 80 * MS]]}},
        "host": [["chipbench.window", 5 * MS, 95 * MS]] + host}


HOST = [
    ["wave.pack", 0 * MS, 7 * MS],              # 2 ms inside the window
    ["wave.dispatch", 7 * MS, 3 * MS],
    ["wave.compile", 7.5 * MS, 2 * MS],         # inside the dispatch
    ["wave.wait", 20 * MS, 30 * MS],
    ["wave.gather", 50 * MS, 1 * MS],
    ["wave.pack", 60 * MS, 1 * MS],
    ["wave.dispatch", 61 * MS, 0.5 * MS],
    ["wave.gather", 99 * MS, 4 * MS],           # 1 ms inside the window
    ["wave.traceback", 80 * MS, 2 * MS],
]


def test_span_readers_sum_their_spans_inside_the_window():
    ctx = {"pairs": PAIRS, "reduction": tracered.Reduction(_trace(HOST))}
    read = harness.metric_reader
    # pack 2 + 1, dispatch 3 + 0.5 ms; the compile is inside a dispatch
    assert read("dispatch_us_per_pair")(ctx) == pytest.approx(
        6.5e3 / 64)
    # gather 1 + 1, traceback 2 ms
    assert read("retire_us_per_pair")(ctx) == pytest.approx(4e3 / 64)


@pytest.mark.parametrize("name,drop", [
    ("dispatch_us_per_pair", "wave.pack"),
    ("dispatch_us_per_pair", "wave.dispatch"),
    ("retire_us_per_pair", "wave.gather")])
def test_span_readers_refuse_a_missing_span(name, drop):
    host = [ev for ev in HOST if ev[0] != drop]
    ctx = {"pairs": PAIRS, "reduction": tracered.Reduction(_trace(host))}
    with pytest.raises(RuntimeError, match=drop):
        harness.metric_reader(name)(ctx)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_refuse_a_renamed_span(monkeypatch, name):
    monkeypatch.setattr(session, "WAVE_SPANS", ("wave.renamed",))
    ctx = {"pairs": PAIRS, "reduction": tracered.Reduction(_trace(HOST))}
    with pytest.raises(RuntimeError):
        harness.metric_reader(name)(ctx)


# -- readers of the four-chip deployment --------------------------------------

def test_shard_skew_reader_compares_the_slowest_shard_with_the_mean(
        registry):
    _count(registry, kernel_shard_trips_max_total=130,
           kernel_shard_trips_mean_total=104)
    read = harness.metric_reader("shard_trip_skew")
    assert read({"pairs": PAIRS}) == pytest.approx(25.0)
    assert read({"pairs": {"score": np.zeros(0)}}) is None


def test_shard_skew_reader_refuses_a_declared_counter_left_empty(registry):
    _count(registry, kernel_shard_trips_max_total=130)
    with pytest.raises(RuntimeError, match="kernel_shard_trips_mean"):
        harness.metric_reader("shard_trip_skew")({"pairs": PAIRS})


def test_put_reader_sums_its_spans_inside_the_window():
    host = HOST + [["wave.put", 4 * MS, 2 * MS],    # 1 ms inside the window
                   ["wave.put", 7.2 * MS, 0.2 * MS],
                   ["wave.put", 61.1 * MS, 0.3 * MS]]
    ctx = {"pairs": PAIRS, "reduction": tracered.Reduction(_trace(host))}
    read = harness.metric_reader("put_us_per_pair")
    assert read(ctx) == pytest.approx(1.5e3 / 64)
    # the dispatch reader still sums dispatches only, not the copy inside
    assert harness.metric_reader("dispatch_us_per_pair")(ctx) == (
        pytest.approx(6.5e3 / 64))


def test_put_reader_refuses_a_declared_span_left_out():
    ctx = {"pairs": PAIRS, "reduction": tracered.Reduction(_trace(HOST))}
    with pytest.raises(RuntimeError, match="wave.put"):
        harness.metric_reader("put_us_per_pair")(ctx)


@pytest.mark.parametrize("name", MESH_READERS)
def test_mesh_readers_are_silent_where_the_names_are_not_declared(
        registry, monkeypatch, name):
    """A program that declares its other spans and counters but predates
    these names (the parent of the four-chip cell) gives no reading."""
    _count(registry, kernel_shard_trips_max_total=130,
           kernel_shard_trips_mean_total=104)
    monkeypatch.setattr(session, "WAVE_COUNTERS", tuple(
        n for n in session.WAVE_COUNTERS if n not in session.SHARD_COUNTERS))
    monkeypatch.setattr(session, "WAVE_SPANS", tuple(
        n for n in session.WAVE_SPANS if n != "wave.put"))
    host = HOST + [["wave.put", 7.2 * MS, 0.2 * MS]]
    ctx = {"pairs": PAIRS, "reduction": tracered.Reduction(_trace(host))}
    assert harness.metric_reader(name)(ctx) is None
