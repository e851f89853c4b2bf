"""AlignmentSession: async submission, pipelined dispatch, out-of-order
gather — parity with the blocking path and the Gotoh oracle, backpressure,
recovery recycling, exception propagation, and zero-retrace steady state."""
import threading
import time

import numpy as np
import pytest
from conftest import gotoh_oracle as _oracle
from conftest import random_pairs as _random_pairs

from repro.core.backends import register_backend, unregister_backend
from repro.core.engine import AlignmentEngine
from repro.core.penalties import DEFAULT
from repro.core.session import AlignmentSession
from repro.core.wavefront import wfa_scores


# ------------------------------------------------------------- parity ----


def test_stream_matches_sync_and_oracle(rng):
    # mixed lengths -> multiple buckets -> out-of-order wave completion
    eng = AlignmentEngine(backend="ring", edit_frac=0.05, chunk_pairs=8)
    chunks = [_random_pairs(rng, 12, lo=5, hi=150) for _ in range(3)]
    sync = [eng.align(p, t) for p, t in chunks]

    with eng.stream(max_inflight_waves=2) as sess:
        tickets = [sess.submit(p, t) for p, t in chunks]
        for tk, sr, (p, t) in zip(tickets, sync, chunks):
            res = tk.result()
            np.testing.assert_array_equal(res.scores, sr.scores)
            np.testing.assert_array_equal(res.scores, _oracle(p, t))
    assert sess.stats.n_submits == 3
    assert sess.stats.n_pairs == 36


def test_out_of_order_gather_covers_all_tickets(rng):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05, chunk_pairs=8)
    chunks = [_random_pairs(rng, 8, lo=5, hi=120) for _ in range(4)]
    with eng.stream(max_inflight_waves=3) as sess:
        tickets = [sess.submit(p, t) for p, t in chunks]
        seen = []
        for tk in sess.as_completed():
            assert tk.done()
            seen.append(tk.index)
        assert sorted(seen) == [tk.index for tk in tickets]
    for tk, (p, t) in zip(tickets, chunks):
        np.testing.assert_array_equal(tk.result().scores, _oracle(p, t))


def test_results_iterates_in_submission_order(rng):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    chunks = [_random_pairs(rng, 6, lo=20, hi=60) for _ in range(3)]
    with eng.stream() as sess:
        for p, t in chunks:
            sess.submit(p, t)
        out = list(sess.results())
    assert len(out) == 3
    for res, (p, t) in zip(out, chunks):
        np.testing.assert_array_equal(res.scores, _oracle(p, t))


def test_stream_with_cigar(rng):
    from repro.core.gotoh import score_cigar
    pats, txts = _random_pairs(rng, 12, lo=5, hi=100)
    eng = AlignmentEngine(backend="ref", edit_frac=0.1, with_cigar=True)
    with eng.stream() as sess:
        res = sess.submit(pats, txts).result()
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts))
    for i, (p, t) in enumerate(zip(pats, txts)):
        cost, ci, cj, ok = score_cigar(
            res.cigars[i], np.frombuffer(p.encode(), np.uint8),
            np.frombuffer(t.encode(), np.uint8), DEFAULT)
        assert ok and cost == res.scores[i]
        assert ci == len(p) and cj == len(t)


# ------------------------------------------------------- backpressure ----


def test_backpressure_bounds_inflight_waves(rng):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05,
                          bucket_by_length=False)
    pats, txts = _random_pairs(rng, 64, lo=40, hi=60)
    with eng.stream(max_inflight_waves=2, wave_pairs=4) as sess:
        for lo in range(0, 64, 8):
            sess.submit(pats[lo:lo + 8], txts[lo:lo + 8])
        sess.drain()
    st = sess.stats
    assert st.n_waves >= 16                  # genuinely multi-wave
    assert st.peak_inflight <= 2             # the bound was respected
    assert st.peak_inflight == 2             # ... and the pipeline filled
    scores = np.concatenate([t.result().scores for t in sess.tickets])
    np.testing.assert_array_equal(scores, _oracle(pats, txts))


def test_invalid_session_params():
    eng = AlignmentEngine(backend="ring")
    with pytest.raises(ValueError, match="max_inflight_waves"):
        AlignmentSession(eng, max_inflight_waves=0)
    with pytest.raises(ValueError, match="wave_pairs"):
        AlignmentSession(eng, wave_pairs=0)


# ---------------------------------------------------- overflow recycle ----


def test_overflow_recycles_into_recovery_queue(rng):
    # divergent pairs overflow the E budget; the wave retires anyway and
    # the stragglers re-run with exact bounds before the ticket completes
    near_p, near_t = _random_pairs(rng, 6, lo=24, hi=32)
    pats = near_p + ["A" * 24, "G" * 18]
    txts = near_t + ["T" * 24, "C" * 21]
    eng = AlignmentEngine(backend="ring", edit_frac=0.02)
    with eng.stream(max_inflight_waves=2) as sess:
        res = sess.submit(pats, txts).result()
    assert res.stats.n_overflow >= 2
    assert res.stats.n_recovered == res.stats.n_overflow
    assert any(b.recovery for b in res.stats.buckets)
    assert (res.scores >= 0).all()
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts))
    # session-level aggregates match the single ticket
    assert sess.stats.n_overflow == res.stats.n_overflow
    assert sess.stats.n_recovered == res.stats.n_recovered


def test_adaptive_off_stream_leaves_overflow_unresolved():
    eng = AlignmentEngine(backend="ring", edit_frac=0.02, adaptive=False)
    with eng.stream() as sess:
        res = sess.submit(["A" * 40], ["T" * 40]).result()
    assert res.scores[0] == -1
    assert res.stats.n_overflow == 1
    assert res.stats.n_recovered == 0


# ------------------------------------------------- empty / duplicate ----


def test_empty_submit_completes_immediately():
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    with eng.stream() as sess:
        tk = sess.submit([], [])
        assert tk.done()
        res = tk.result()
    assert res.scores.shape == (0,)
    assert res.stats.n_pairs == 0


def test_duplicate_submits_are_independent(rng):
    pats, txts = _random_pairs(rng, 8, lo=20, hi=60)
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    with eng.stream(max_inflight_waves=2) as sess:
        t1 = sess.submit(pats, txts)
        t2 = sess.submit(pats, txts)
        r1, r2 = t1.result(), t2.result()
    assert t1 is not t2
    np.testing.assert_array_equal(r1.scores, r2.scores)
    np.testing.assert_array_equal(r1.scores, _oracle(pats, txts))


# ------------------------------------------------- failure semantics ----


def test_backend_runtime_failure_propagates(rng):
    import jax
    import jax.numpy as jnp

    def _boom(scores):
        raise RuntimeError("injected backend failure")

    @register_backend("boom")
    def _boom_backend(pattern, text, plen, tlen, *, pen, s_max, k_max):
        res = wfa_scores(pattern, text, plen, tlen, pen=pen, s_max=s_max,
                         k_max=k_max)
        score = jax.pure_callback(
            _boom, jax.ShapeDtypeStruct(res.score.shape, jnp.int32),
            res.score)
        return res._replace(score=score)

    try:
        pats, txts = _random_pairs(rng, 4, lo=20, hi=40)
        eng = AlignmentEngine(backend="boom", edit_frac=0.05)
        sess = eng.stream(max_inflight_waves=2)
        # the injected failure surfaces at submit (when the runtime raises
        # it while dispatching) or at drain (when it arrives with the
        # result); either way the session re-raises it
        with pytest.raises(Exception, match="injected backend failure"):
            sess.submit(pats, txts)
            sess.drain()
        # the session is poisoned: no further submissions accepted
        with pytest.raises(RuntimeError, match="session failed"):
            sess.submit(pats, txts)
    finally:
        unregister_backend("boom")


def test_submit_after_close_raises(rng):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    sess = eng.stream()
    sess.close()
    with pytest.raises(RuntimeError, match="closed"):
        sess.submit(["ACGT"], ["ACGT"])


# ------------------------------------------------- steady-state cache ----


def test_zero_retraces_across_multiwave_steady_state(rng):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05, chunk_pairs=8)
    chunks = [_random_pairs(rng, 16, lo=40, hi=120) for _ in range(3)]
    with eng.stream(max_inflight_waves=2) as sess:
        for p, t in chunks:
            sess.submit(p, t)
    warm = sess.stats
    assert warm.n_traces == warm.cache_misses > 0

    # steady state: same serving shapes, fresh session -> fully cached
    with eng.stream(max_inflight_waves=2) as sess2:
        for p, t in chunks:
            sess2.submit(p, t)
        for tk in sess2.as_completed():
            assert (tk.result().scores >= 0).all()
    assert sess2.stats.n_traces == 0
    assert sess2.stats.cache_misses == 0
    assert sess2.stats.cache_hits > 0
    assert sess2.stats.n_waves > 1           # genuinely multi-wave


def test_sync_align_is_session_backed(rng):
    # the blocking path routes through the same session machinery
    pats, txts = _random_pairs(rng, 10, lo=10, hi=80)
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    res = eng.align(pats, txts)
    np.testing.assert_array_equal(res.scores, _oracle(pats, txts))
    assert res.stats.n_pairs == 10


# ------------------------------------------------- dispatch hooks -------


def test_backend_dispatch_hook_routes_every_wave(rng):
    calls = []

    def _spy_dispatch(fn, *arrays):
        calls.append(arrays[0].shape)
        return fn(*arrays)

    register_backend(
        "spy",
        lambda pattern, text, plen, tlen, *, pen, s_max, k_max:
            wfa_scores(pattern, text, plen, tlen, pen=pen, s_max=s_max,
                       k_max=k_max),
        dispatch=_spy_dispatch)
    try:
        pats, txts = _random_pairs(rng, 12, lo=20, hi=40)
        eng = AlignmentEngine(backend="spy", edit_frac=0.05)
        with eng.stream(wave_pairs=4) as sess:
            res = sess.submit(pats, txts).result()
        assert len(calls) >= 3               # one hook call per wave
        np.testing.assert_array_equal(res.scores, _oracle(pats, txts))
    finally:
        unregister_backend("spy")


# ---------------------------------------------- poll / timeout probes ---


def test_poll_is_nonblocking_and_drains_backlog(rng, monkeypatch):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    pats, txts = _random_pairs(rng, 6, lo=20, hi=50)
    with eng.stream(max_inflight_waves=2) as sess:
        tk = sess.submit(pats, txts)
        # a "still running" wave (readiness probe forced False) must not
        # be gathered: poll returns nothing and never blocks
        monkeypatch.setattr(AlignmentSession, "_wave_ready",
                            staticmethod(lambda wave: False))
        assert sess.poll() == []
        assert not tk.done()
        monkeypatch.undo()
        deadline = time.monotonic() + 30
        done = []
        while not done and time.monotonic() < deadline:
            done = sess.poll()
        assert done == [tk] and tk.done()
        assert sess.poll() == []             # backlog yielded exactly once
    np.testing.assert_array_equal(tk.result().scores, _oracle(pats, txts))


def test_poll_flushes_recovery_stragglers(rng):
    # a lone over-budget pair must not wait for a full recovery wave:
    # poll() re-dispatches queued overflow as soon as the pipe is empty
    eng = AlignmentEngine(backend="ring", edit_frac=0.02)
    with eng.stream() as sess:
        tk = sess.submit(["A" * 40], ["T" * 40])
        deadline = time.monotonic() + 30
        while not tk.done() and time.monotonic() < deadline:
            sess.poll()
        assert tk.done()
    res = tk.result()
    assert res.stats.n_overflow == 1 and res.stats.n_recovered == 1
    np.testing.assert_array_equal(res.scores, _oracle(["A" * 40],
                                                      ["T" * 40]))


def test_as_completed_timeout_raises_with_diagnostics(rng, monkeypatch):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    pats, txts = _random_pairs(rng, 4, lo=20, hi=40)
    with eng.stream(max_inflight_waves=2) as sess:
        sess.submit(pats, txts)
        # freeze the pipeline: the wave never reports ready, so the
        # deadline must fire instead of blocking forever
        monkeypatch.setattr(AlignmentSession, "_wave_ready",
                            staticmethod(lambda wave: False))
        t0 = time.monotonic()
        with pytest.raises(TimeoutError,
                           match=r"wave\(s\) in flight .*ticket 0"):
            list(sess.as_completed(timeout=0.2))
        assert 0.1 < time.monotonic() - t0 < 10
        monkeypatch.undo()
        for tk in sess.as_completed(timeout=60):   # recovers after unfreeze
            np.testing.assert_array_equal(tk.result().scores,
                                          _oracle(pats, txts))


# ------------------------------------------------- thread safety --------


def test_concurrent_submit_and_result_from_two_threads(rng):
    """Two producer threads share one session (the repro.serve contract):
    every ticket resolves with oracle scores, stats account every pair."""
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    chunks = [_random_pairs(np.random.default_rng(i), 6, lo=20, hi=80)
              for i in range(8)]
    out = {}
    errors = []

    def _producer(which):
        try:
            for i in range(which, 8, 2):
                p, t = chunks[i]
                out[i] = sess.submit(p, t).result()
        except BaseException as e:              # noqa: BLE001
            errors.append(e)

    with eng.stream(max_inflight_waves=2) as sess:
        threads = [threading.Thread(target=_producer, args=(w,))
                   for w in (0, 1)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errors
    assert sorted(out) == list(range(8))
    for i, (p, t) in enumerate(chunks):
        np.testing.assert_array_equal(out[i].scores, _oracle(p, t))
    assert sess.stats.n_submits == 8
    assert sess.stats.n_pairs == 48


# ------------------------------------------- occupancy / padding stats --


def test_wave_occupancy_counters(rng):
    # 5 equal-length pairs quantize to a 6-row device batch (3/4 of the
    # next pow2): the padding is counted, not hidden
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    pats = ["ACGTACGTACGTACGTACGT"] * 5
    with eng.stream() as sess:
        res = sess.submit(pats, pats).result()
    st = res.stats
    assert st.rows_real == 5
    assert st.rows_padded == 6
    assert st.wave_occupancy == pytest.approx(5 / 6)
    assert st.padding_waste_frac == pytest.approx(1 / 6)
    # session aggregates match the single ticket
    assert sess.stats.rows_real == 5 and sess.stats.rows_padded == 6


def test_occupancy_is_one_for_full_quantized_waves(rng):
    eng = AlignmentEngine(backend="ring", edit_frac=0.05)
    pats = ["ACGT" * 8] * 8
    with eng.stream(wave_pairs=8) as sess:
        res = sess.submit(pats, pats).result()
    assert res.stats.rows_real == res.stats.rows_padded == 8
    assert res.stats.wave_occupancy == 1.0
    assert res.stats.padding_waste_frac == 0.0


# ------------------------------------------------- deprecated shims -----


def test_wfaligner_shim_warns_deprecation():
    from repro.core.aligner import WFAligner
    with pytest.warns(DeprecationWarning, match="AlignmentEngine"):
        WFAligner(backend="ring")


def test_pim_shim_warns_deprecation():
    import warnings
    from repro.core.aligner import WFAligner
    from repro.core.pim import PIMBatchAligner
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        al = WFAligner(backend="ring")
    with pytest.warns(DeprecationWarning, match="AlignmentSession"):
        PIMBatchAligner(al)


# ------------------------------------------------- wall-clock overlap ---


@pytest.mark.slow
def test_streamed_wall_clock_not_worse_than_sync():
    """Acceptance: streamed >= sync throughput on the paper workload
    (8192 pairs, 100bp, E=2%), identical scores."""
    import time
    from repro.configs import wfa_paper
    from repro.data.reads import ReadPairSpec, generate_pairs

    n, chunk = 8192, 512
    P, plen, T, tlen = generate_pairs(
        ReadPairSpec(n_pairs=n, read_len=100, edit_frac=0.02, seed=2))
    eng = AlignmentEngine(wfa_paper.pen, backend="ring", edit_frac=0.02,
                          chunk_pairs=chunk)
    eng.align_packed(P, plen, T, tlen)       # warm the executable cache

    def sync_once():
        t0 = time.perf_counter()
        res = eng.align_packed(P, plen, T, tlen)
        return res.scores, time.perf_counter() - t0

    def stream_once():
        out = np.empty((n,), np.int32)
        t0 = time.perf_counter()
        with eng.stream(max_inflight_waves=4) as sess:
            offs = {}
            for lo in range(0, n, chunk):
                tk = sess.submit_packed(P[lo:lo + chunk], plen[lo:lo + chunk],
                                        T[lo:lo + chunk], tlen[lo:lo + chunk])
                offs[tk.index] = lo
            for tk in sess.as_completed():
                offset = offs[tk.index]
                out[offset:offset + tk.n_pairs] = tk.result().scores
        return out, time.perf_counter() - t0

    # interleaved best-of-4 so drifting machine load hits both modes alike
    sync_scores = None
    t_sync = t_stream = float("inf")
    for _ in range(4):
        scores, t_s = sync_once()
        sync_scores = scores if sync_scores is None else sync_scores
        streamed, t_p = stream_once()
        np.testing.assert_array_equal(streamed, sync_scores)
        t_sync = min(t_sync, t_s)
        t_stream = min(t_stream, t_p)
    # identical hardware, identical work: pipelining must not cost wall
    # clock (generous scheduling-noise headroom for loaded 2-core CI boxes)
    assert t_stream <= t_sync * 1.25, (t_stream, t_sync)
