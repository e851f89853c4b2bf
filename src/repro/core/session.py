"""Streaming alignment sessions — async submission, pipelined dispatch,
out-of-order gather.

The paper's second headline number is the transfer gap: 4.87x speedup with
CPU<->DPU transfers vs 37.4x without (E=2%), closed on UPMEM by overlapping
parallel transfers with kernel execution.  The blocking ``align()`` path
cannot overlap anything: it packs, copies, runs and gathers one wave at a
time.  :class:`AlignmentSession` is the pipelined execution model behind
:meth:`AlignmentEngine.stream`:

* ``submit(patterns, texts) -> Ticket`` returns immediately.  Pairs are
  bucketed and cut into *waves* (``wave_pairs`` — the MRAM-capacity
  analogue); each wave is packed on the host and dispatched without
  blocking, so JAX async dispatch runs the device kernel of wave *N* while
  the host packs and enqueues wave *N+1* (double-buffered ``device_put``).
* at most ``max_inflight_waves`` waves are in flight — **backpressure**:
  when the pipeline is full, the oldest wave is retired (gathered) before
  the next is packed, bounding host and device memory.
* waves retire **out of order** across buckets and submissions; a
  :class:`Ticket` completes as soon as its own waves (and any recovery
  re-runs) have retired.  ``as_completed()`` yields tickets in completion
  order, ``results()`` in submission order, ``drain()`` flushes everything.
* pairs that overflow the optimistic ``edit_frac`` bound are **recycled
  into a recovery queue** instead of stalling their wave — they re-run with
  exact worst-case bounds when a full recovery wave accumulates or at
  drain, exactly like the engine's two-pass scheme (BIMSA's CPU recovery).
* each submit carries its own **output mode**: ``submit(..., output=
  "cigar")`` dispatches the backend's trace variant for that ticket's
  waves (packed backtrace on ``ring``/``kernel``/``shardmap``, full
  history on ``ref``), tracebacks run at retirement (host-side, under the
  in-flight kernels), and recovery re-runs go through the traced path too
  — so out-of-order gather and overflow recycling hand back full
  alignments, not just scores.

The sync ``engine.align()`` is itself one blocking pass through this class
(``max_inflight_waves=1`` + per-phase blocking for the Fig. 1 scatter /
kernel / gather decomposition), so there is a single execution path to
test, profile and extend.

Quickstart::

    eng = AlignmentEngine(backend="ring", edit_frac=0.02)
    with eng.stream(max_inflight_waves=2) as sess:
        tickets = [sess.submit(ps, ts) for ps, ts in chunks]
        for t in sess.as_completed():        # completion order
            consume(t.result().scores)
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Deque, Iterator, List, Optional, Sequence

import jax
import numpy as np

from repro.core import cigar as cigar_mod
from repro.core.engine import (COMPILE_SECONDS, AlignmentEngine, BucketInfo,
                               EngineResult, EngineStats, Seq, _fit_width,
                               _pad_rows, _quantize_rows, _round_up,
                               fetch_char_bits, pack_batch)
from repro.obs import metrics as obs_metrics
from repro.obs import record as obs_record
from repro.obs import trace as obs_trace

__all__ = ["AlignmentSession", "FETCH_PAIRS", "GCD_PAIRS",
           "SHARD_COUNTERS", "SessionStats", "Ticket", "WAVE_COUNTERS",
           "WAVE_SPANS", "run_streamed"]

# The spans each wave opens (pack, then dispatch with the host-to-device
# copy inside it, and a compile too on a cache miss; wait, gather and, for
# CIGARs, traceback at retirement) and the registry counters the wave path
# adds to.  Readers outside the package look these names up here, so a
# rename shows as a missing name rather than as an empty reading.
WAVE_SPANS = ("wave.pack", "wave.dispatch", "wave.put", "wave.compile",
              "wave.wait", "wave.gather", "wave.traceback")
# Waves split over more than one shard also add, per wave, the extend
# trips of their slowest shard and the mean over their shards.
SHARD_COUNTERS = ("kernel_shard_trips_max_total",
                  "kernel_shard_trips_mean_total")
WAVE_COUNTERS = ("kernel_pairs_total", "kernel_score_steps_total",
                 "kernel_extend_trips_total", COMPILE_SECONDS) + SHARD_COUNTERS
# Pairs retired from waves of a backend with a packed extend fetch, one
# counter per fetch width (characters compared per trip: 16 for ACGT, 4
# for other byte codes, 1 for wider codes), beside kernel_pairs_total.
FETCH_PAIRS = {c: f"kernel_pairs_chars_per_trip_{c}_total"
               for c in (16, 4, 1)}
# Pairs retired from waves whose executable ran the penalties divided by
# their gcd (``_Executable.score_scale`` above 1).
GCD_PAIRS = "engine_gcd_pairs_total"


@dataclasses.dataclass
class SessionStats(EngineStats):
    """Aggregate telemetry across every submit of one session."""
    n_submits: int = 0
    n_waves: int = 0
    max_inflight: int = 0      # configured backpressure bound
    peak_inflight: int = 0     # highest observed in-flight wave count


class Ticket:
    """Handle for one ``submit()`` call.

    Fills in as its waves retire (possibly interleaved with other tickets'
    waves); ``done()`` is non-blocking, ``result()`` drives the session
    until this ticket is complete and returns its :class:`EngineResult`
    (scores in submission row order, per-ticket stats).
    """

    def __init__(self, session: "AlignmentSession", index: int, n_pairs: int,
                 output: str = "score", pen=None, heur=None, meta=None,
                 trace_variant: str = "packed", states=("M", "M"),
                 s_cap=None, internal: bool = False, on_done=None):
        eng = session.engine
        self.index = index
        self.n_pairs = n_pairs
        self.output = output
        # opaque caller payload (e.g. repro.mapping's (read, locus, strand)
        # records): rides the ticket through out-of-order retirement so
        # as_completed() consumers can interpret rows without a side table
        self.meta = meta
        self.pen = eng.pen if pen is None else pen          # PenaltyModel
        self.heur = eng.heuristic if heur is None else heur
        self.trace_variant = trace_variant   # "packed" | "bidir"
        # boundary states for BiWFA recursion children: "I"/"D" pins the
        # alignment start/end inside an open gap run
        self.states = tuple(states)
        # per-submit score ceiling (BiWFA children dispatch at their known
        # cost, far below the bucket worst case); None = engine bounds.
        # Capped tickets are single-pass: an unresolved row means "over the
        # cap", not "over the optimistic bound", so no recovery re-run.
        self._s_cap = s_cap
        # internal tickets (BiWFA sub-problems) never surface through
        # poll()/as_completed()/results(); on_done fires at finalization
        self.internal = internal
        self._on_done = on_done
        # trace-flow IDs riding this ticket: each connects one logical
        # request's spans (submit -> dispatch -> kernel -> retire -> done)
        # across threads.  _own_flows marks IDs this ticket allocated (it
        # ends them at finalize); externally-passed flows (serve requests,
        # BiWFA parents) are only stepped.
        self.flows: tuple = ()
        self._own_flows = False
        self.stats = EngineStats(n_pairs=n_pairs, n_workers=eng.n_workers)
        self._session = session
        self._scores = np.full((n_pairs,), -1, np.int32)
        self._cigars: Optional[dict] = {} if output == "cigar" else None
        # breakpoint fields for output="bidir_meet" rows:
        # (state, a, b, k, h, safe) per pair, -1 until the wave retires
        self._meet = (np.full((n_pairs, 6), -1, np.int32)
                      if output == "bidir_meet" else None)
        self._starget = None             # [n] known costs for meet waves
        self._p = self._t = self._plen = self._tlen = None
        self.char_bits = 32   # the kernel's fetch class for these codes
        self._outstanding = n_pairs      # rows without a final score yet
        self._recovery_rows: List[np.ndarray] = []   # overflow awaiting re-run
        self._steps = 0
        self._s_hi = 0
        self._k_hi = 0
        self._done = False
        self._result: Optional[EngineResult] = None

    def done(self) -> bool:
        return self._done

    def result(self) -> EngineResult:
        if not self._done:
            self._session._wait_for(self)
        return self._result


@dataclasses.dataclass
class _Wave:
    """One dispatched rectangular chunk whose device result is in flight."""
    ticket: Ticket
    rows: np.ndarray            # ticket-local row indices (un-padded count)
    res: object                 # WFAResult of in-flight device arrays
    plc: np.ndarray             # padded lens kept for CIGAR traceback
    tlc: np.ndarray
    k_max: int
    recovery: bool
    pc: Optional[np.ndarray] = None   # padded codes, kept only for CIGAR
    tc: Optional[np.ndarray] = None   # waves (packed-backtrace replay)
    chars_per_trip: Optional[int] = None   # the executable's fetch width
    run_pen: object = None      # the model the executable ran, and the
    score_scale: int = 1        # factor from its scores to the ticket's


class AlignmentSession:
    """Pipelined submit/drain front-end over one :class:`AlignmentEngine`.

    Created via :meth:`AlignmentEngine.stream` (or directly).  Shares the
    engine's executable cache, so a warm engine streams with zero retraces.

    **Thread safety**: every public entry point (``submit*``, ``poll``,
    ``as_completed``, ``drain``, ``Ticket.result``) serializes on one
    internal re-entrant lock, so multiple worker threads may feed and
    drain one shared session — the contract ``repro.serve``'s
    :class:`~repro.serve.loop.ServeLoop` relies on.  The lock is held per
    pipeline step (one wave packed or retired), never across a blocking
    iteration, so producers are not starved by a consumer driving the
    pipe.  One session is still one logical submission stream; open
    several sessions over the same engine for independent streams.

    ``_sync_timing`` is the engine-internal blocking mode used by
    ``align()``: each wave blocks per phase so scatter/kernel/gather stay
    separable (the streaming default instead attributes host dispatch time
    to scatter and wait-time at retirement to kernel).
    """

    def __init__(self, engine: AlignmentEngine, *,
                 max_inflight_waves: int = 2,
                 wave_pairs: Optional[int] = None,
                 _sync_timing: bool = False):
        if max_inflight_waves < 1:
            raise ValueError("max_inflight_waves must be >= 1")
        self.engine = engine
        self.max_inflight = int(max_inflight_waves)
        self.wave_pairs = int(wave_pairs if wave_pairs is not None
                              else engine.chunk_pairs)
        if self.wave_pairs < 1:
            raise ValueError("wave_pairs must be >= 1")
        self._sync = bool(_sync_timing)
        self.stats = SessionStats(n_workers=engine.n_workers,
                                  max_inflight=self.max_inflight)
        self._tickets: List[Ticket] = []
        self._inflight: Deque[_Wave] = collections.deque()
        self._completed: Deque[Ticket] = collections.deque()
        self._error: Optional[BaseException] = None
        self._closed = False
        # re-entrant: a locked step may recurse (backpressure retirement
        # inside a locked dispatch, recovery flush inside a retirement)
        self._lock = threading.RLock()

    # -- lifecycle -----------------------------------------------------------

    def __enter__(self) -> "AlignmentSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            self.close()
        else:
            # don't drain a failing block, but settle dispatched waves so
            # no in-flight computation outlives the session
            self._abandon_inflight()
            self._closed = True
        return False

    def close(self) -> None:
        """Drain outstanding work and refuse further submissions."""
        if not self._closed:
            try:
                self.drain()
            finally:
                self._closed = True

    @property
    def n_inflight(self) -> int:
        return len(self._inflight)

    @property
    def tickets(self) -> List[Ticket]:
        return list(self._tickets)

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("session is closed")
        if self._error is not None:
            raise RuntimeError(
                "session failed; no further submissions") from self._error

    # -- submission ----------------------------------------------------------

    def submit(self, patterns: Sequence[Seq], texts: Sequence[Seq], *,
               output: Optional[str] = None, penalties=None,
               heuristic=None, meta=None,
               trace_variant: Optional[str] = None) -> Ticket:
        """Enqueue one batch of python sequences; returns immediately.

        ``output="cigar"`` makes this ticket's waves run the backend's
        trace variant and its result carry per-pair CIGAR op arrays;
        ``penalties=``/``heuristic=`` select this ticket's penalty model
        and wavefront heuristic (tickets with different models coexist in
        one session — each compiles and caches its own executables);
        ``trace_variant="bidir"`` produces this ticket's CIGARs through the
        O(s)-memory BiWFA recursion (``repro.biwfa``) instead of the packed
        backtrace; ``None`` uses the engine defaults.  ``meta`` is an
        opaque payload stored on the returned ticket (``ticket.meta``) —
        the session never reads it.
        """
        assert len(patterns) == len(texts)
        p, plen = pack_batch(patterns)
        t, tlen = pack_batch(texts)
        return self.submit_packed(p, plen, t, tlen, output=output,
                                  penalties=penalties, heuristic=heuristic,
                                  meta=meta, trace_variant=trace_variant)

    def submit_packed(self, p: np.ndarray, plen: np.ndarray, t: np.ndarray,
                      tlen: np.ndarray, *, output: Optional[str] = None,
                      penalties=None, heuristic=None, meta=None,
                      trace_variant: Optional[str] = None,
                      _s_cap=None, _states=("M", "M"), _starget=None,
                      _internal: bool = False, _on_done=None,
                      _flows=None) -> Ticket:
        """Enqueue pre-packed [B, L] codes + [B] lens; returns immediately.

        The underscore keywords are the BiWFA driver's internal seam
        (``repro.biwfa.recurse``): sub-problems resubmit through the same
        session so they batch with live traffic.  ``_starget`` (known
        per-pair costs) flips the ticket to the engine-level
        ``"bidir_meet"`` output — a breakpoint wave, not a score/trace one.
        ``_flows`` hands the ticket externally-owned trace-flow IDs (serve
        requests, BiWFA parent tickets) to step through its spans instead
        of allocating its own.
        """
        with self._lock:
            self._check_open()
            n = int(p.shape[0])
            # resolve everything before the Ticket exists: a rejected submit
            # must leave the session clean (no permanently-incomplete ticket)
            pen = self.engine.resolve_penalties(penalties)
            if _starget is not None:
                out = "bidir_meet"
            else:
                out = self.engine.resolve_output(output, pen)
            heur = self.engine.resolve_heuristic(heuristic, out)
            tv = self.engine.resolve_trace_variant(trace_variant, out)
            ticket = Ticket(self, len(self._tickets), n, out, pen=pen,
                            heur=heur, meta=meta, trace_variant=tv,
                            states=_states, s_cap=_s_cap,
                            internal=_internal, on_done=_on_done)
            self._tickets.append(ticket)
            if _flows is not None:
                ticket.flows = tuple(_flows)
            elif obs_trace.enabled():
                # one flow per ticket: the arrow chain a Perfetto timeline
                # draws from this submit through every wave to finalize
                ticket.flows = (obs_trace.new_flow(),)
                ticket._own_flows = True
            if not _internal:
                self.stats.n_submits += 1
                self.stats.n_pairs += n
            with obs_trace.span(
                    "session.submit", cat="session",
                    args={"ticket": ticket.index, "pairs": n, "output": out}
                    if obs_trace.enabled() else None) as sp:
                for fid in ticket.flows:
                    (sp.flow_start if ticket._own_flows
                     else sp.flow_step)(fid)
                if n == 0:
                    self._finalize(ticket)
                    return ticket
                ticket._p = np.asarray(p)
                ticket._t = np.asarray(t)
                ticket._plen = np.asarray(plen, np.int32)
                ticket._tlen = np.asarray(tlen, np.int32)
                # once per ticket: every wave of it, recovery re-runs
                # included, fetches at this width
                ticket.char_bits = fetch_char_bits(
                    ticket._p, ticket._plen, ticket._t, ticket._tlen)
                if _starget is not None:
                    ticket._starget = np.asarray(_starget, np.int32)
                if tv == "bidir" and out == "cigar" and not _internal:
                    # meet-in-the-middle traceback: a host-side driver owns
                    # this ticket — it resolves scores first, then
                    # recursively splits each pair via breakpoint waves and
                    # internal sub-tickets, all batched through this same
                    # session
                    from repro.biwfa.recurse import BidirDriver
                    BidirDriver(self, ticket).start()
                    return ticket
                eng = self.engine
                # capped tickets (BiWFA children) are single-pass: the cap
                # is already an exact bound, so skip the optimistic first
                # pass
                optimistic = (eng.edit_frac is not None
                              and eng._s_max is None and _s_cap is None)
                self._enqueue_pass(ticket, np.arange(n),
                                   exact=not optimistic, recovery=False)
                return ticket

    def _enqueue_pass(self, ticket: Ticket, idx: np.ndarray, *, exact: bool,
                      recovery: bool) -> None:
        """Bucket ``idx`` rows of ``ticket`` and dispatch them as waves."""
        eng = self.engine
        for width, bidx in eng._plan_buckets(ticket._plen, ticket._tlen, idx):
            s_max, k_max = eng._bounds_for_bucket(
                width, ticket._plen[bidx], ticket._tlen[bidx], exact,
                pen=ticket.pen, s_cap=ticket._s_cap)
            ticket._s_hi = max(ticket._s_hi, s_max)
            ticket._k_hi = max(ticket._k_hi, k_max)
            info = BucketInfo(width, s_max, k_max, len(bidx),
                              recovery=recovery)
            ticket.stats.buckets.append(info)
            self.stats.buckets.append(info)
            # long-read bucket ladder: wide buckets cap rows-per-wave so a
            # 100 kb bucket dispatches narrow waves instead of OOMing at
            # wave_pairs rows (max_wave_cells bounds rows*width per wave)
            step = min(self.wave_pairs,
                       max(eng.max_wave_cells // max(width, 1),
                           eng.n_workers, 1))
            for lo in range(0, len(bidx), step):
                self._dispatch(ticket, bidx[lo:lo + step], width,
                               s_max, k_max, recovery)

    def _dispatch(self, ticket: Ticket, rows: np.ndarray, width: int,
                  s_max: int, k_max: int, recovery: bool) -> None:
        """Pack one wave and launch it without waiting for the result."""
        # Backpressure first: retiring *before* packing keeps the remaining
        # in-flight kernels running under this wave's host-side work.
        while len(self._inflight) >= self.max_inflight:
            self._retire_one()
        eng = self.engine
        args = ({"ticket": ticket.index, "rows": len(rows), "width": width,
                 "s_max": s_max, "recovery": recovery}
                if obs_trace.recording() else None)
        t0 = time.perf_counter()
        with obs_trace.span("wave.pack", cat="wave", args=args) as sp:
            for fid in ticket.flows:
                sp.flow_step(fid)
            # quantized for cache reuse, but never above the per-wave
            # memory cap
            nb = min(_quantize_rows(len(rows), eng.n_workers),
                     _round_up(self.wave_pairs, eng.n_workers))
            pc = _pad_rows(_fit_width(ticket._p[rows], width), nb)
            tc = _pad_rows(_fit_width(ticket._t[rows], width), nb)
            plc = _pad_rows(ticket._plen[rows], nb)
            tlc = _pad_rows(ticket._tlen[rows], nb)
            arrays = [pc, tc, plc, tlc]
            if ticket.output == "bidir_meet":
                # breakpoint waves carry each pair's known cost as a 5th
                # input
                arrays.append(_pad_rows(ticket._starget[rows], nb))
            exe, hit = eng._executable_for(pc.shape, tc.shape, s_max, k_max,
                                           ticket.output, pen=ticket.pen,
                                           heur=ticket.heur,
                                           states=ticket.states,
                                           char_bits=ticket.char_bits)
            for st in (ticket.stats, self.stats):
                if hit:
                    st.cache_hits += 1
                else:
                    st.cache_misses += 1
                st.bytes_in += (pc.nbytes + tc.nbytes + plc.nbytes
                                + tlc.nbytes)
            for st in (ticket.stats, self.stats):
                st.rows_real += len(rows)
                st.rows_padded += nb
        pre = exe.n_traces
        try:
            with obs_trace.span("wave.dispatch", cat="wave",
                                args=args) as sp:
                for fid in ticket.flows:
                    sp.flow_step(fid)
                with obs_trace.span("wave.put", cat="wave", args=args):
                    dev = eng._device_put(*arrays)
                if self._sync:
                    jax.block_until_ready(dev)
                    t1 = time.perf_counter()
                    for st in (ticket.stats, self.stats):
                        st.t_scatter += t1 - t0
                if hit:
                    res = exe.call(*dev)
                else:
                    # the fresh executable traces and compiles (or reads
                    # JAX's persistent cache) inside this call
                    with obs_trace.span("wave.compile", cat="wave",
                                        args=args):
                        res = exe.call(*dev)
            if self._sync:
                with obs_trace.span("wave.wait", cat="wave", args=args):
                    res.score.block_until_ready()
                t2 = time.perf_counter()
                for st in (ticket.stats, self.stats):
                    st.t_kernel += t2 - t1
            else:
                # async: pack + enqueue cost only; the copy and kernel
                # are both still in flight behind this wave
                t1 = time.perf_counter()
                for st in (ticket.stats, self.stats):
                    st.t_scatter += t1 - t0
        except Exception as e:
            self._error = e
            self._abandon_inflight()
            raise
        n_tr = exe.n_traces - pre
        for st in (ticket.stats, self.stats):
            st.n_traces += n_tr
        keep = ticket.output == "cigar"
        self._inflight.append(_Wave(ticket, rows, res, plc, tlc, k_max,
                                    recovery, pc=pc if keep else None,
                                    tc=tc if keep else None,
                                    chars_per_trip=exe.chars_per_trip,
                                    run_pen=exe.run_pen,
                                    score_scale=exe.score_scale))
        self.stats.n_waves += 1
        self.stats.peak_inflight = max(self.stats.peak_inflight,
                                       len(self._inflight))
        self._sample_inflight()
        if self._sync:
            self._retire_one()

    # -- retirement ----------------------------------------------------------

    def _sample_inflight(self) -> None:
        """Record the in-flight wave count on the gauge + counter track."""
        n = len(self._inflight)
        obs_metrics.gauge("session_inflight_waves",
                          "waves dispatched but not yet retired").set(n)
        obs_trace.counter("inflight_waves", n, cat="session")

    def _retire_one(self) -> None:
        """Gather the oldest in-flight wave and scatter its results."""
        wave = self._inflight.popleft()
        ticket = wave.ticket
        self._sample_inflight()
        _args = ({"ticket": ticket.index, "rows": len(wave.rows),
                  "recovery": wave.recovery}
                 if obs_trace.recording() else None)
        t0 = time.perf_counter()
        with obs_trace.span("wave.wait", cat="wave", args=_args) as sp:
            for fid in ticket.flows:
                sp.flow_step(fid)
            try:
                wave.res.score.block_until_ready()
            except Exception as e:
                self._error = e
                self._abandon_inflight()
                raise
        t1 = time.perf_counter()
        sp = obs_trace.span("wave.gather", cat="wave", args=_args)
        sp.__enter__()
        for fid in ticket.flows:
            sp.flow_step(fid)
        # one batch of device-to-host copies, so the counters' reads
        # overlap the scores' (breakpoint waves carry no trip counter; a
        # sharded backend's counters hold one value per shard)
        full, steps, shard_trips = jax.device_get(
            (wave.res.score, wave.res.n_steps,
             getattr(wave.res, "n_ext_trips", None)))
        out = full[: len(wave.rows)]
        if wave.score_scale > 1:
            # back from the gcd-reduced model to the ticket's units
            out = np.where(out >= 0, out * wave.score_scale, out)
            obs_metrics.counter(GCD_PAIRS,
                                "real pair rows retired from waves run on "
                                "the penalties divided by their gcd"
                                ).inc(len(wave.rows))
        steps = int(np.sum(steps))
        if shard_trips is not None:
            shard_trips = np.ravel(shard_trips)
        trips = None if shard_trips is None else int(shard_trips.sum())
        t2 = time.perf_counter()
        if not self._sync:       # sync mode billed the kernel at dispatch
            for st in (ticket.stats, self.stats):
                st.t_kernel += t1 - t0
        for st in (ticket.stats, self.stats):
            st.t_gather += t2 - t1
            st.bytes_out += full.nbytes
        ticket._scores[wave.rows] = out
        ticket._steps += steps
        obs_metrics.counter("kernel_pairs_total",
                            "real pair rows retired from waves"
                            ).inc(len(wave.rows))
        obs_metrics.counter("kernel_score_steps_total",
                            "score-loop steps of retired waves"
                            ).inc(steps)
        if wave.chars_per_trip is not None:
            obs_metrics.counter(
                FETCH_PAIRS[wave.chars_per_trip],
                f"real pair rows retired from kernel waves comparing "
                f"{wave.chars_per_trip} characters per extend trip"
            ).inc(len(wave.rows))
        if trips is not None:
            for st in (ticket.stats, self.stats):
                st.n_ext_trips += trips
            obs_metrics.counter("kernel_extend_trips_total",
                                "extend trips of retired waves, summed "
                                "over the kernel's grid blocks").inc(trips)
            if shard_trips.size > 1:
                n_max, n_mean = SHARD_COUNTERS
                obs_metrics.counter(
                    n_max, "extend trips of each retired wave's slowest "
                    "shard").inc(int(shard_trips.max()))
                obs_metrics.counter(
                    n_mean, "extend trips of each retired wave, averaged "
                    "over its shards").inc(float(shard_trips.mean()))
        if ticket._meet is not None:
            r = wave.res
            nr = len(wave.rows)
            ticket._meet[wave.rows] = np.stack(
                [np.asarray(r.meet_state)[:nr], np.asarray(r.meet_a)[:nr],
                 np.asarray(r.meet_b)[:nr], np.asarray(r.meet_k)[:nr],
                 np.asarray(r.meet_h)[:nr], np.asarray(r.meet_safe)[:nr]],
                axis=1).astype(np.int32)
            n_unmet = int((out < 0).sum())
            for st in (ticket.stats, self.stats):
                st.n_meet_unmet += n_unmet
        sp.__exit__(None, None, None)        # close the gather span
        if ticket._cigars is not None:
            with obs_trace.span("wave.traceback", cat="wave",
                                args=_args) as tsp:
                for fid in ticket.flows:
                    tsp.flow_step(fid)
                t3 = time.perf_counter()
                ops = cigar_mod.traceback_result(
                    wave.res, wave.run_pen, pattern=wave.pc, text=wave.tc,
                    plen=wave.plc, tlen=wave.tlc, k_max=wave.k_max,
                    begin_state=ticket.states[0],
                    end_state=ticket.states[1])
                dt = time.perf_counter() - t3
                nbytes = cigar_mod.trace_nbytes(wave.res)
                for st in (ticket.stats, self.stats):
                    st.t_gather += dt
                    st.bytes_out += nbytes
                    st.peak_trace_bytes = max(st.peak_trace_bytes, nbytes)
                for j, orig in enumerate(wave.rows):
                    ticket._cigars[int(orig)] = ops[j]

        eng = self.engine
        optimistic = (eng.edit_frac is not None and eng._s_max is None
                      and ticket._s_cap is None)
        settled = len(wave.rows)     # rows this wave resolved for good
        if wave.recovery:
            n_rec = int((out >= 0).sum())
            for st in (ticket.stats, self.stats):
                st.n_recovered += n_rec
        elif optimistic:
            overflow = wave.rows[out < 0]
            if len(overflow):
                for st in (ticket.stats, self.stats):
                    st.n_overflow += len(overflow)
                obs_metrics.counter("session_overflow_pairs_total",
                                    "pairs past the optimistic bound, "
                                    "queued for exact re-run"
                                    ).inc(len(overflow))
                if obs_trace.enabled():
                    obs_trace.instant("session.overflow", cat="session",
                                      args={"ticket": ticket.index,
                                            "rows": len(overflow)})
                if eng.adaptive:
                    # recycle into the recovery queue rather than blocking
                    # the pipeline for one straggler
                    ticket._recovery_rows.append(overflow)
                    settled -= len(overflow)
        ticket._outstanding -= settled
        self._maybe_finish(ticket)
        if (ticket._recovery_rows and
                sum(len(r) for r in ticket._recovery_rows)
                >= self.wave_pairs):
            self._flush_recovery(ticket)    # a full recovery wave is ready

    def _abandon_inflight(self) -> None:
        """Settle and drop every in-flight wave after the session failed.

        The first error poisons the session; the remaining dispatched waves
        are synchronized (their errors swallowed — the first one is the one
        reported) so no in-flight computation outlives the session to raise
        at interpreter exit.
        """
        obs_record.dump("session_failure",
                        {"error": repr(self._error) if self._error else None,
                         "inflight_waves": len(self._inflight)})
        with self._lock:
            inflight, self._inflight = list(self._inflight), \
                collections.deque()
        for wave in inflight:
            try:
                wave.res.score.block_until_ready()
            except Exception:
                pass
        try:
            # drain runtime-token errors too (e.g. a failed callback inside
            # a backend) so nothing re-raises at interpreter exit
            jax.effects_barrier()
        except Exception:
            # a poisoned token makes effects_barrier raise *before* it
            # clears the token set, so jax's atexit barrier would re-raise
            # the same error; every wave is already settled above, so the
            # tokens are safe to drop
            try:
                from jax._src import dispatch as _dispatch
                _dispatch.runtime_tokens.clear()
            except Exception:            # pragma: no cover - jax internals
                pass

    def _maybe_finish(self, ticket: Ticket) -> None:
        if not ticket._done and ticket._outstanding == 0:
            self._finalize(ticket)

    def _finalize(self, ticket: Ticket) -> None:
        cig = None
        if ticket._cigars is not None:
            cig = [ticket._cigars[i] for i in range(ticket.n_pairs)]
        ticket._result = EngineResult(ticket._scores, cig, ticket._steps,
                                      ticket._s_hi, ticket._k_hi,
                                      ticket.stats,
                                      approximate=not ticket.heur.exact)
        ticket._p = ticket._t = ticket._plen = ticket._tlen = None
        ticket._done = True
        if ticket._own_flows and ticket.flows:
            # terminate the arrow chain: a zero-length span hosts the flow
            # end so viewers bind the arrowhead to this thread's timeline
            with obs_trace.span("session.ticket_done", cat="session",
                                args={"ticket": ticket.index}
                                if obs_trace.enabled() else None) as sp:
                for fid in ticket.flows:
                    sp.flow_end(fid)
        if ticket.internal:
            # BiWFA sub-problem: hand the result to the driver (which may
            # re-enter submit_packed — the lock is re-entrant) instead of
            # surfacing through poll()/as_completed()
            if ticket._on_done is not None:
                ticket._on_done(ticket)
        else:
            self._completed.append(ticket)

    def _flush_recovery(self, ticket: Optional[Ticket] = None) -> None:
        """Re-run queued overflow pairs with exact worst-case bounds."""
        for t in ([ticket] if ticket is not None else list(self._tickets)):
            if t._recovery_rows:
                rows = np.concatenate(t._recovery_rows)
                t._recovery_rows = []
                if obs_trace.enabled():
                    obs_trace.instant("session.recovery_flush",
                                      cat="session",
                                      args={"ticket": t.index,
                                            "rows": len(rows)})
                self._enqueue_pass(t, rows, exact=True, recovery=True)

    # -- gather --------------------------------------------------------------

    def _step(self, ticket: Optional[Ticket] = None) -> None:
        """Make one unit of progress (retire a wave or launch recovery)."""
        if self._error is not None:
            raise RuntimeError("session failed") from self._error
        if self._inflight:
            self._retire_one()
        elif ticket is not None and ticket._recovery_rows:
            self._flush_recovery(ticket)
        elif any(t._recovery_rows for t in self._tickets):
            self._flush_recovery()
        else:
            raise RuntimeError("session stalled: incomplete tickets with "
                               "no in-flight waves")        # pragma: no cover

    def _wait_for(self, ticket: Ticket) -> None:
        """Drive the pipeline until ``ticket`` is complete."""
        while not ticket._done:
            with self._lock:
                if not ticket._done:
                    self._step(ticket)

    @staticmethod
    def _wave_ready(wave: _Wave) -> bool:
        """True when the wave's device result can be gathered without
        blocking.  Results that don't expose ``is_ready`` (plug-in
        backends returning exotic array types) count as ready, so
        retirement falls back to blocking rather than never progressing.
        """
        probe = getattr(wave.res.score, "is_ready", None)
        return True if probe is None else bool(probe())

    def _inflight_diagnostics(self) -> str:
        """One-line pipeline state for TimeoutError messages."""
        with self._lock:
            waves = [f"ticket {w.ticket.index}:{len(w.rows)} rows"
                     + (" (recovery)" if w.recovery else "")
                     for w in self._inflight]
            n_open = sum(1 for t in self._tickets if not t._done)
            n_rec = sum(len(r) for t in self._tickets
                        for r in t._recovery_rows)
        return (f"{len(waves)} wave(s) in flight [{'; '.join(waves)}], "
                f"{n_open} ticket(s) incomplete, "
                f"{n_rec} recovery row(s) queued")

    def _step_timed(self, deadline: float) -> None:
        """Make one unit of progress before ``deadline`` or raise
        ``TimeoutError`` (with pipeline diagnostics) — never yields a
        partial step."""
        while True:
            with self._lock:
                if self._error is not None:
                    raise RuntimeError("session failed") from self._error
                if self._completed or all(t._done for t in self._tickets):
                    return
                if self._inflight:
                    if self._wave_ready(self._inflight[0]):
                        self._retire_one()
                        return
                elif any(t._recovery_rows for t in self._tickets):
                    self._flush_recovery()
                    return
                else:
                    raise RuntimeError(
                        "session stalled: incomplete tickets with no "
                        "in-flight waves")          # pragma: no cover
            now = time.monotonic()
            if now >= deadline:
                diag = self._inflight_diagnostics()
                obs_record.dump("as_completed_timeout", {"detail": diag})
                raise TimeoutError("as_completed timed out: " + diag)
            # oldest wave still running: nap outside the lock so producers
            # keep submitting while we wait
            time.sleep(min(1e-3, deadline - now))

    def poll(self, *, flush_recovery: bool = True) -> List[Ticket]:
        """Non-blocking progress probe -> tickets that newly completed.

        Retires every in-flight wave whose device result is already ready
        (``jax.Array.is_ready``), never blocking on a running kernel; when
        the pipeline is otherwise empty and ``flush_recovery`` is set,
        queued overflow rows are re-dispatched immediately (a server loop
        cannot wait for a full recovery wave to accumulate — stragglers
        would stall forever at low load).  Returns the completed-ticket
        backlog (the same queue ``as_completed()`` consumes), possibly
        empty.  This is the probe ``repro.serve``'s worker loop runs
        between admissions.
        """
        with self._lock:
            if self._error is not None:
                raise RuntimeError("session failed") from self._error
            while self._inflight and self._wave_ready(self._inflight[0]):
                self._retire_one()
            if flush_recovery and not self._inflight:
                self._flush_recovery()
                while self._inflight and self._wave_ready(self._inflight[0]):
                    self._retire_one()
            out = list(self._completed)
            self._completed.clear()
            return out

    def as_completed(self, timeout: Optional[float] = None) -> Iterator[Ticket]:
        """Yield tickets as they finish — out of order, minimal latency.

        Keeps driving the pipeline between yields; tickets submitted while
        iterating are picked up too.  Each completed ticket is yielded
        exactly once per session (``poll()`` consumes the same backlog).

        ``timeout`` bounds the **total** wait across the iteration (like
        ``concurrent.futures.as_completed``): if the deadline passes while
        a wave is still running, ``TimeoutError`` is raised with in-flight
        diagnostics (which tickets' waves are stuck, how many recovery
        rows are queued) instead of blocking forever on a stalled kernel.
        """
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        while True:
            while True:
                with self._lock:
                    ticket = (self._completed.popleft()
                              if self._completed else None)
                if ticket is None:
                    break
                yield ticket
            with self._lock:
                if self._completed:         # another thread raced a wave in
                    continue
                if all(t._done for t in self._tickets):
                    return
                if deadline is None:
                    self._step()
                    continue
            self._step_timed(deadline)

    def results(self) -> Iterator[EngineResult]:
        """Yield each submit's :class:`EngineResult` in submission order
        (internal BiWFA sub-tickets excluded)."""
        i = 0
        while i < len(self._tickets):
            if not self._tickets[i].internal:
                yield self._tickets[i].result()
            i += 1

    def drain(self) -> SessionStats:
        """Block until every submitted pair (incl. recovery) has a result."""
        while True:
            with self._lock:
                if not (self._inflight
                        or any(t._recovery_rows for t in self._tickets)):
                    return self.stats
                self._step()


def run_streamed(engine: AlignmentEngine, p: np.ndarray, plen: np.ndarray,
                 t: np.ndarray, tlen: np.ndarray, *, submit_pairs: int,
                 max_inflight_waves: int = 4,
                 output: Optional[str] = None, penalties=None,
                 heuristic=None, trace_variant: Optional[str] = None):
    """Stream one packed batch through a fresh session in ``submit_pairs``
    chunks with out-of-order gather
    -> (scores, cigars-or-None, SessionStats, wall_seconds).

    The shared harness behind the launcher's ``--mode stream`` and the
    transfer-overhead benchmark's streamed column.  ``output="cigar"``
    gathers per-pair op arrays (in submission row order) alongside scores.
    """
    n = int(p.shape[0])
    out_mode = engine.resolve_output(output,
                                     engine.resolve_penalties(penalties))
    scores = np.empty((n,), np.int32)
    cigars: Optional[List[np.ndarray]] = \
        [None] * n if out_mode == "cigar" else None
    t0 = time.perf_counter()
    with engine.stream(max_inflight_waves=max_inflight_waves) as sess:
        offset = {}
        for lo in range(0, n, submit_pairs):
            hi = min(n, lo + submit_pairs)
            ticket = sess.submit_packed(p[lo:hi], plen[lo:hi],
                                        t[lo:hi], tlen[lo:hi],
                                        output=out_mode,
                                        penalties=penalties,
                                        heuristic=heuristic,
                                        trace_variant=trace_variant)
            offset[ticket.index] = lo
        for ticket in sess.as_completed():
            lo = offset[ticket.index]
            res = ticket.result()
            scores[lo:lo + ticket.n_pairs] = res.scores
            if cigars is not None:
                cigars[lo:lo + ticket.n_pairs] = res.cigars
        stats = sess.stats
    return scores, cigars, stats, time.perf_counter() - t0
