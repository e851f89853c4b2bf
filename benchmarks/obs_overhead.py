"""Observability overhead: is `repro.obs` safe to leave compiled in?

The instrumentation layer (PR 9) is always-available: every wave
dispatch/retire crosses `obs.trace` span points and a couple of
`obs.metrics` updates, with a module-global switch gating the trace
emission.  This suite measures both costs the design promises to keep
negligible:

* **disabled** (the default) — each span point is one function call and
  one branch returning a shared no-op object.  ``obs/disabled_ns``
  microbenchmarks that call; ``obs/disabled_frac`` projects it onto a
  wave (a conservative per-wave call count x ns-per-call / measured wave
  time).  Since PR 10 "disabled" includes the **flight recorder**: the
  streamed measurement and the projection both run with the post-mortem
  ring active (``obs/flightrec_ns`` is the ring's full span cycle), so
  the 2% budget covers the always-on configuration a live server
  actually runs in, not just the bare branch.  Both are timed with the
  ``jax.profiler`` bridge inactive (``obs.profile._active`` false), the
  branch every span takes outside a ``--profile`` capture; while one is
  active each span also opens a profiler annotation, which
  ``obs/annotated_ns`` times.  Gate: <= 2%.
* **enabled** — spans, flow events and counters are actually buffered.
  ``obs/on_ratio`` is enabled/disabled align throughput (warm engine,
  best-of-3 each, interleaved).  Gate: >= 0.90 — capturing a timeline
  costs at most 10%.

``main(--check)`` is the CI gate; ``--from-json`` gates on the newest
``benchmarks.run --json`` snapshot like the other suites.  The whole
measurement runs inside ``obs_trace.isolated()``, so toggling the
switch and emitting ~10^5 throwaway spans never corrupts an outer
``benchmarks.run --trace-out`` capture.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import Row
from repro.configs import wfa_paper
from repro.core.engine import AlignmentEngine
from repro.core.session import run_streamed
from repro.data.reads import ReadPairSpec, generate_pairs
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import record as obs_record
from repro.obs import trace as obs_trace

ON_RATIO_GATE = 0.90       # tracing-on throughput >= 90% of tracing-off
DISABLED_FRAC_GATE = 0.02  # projected tracing-off overhead <= 2%

# Conservative upper bound on obs entry points crossed per dispatched
# wave (spans + enabled() checks + instants in session._dispatch /
# _retire_one / engine._executable_for), used to project the disabled
# per-call cost onto a wave.  The real count is ~15-25; the margin keeps
# the gate honest if later PRs add span points without re-counting.
CALLS_PER_WAVE = 64
# metrics updates per wave (gauge/counter registry lookups) that run
# regardless of the trace switch
METRIC_CALLS_PER_WAVE = 8


def _bench_stream(eng, P, plen, T, tlen, submit_pairs: int,
                  iters: int = 3) -> float:
    """Best-of-``iters`` wall seconds for one warm streamed pass.

    The streamed session is the instrumented path (wave.pack /
    wave.dispatch / wave.wait / wave.gather spans + per-ticket flows),
    so this is the
    surface the overhead gates actually protect.
    """
    best = float("inf")
    for _ in range(iters):
        _, _, _, dt = run_streamed(eng, P, plen, T, tlen,
                                   submit_pairs=submit_pairs)
        best = min(best, dt)
    return best


def _ns_per_call(fn, n: int = 200_000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e9


def run(pairs: int = 4096, read_len: int = 100, edit_frac: float = 0.02,
        backend: str = "ring", submit_pairs: int = 256) -> list[Row]:
    spec = ReadPairSpec(n_pairs=pairs, read_len=read_len,
                        edit_frac=edit_frac, seed=11)
    P, plen, T, tlen = generate_pairs(spec)
    eng = AlignmentEngine(wfa_paper.pen, backend=backend,
                          edit_frac=edit_frac)
    run_streamed(eng, P, plen, T, tlen,
                 submit_pairs=submit_pairs)          # warm the cache

    with obs_trace.isolated():
        # "disabled" is the production default: tracer off, no profile
        # capture, flight recorder ON (a live server keeps the
        # post-mortem ring warm).
        assert not obs_profile.active(), "measure outside --profile"
        obs_record.acquire()
        try:
            # interleaved off/on/off/on: shared-host noise hits both modes
            obs_trace.disable()
            t_off = _bench_stream(eng, P, plen, T, tlen, submit_pairs)
            obs_trace.enable()
            obs_trace.reset()
            t_on = _bench_stream(eng, P, plen, T, tlen, submit_pairs)
            n_events = len(obs_trace.events())
            obs_trace.reset()
            obs_trace.disable()
            t_off = min(t_off, _bench_stream(eng, P, plen, T, tlen,
                                             submit_pairs))
            obs_trace.enable()
            obs_trace.reset()
            t_on = min(t_on, _bench_stream(eng, P, plen, T, tlen,
                                           submit_pairs))
            obs_trace.reset()

            # ring-only span cost: tracer off, recorder active — a real
            # Span is built and its exit event lands in the ring
            obs_trace.disable()

            def _span_cycle():
                with obs_trace.span("x"):
                    pass

            rec_span_ns = _ns_per_call(_span_cycle)
            g = obs_metrics.gauge("obs_overhead_probe")
            gauge_ns = _ns_per_call(lambda: g.set(1.0))
        finally:
            obs_record.release()
        # bare branch cost: tracer off, recorder off, profile inactive
        # -> NULL span
        span_ns = _ns_per_call(lambda: obs_trace.span("x"))
        # profile active: the span opens a profiler annotation (no
        # capture running, so this is the bridge's own cost)
        obs_profile._active = True
        try:
            annotated_ns = _ns_per_call(_span_cycle, n=50_000)
        finally:
            obs_profile._active = False

    n_waves = max(1, -(-pairs // submit_pairs))
    wave_s = t_off / n_waves
    worst_span_ns = max(span_ns, rec_span_ns)
    disabled_frac = (CALLS_PER_WAVE * worst_span_ns
                     + METRIC_CALLS_PER_WAVE * gauge_ns) / 1e9 / wave_s
    on_ratio = t_off / t_on

    return [
        ("obs/off", t_off / pairs * 1e6,
         f"{pairs / t_off:,.0f} pairs/s tracing disabled "
         f"(flight recorder active)"),
        ("obs/on", t_on / pairs * 1e6,
         f"{pairs / t_on:,.0f} pairs/s tracing enabled "
         f"({n_events} trace events over 3 passes)"),
        ("obs/on_ratio", on_ratio,
         f"enabled/disabled throughput (gate >= {ON_RATIO_GATE})"),
        ("obs/disabled_ns", span_ns,
         f"ns per disabled span() call ({gauge_ns:.0f} ns per gauge set)"),
        ("obs/flightrec_ns", rec_span_ns,
         "ns per full span cycle with tracing off + flight-recorder "
         "ring active"),
        ("obs/annotated_ns", annotated_ns,
         "ns per full span cycle with tracing off + profile active "
         "(the span's jax.profiler annotation)"),
        ("obs/disabled_frac", disabled_frac,
         f"projected disabled overhead per wave: {CALLS_PER_WAVE} span "
         f"points x {worst_span_ns:.0f} ns (ring-active worst case) + "
         f"{METRIC_CALLS_PER_WAVE} metric updates x {gauge_ns:.0f} ns "
         f"over {wave_s * 1e3:.1f} ms (gate <= {DISABLED_FRAC_GATE})"),
    ]


def _value(rows: list[Row], name: str) -> float:
    for n, v, _ in rows:
        if n == name:
            return v
    raise KeyError(name)


def check(rows: list[Row], on_ratio_gate: float = ON_RATIO_GATE,
          disabled_frac_gate: float = DISABLED_FRAC_GATE) -> list[str]:
    """The CI gate over obs rows (live or from a JSON snapshot)."""
    failures = []
    frac = _value(rows, "obs/disabled_frac")
    if not frac <= disabled_frac_gate:
        failures.append(
            f"obs/disabled_frac: projected tracing-off overhead "
            f"{frac:.1%} > {disabled_frac_gate:.0%} — the disabled hot "
            f"path is no longer a single branch")
    ratio = _value(rows, "obs/on_ratio")
    if not ratio >= on_ratio_gate:
        failures.append(
            f"obs/on_ratio: tracing-on throughput {ratio:.2f}x of "
            f"tracing-off < {on_ratio_gate}x — span emission is too "
            f"expensive to capture timelines in production")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=4096)
    ap.add_argument("--read-len", type=int, default=100)
    ap.add_argument("--backend", default="ring")
    ap.add_argument("--on-ratio-gate", type=float, default=ON_RATIO_GATE)
    ap.add_argument("--disabled-frac-gate", type=float,
                    default=DISABLED_FRAC_GATE)
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) unless disabled overhead <= 2%% "
                         "and tracing-on throughput >= 90%% of tracing-off")
    ap.add_argument("--from-json", default=None, metavar="GLOB",
                    help="with --check: gate on the newest matching "
                         "benchmarks.run --json snapshot instead of "
                         "re-running")
    args = ap.parse_args(argv)
    from benchmarks.common import emit
    if args.from_json:
        from benchmarks.common import rows_from_json
        rows = rows_from_json(args.from_json, "obs/")
    else:
        rows = run(pairs=args.pairs, read_len=args.read_len,
                   backend=args.backend)
        emit(rows)
    if args.check:
        failures = check(rows, on_ratio_gate=args.on_ratio_gate,
                         disabled_frac_gate=args.disabled_frac_gate)
        for f in failures:
            print(f"# obs REGRESSION: {f}", file=sys.stderr)
        if failures:
            if args.from_json:
                from benchmarks.common import snapshot_diff
                for line in snapshot_diff(args.from_json, "obs/"):
                    print(f"# obs {line}", file=sys.stderr)
            return 1
        print("# obs gate passed: disabled overhead <= "
              f"{args.disabled_frac_gate:.0%}, tracing-on within "
              f"{1 - args.on_ratio_gate:.0%} of tracing-off",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
