"""Benchmark driver: one module per paper table/figure + substrate benches.

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig1,scaling,...]
Prints ``name,us_per_call,derived`` CSV (one row per measurement).
``--json [PATH]`` additionally writes a machine-readable snapshot (default
``results/perf/BENCH_<utc-timestamp>.json``) so per-commit runs accumulate
a perf trajectory."""
from __future__ import annotations

import argparse
import datetime
import json
import os
import platform
import subprocess
import sys
import traceback

from benchmarks.common import emit
from repro.launch.runtime import device_info, device_tag, enable_compile_cache


def _write_json(path: str, rows, argv, failed) -> str:
    if path == "auto":
        stamp = datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y%m%dT%H%M%SZ")
        path = os.path.join("results", "perf", f"BENCH_{stamp}.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    try:
        commit = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                                capture_output=True, text=True,
                                timeout=10).stdout.strip() or None
    except Exception:
        commit = None
    payload = {
        "created_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "commit": commit,
        "argv": list(argv) if argv is not None else sys.argv[1:],
        "host": {"platform": platform.platform(),
                 "python": platform.python_version()},
        "device": device_info(),
        "failed_suites": failed,
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig1,scaling,transfer,"
                         "cigar,scoring,mapping,serving,longread,kernelgap,"
                         "wfa_ops,lm,obs")
    ap.add_argument("--pairs", type=int, default=8192)
    ap.add_argument("--json", nargs="?", const="auto", default=None,
                    metavar="PATH",
                    help="also write a JSON snapshot (default "
                         "results/perf/BENCH_<timestamp>.json)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="run the suites with tracing enabled and write "
                         "one Chrome trace-event JSON timeline (open in "
                         "ui.perfetto.dev)")
    args = ap.parse_args(argv)
    want = set(args.only.split(",")) if args.only else None

    suites = []
    if want is None or "fig1" in want:
        from benchmarks import fig1_throughput
        suites.append(("fig1", lambda: fig1_throughput.run(pairs=args.pairs)))
    if want is None or "scaling" in want:
        from benchmarks import scaling_batch
        suites.append(("scaling", scaling_batch.run))
    if want is None or "transfer" in want:
        from benchmarks import transfer_overhead
        suites.append(("transfer",
                       lambda: transfer_overhead.run(pairs=args.pairs)))
    if want is None or "cigar" in want:
        from benchmarks import cigar_overhead
        suites.append(("cigar",
                       lambda: cigar_overhead.run(
                           pairs=min(args.pairs, 2048))))
    if want is None or "scoring" in want:
        from benchmarks import scoring_models
        suites.append(("scoring",
                       lambda: scoring_models.run(
                           pairs=min(args.pairs, 2048))))
    if want is None or "mapping" in want:
        from benchmarks import mapping
        suites.append(("mapping",
                       lambda: mapping.run(reads=min(args.pairs, 512))))
    if want is None or "serving" in want:
        from benchmarks import serving
        # the ratio gate needs a trace long enough to amortize the
        # form-deadline/drain tail: don't shrink below ~512 requests
        # unless --pairs is tiny
        suites.append(("serving",
                       lambda: serving.run(
                           requests=min(max(args.pairs // 2, 64), 512))))
    if want is None or "longread" in want:
        from benchmarks import longread
        suites.append(("longread",
                       lambda: longread.run(
                           pairs=min(max(args.pairs // 64, 8), 32))))
    if want is None or "kernelgap" in want:
        from benchmarks import kernelgap
        # interpret-mode kernel runs: keep the batch modest
        suites.append(("kernelgap",
                       lambda: kernelgap.run(
                           pairs=min(max(args.pairs // 8, 64), 256))))
    if want is None or "wfa_ops" in want:
        from benchmarks import wfa_ops
        suites.append(("wfa_ops", wfa_ops.run))
    if want is None or "lm" in want:
        from benchmarks import lm_substrate
        suites.append(("lm", lm_substrate.run))
    if want is None or "obs" in want:
        # safe under --trace-out: the suite self-measures inside
        # obs_trace.isolated(), which restores the outer timeline
        from benchmarks import obs_overhead
        suites.append(("obs",
                       lambda: obs_overhead.run(
                           pairs=min(args.pairs, 4096))))

    rows = []
    failed = []
    rc = 0
    from repro import obs
    with obs.capture_trace(args.trace_out):
        for name, fn in suites:
            try:
                rows.extend(fn())
            except Exception:
                print(f"# suite {name} FAILED:", file=sys.stderr)
                traceback.print_exc()
                failed.append(name)
                rc = 1
    if args.trace_out:
        print(f"# trace -> {args.trace_out}", file=sys.stderr)
        try:
            # phase accounting over the capture we just wrote: the
            # paper's transfer/kernel/retrieve split lands in the same
            # snapshot, so snapshot diffs can name the phase that moved
            from repro.obs import analyze
            pt = analyze.phase_accounting(
                analyze.Trace.from_file(args.trace_out))
            rows.extend(pt.as_rows())
        except Exception:
            print("# phase accounting FAILED:", file=sys.stderr)
            traceback.print_exc()
            failed.append("phase")
            rc = 1
    # every rate below was measured on this device (a CPU run is never a
    # chip number)
    print(f"# device {device_tag()}")
    emit(rows)
    if args.json is not None:
        path = _write_json(args.json, rows, argv, failed)
        print(f"# wrote {path}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
