"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; each lives in a file of
its own under ``chipbench/`` (``configs/``, ``traffic/``, ``metrics/``).
With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a ``jax.profiler``
trace of the same window, the device's busy and window seconds, and a
breakdown.  Earlier lines on standard error give the pool, the bounds
and the compiles inside the window; the last ones, each number the check
compared with its limit.

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, the command exits non-zero and prints no result.  JAX's
persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""
import time

T_PROC = time.perf_counter()   # as near to the process's start as Python

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="PATH",
                    help="also write the traced run's compact trace here")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    cell = harness.load_cell(args.workload)
    # a fixed directory inside the checkout, whatever the machine sets
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"[chipbench] need {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    if devs[0].device_kind not in harness.load_peaks():
        print(f"[chipbench] no peaks for {devs[0].device_kind!r} in "
              f"peaks.json", file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_proc=T_PROC, keep_trace=args.keep_trace)
    for line in out["log"]:
        print(f"[chipbench] {line}", file=sys.stderr, flush=True)
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
