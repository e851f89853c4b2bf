"""Run a cell's control on the chip: the check must read it as wrong.

    python3 chipbench/control.py --workload wfa100.e4.score \
        --seeds 11,12,13 --seconds 10

The configurations state exact alignment.  The control is the program's
own pruning path switched on (``ZDrop(zdrop=1)``, passed as every
submission's ``heuristic=``), the shortcut that would tempt a later
change: it breaks exactness.  Each seed runs the cell at its own size
and load through the same harness, with the same check, in one process,
and prints the result line.  The benchmark's own runs never run it.
"""
import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.core.scoring import ZDrop
    if jax.devices()[0].platform != "tpu":
        print("[control] no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_proc=time.perf_counter(),
                               heuristic=ZDrop(zdrop=1))
        for line in out["log"]:
            print(f"[control] {line}", file=sys.stderr, flush=True)
        print(json.dumps(dict(out["line"], seed=seed,
                              workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
