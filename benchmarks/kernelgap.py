"""Kernel-vs-ring gap tracker: throughput ratio + parity + pruning gate.

The interpreted Pallas kernel runs the chip's fetch, a select tree over
``[L, 8, 128]`` word slabs per extend trip, which interpret mode
executes as XLA ops on the CPU.  This suite tracks its speed against the jnp ring
solver on every push (a CPU ratio, not a chip figure), plus two
correctness properties:

* **ratio** — kernel/ring pairs-per-second must stay >= ``RATIO_GATE`` x
  the pre-PR baseline ratio (``BASELINE_RATIO``, from
  BENCH_20260801T164232Z: kernel at ~1% of ring throughput);
* **parity** — scores *and* CIGARs bit-identical kernel-vs-ring on an
  {edit, gap-affine} grid (exact alignment, no tolerance to pick);
* **pruning win** — ``ring/affine/adaptive`` >= ``ring/affine/exact`` on
  the divergent-mix workload.  Masked-lane pruning used to *lose* here
  (the mask work cost more than it saved); the compacting band
  (``backend_opts={"band_cap": "auto"}``) shrinks the vector width to
  the heuristic's own radius, which is what flips it.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from benchmarks.common import Row, emit, rows_from_json, time_fn
from repro.configs import wfa_paper
from repro.core.engine import AlignmentEngine
from repro.core.scoring import AdaptiveBand, Edit
from repro.data.reads import ReadPairSpec, generate_pairs

# Pre-PR interpret-mode gap (BENCH_20260801T164232Z, b1024 L100 E0.02):
# ring 211 us/call vs kernel 20,153 us/call -> kernel at ~1.05% of ring.
BASELINE_RATIO = 211.0 / 20153.0
RATIO_GATE = 10.0               # fused kernel must hold >= 10x that ratio


def _divergent_mix(n_pairs: int, read_len: int, edit_frac: float, seed: int):
    """Half related mates (within the E budget), half unrelated random
    pairs — the workload where pruning pays and exact alignment walks the
    full band to ``s_max``."""
    half = n_pairs // 2
    P, plen, T, tlen = generate_pairs(ReadPairSpec(
        n_pairs=half, read_len=read_len, edit_frac=edit_frac, seed=seed))
    rng = np.random.default_rng(seed + 1)
    bases = np.frombuffer(b"ACGT", np.uint8).astype(np.int32)
    Pr = bases[rng.integers(0, 4, size=(n_pairs - half, read_len))]
    Tr = bases[rng.integers(0, 4, size=(n_pairs - half, read_len))]
    width = max(P.shape[1], T.shape[1], read_len)

    def fit(a):
        out = np.zeros((a.shape[0], width), np.int32)
        out[:, :a.shape[1]] = a
        return out

    Lr = np.full(n_pairs - half, read_len, np.int32)
    return (np.concatenate([fit(P), fit(Pr)]),
            np.concatenate([plen, Lr]),
            np.concatenate([fit(T), fit(Tr)]),
            np.concatenate([tlen, Lr]))


def _pps(eng, P, plen, T, tlen, n_pairs):
    eng.align_packed(P, plen, T, tlen)           # compile / warm the cache
    sec = time_fn(lambda: eng.align_packed(P, plen, T, tlen).scores,
                  warmup=1, iters=3)
    return n_pairs / sec, sec


def run(pairs: int = 256, read_len: int = 256,
        edit_frac: float = 0.03) -> list[Row]:
    spec = ReadPairSpec(n_pairs=pairs, read_len=read_len,
                        edit_frac=edit_frac, seed=11)
    P, plen, T, tlen = generate_pairs(spec)
    rows: list[Row] = []

    # -- throughput: ring vs fused kernel, edit distance -------------------
    ring = AlignmentEngine(Edit(), backend="ring", edit_frac=edit_frac)
    kern = AlignmentEngine(Edit(), backend="kernel", edit_frac=edit_frac)
    ring_pps, ring_sec = _pps(ring, P, plen, T, tlen, pairs)
    kern_pps, kern_sec = _pps(kern, P, plen, T, tlen, pairs)
    ratio = kern_pps / ring_pps
    rows.append((f"kernelgap/ring-b{pairs}", ring_sec * 1e6,
                 f"{ring_pps:,.0f} pairs/s jnp ring, edit L={read_len}"))
    rows.append((f"kernelgap/kernel-b{pairs}", kern_sec * 1e6,
                 f"{kern_pps:,.0f} pairs/s fused Pallas grid (interpret)"))
    rows.append(("kernelgap/ratio", ratio,
                 f"kernel/ring pairs/s (gate >= "
                 f"{RATIO_GATE * BASELINE_RATIO:.3f} = {RATIO_GATE:.0f}x "
                 f"pre-PR baseline {BASELINE_RATIO:.4f})"))

    # -- parity: scores + CIGARs kernel vs ring on {edit, affine} ----------
    ok = 1.0
    for pen in (Edit(), wfa_paper.pen):
        r = AlignmentEngine(pen, backend="ring").align_packed(
            P, plen, T, tlen, output="cigar")
        k = AlignmentEngine(pen, backend="kernel").align_packed(
            P, plen, T, tlen, output="cigar")
        if not (np.array_equal(r.scores, k.scores)
                and all(np.array_equal(a, b)
                        for a, b in zip(r.cigars, k.cigars))):
            ok = 0.0
    rows.append(("kernelgap/parity", ok,
                 "scores+CIGARs kernel==ring over {edit, affine} "
                 "(gate == 1)"))

    # -- pruning: adaptive+band vs exact on the divergent mix --------------
    Pd, pld, Td, tld = _divergent_mix(pairs, read_len, edit_frac, seed=17)
    exact = AlignmentEngine(wfa_paper.pen, backend="ring", adaptive=False)
    adapt = AlignmentEngine(wfa_paper.pen, backend="ring", adaptive=False,
                            heuristic=AdaptiveBand(),
                            backend_opts={"band_cap": "auto"})
    ex_pps, ex_sec = _pps(exact, Pd, pld, Td, tld, pairs)
    ad_pps, ad_sec = _pps(adapt, Pd, pld, Td, tld, pairs)
    rows.append((f"kernelgap/affine-exact-b{pairs}", ex_sec * 1e6,
                 f"{ex_pps:,.0f} pairs/s exact, divergent mix"))
    rows.append((f"kernelgap/affine-adaptive-b{pairs}", ad_sec * 1e6,
                 f"{ad_pps:,.0f} pairs/s AdaptiveBand + compacting band"))
    rows.append(("kernelgap/adaptive-speedup", ad_pps / ex_pps,
                 "adaptive/exact pairs/s on divergent mix (gate >= 1)"))
    return rows


def _value(rows: list[Row], name: str) -> float:
    for n, v, _ in rows:
        if n == name:
            return v
    raise KeyError(name)


def check(rows: list[Row]) -> list[str]:
    """The CI gate over kernelgap rows (live or from a JSON snapshot)."""
    failures = []
    ratio = _value(rows, "kernelgap/ratio")
    floor = RATIO_GATE * BASELINE_RATIO
    if ratio < floor:
        failures.append(
            f"kernelgap/ratio: kernel at {ratio:.3f}x of ring < {floor:.3f}"
            f" ({RATIO_GATE:.0f}x the pre-PR baseline {BASELINE_RATIO:.4f})")
    if _value(rows, "kernelgap/parity") != 1.0:
        failures.append(
            "kernelgap/parity: kernel scores/CIGARs diverge from ring")
    speedup = _value(rows, "kernelgap/adaptive-speedup")
    if speedup < 1.0:
        failures.append(
            f"kernelgap/adaptive-speedup: {speedup:.2f}x < 1.0 — pruning "
            "must not lose to exact on the divergent mix")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=256)
    ap.add_argument("--read-len", type=int, default=256)
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) unless kernel/ring ratio >= "
                         "10x the pre-PR baseline, kernel parity with "
                         "ring holds, and adaptive >= exact on the "
                         "divergent mix")
    ap.add_argument("--from-json", default=None, metavar="GLOB",
                    help="with --check: gate on the newest matching "
                         "benchmarks.run --json snapshot instead of "
                         "re-running")
    args = ap.parse_args(argv)
    if args.from_json:
        rows = rows_from_json(args.from_json, "kernelgap/")
    else:
        rows = run(pairs=args.pairs, read_len=args.read_len)
        emit(rows)
    if args.check:
        failures = check(rows)
        for f in failures:
            print(f"# kernelgap REGRESSION: {f}", file=sys.stderr)
        if failures:
            if args.from_json:
                from benchmarks.common import snapshot_diff
                for line in snapshot_diff(args.from_json, "kernelgap/"):
                    print(f"# kernelgap {line}", file=sys.stderr)
            return 1
        print("# kernelgap gate passed: ratio >= 10x pre-PR baseline, "
              "kernel==ring parity, adaptive >= exact on divergent mix",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
