"""Extend trips per pair aligned, from the program's counters.

``kernel_extend_trips_total`` over ``kernel_pairs_total`` in the program's
metrics registry (``repro.obs.metrics``), which the session adds to as
each wave retires.  A trip is one lock-step character fetch over a grid
block's whole front ([pairs, diagonals]), so a block's trips count once
for all of its pairs.  The counters are cumulative per process; one run
is one process, and its two warm-up waves come from the same pool as the
window, so the ratio stands for the window's mix.  No reading when the
backend has no trip counter (it wrote pairs but no trips) or the program
predates these counters (no ``WAVE_COUNTERS``); a program that lists
them but wrote no pairs while pairs came back fails the run."""
from repro.core import session
from repro.obs.metrics import REGISTRY

PAIRS, TRIPS = "kernel_pairs_total", "kernel_extend_trips_total"


def read(ctx):
    pairs = ctx.get("pairs")
    declared = getattr(session, "WAVE_COUNTERS", None)
    if not pairs or len(pairs["score"]) == 0 or declared is None:
        return None
    missing = [n for n in (PAIRS, TRIPS) if n not in declared]
    done = REGISTRY.get(PAIRS)
    if missing or done is None or done.value <= 0:
        raise RuntimeError(f"no {missing or PAIRS!r} in the program's "
                           f"registry for {len(pairs['score'])} pairs "
                           f"returned")
    trips = REGISTRY.get(TRIPS)
    if trips is None:
        return None
    if trips.value <= 0:
        raise RuntimeError(f"{TRIPS!r} is 0 for {done.value:g} pairs")
    return trips.value / done.value
