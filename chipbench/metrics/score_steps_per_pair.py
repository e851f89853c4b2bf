"""Score-loop steps per pair aligned, from the program's counters.

``kernel_score_steps_total`` over ``kernel_pairs_total`` in the program's
metrics registry (``repro.obs.metrics``), which the session adds to as
each wave retires.  The Pallas kernel runs one score loop per grid block,
so a block's steps count once for all of its pairs.  The counters are
cumulative per process; one run is one process, and its two warm-up waves
come from the same pool as the window, so the ratio stands for the
window's mix.  A program that predates these counters (no
``WAVE_COUNTERS``) gives no reading; one that lists them but wrote none
while pairs came back fails the run."""
from repro.core import session
from repro.obs.metrics import REGISTRY

PAIRS, STEPS = "kernel_pairs_total", "kernel_score_steps_total"


def read(ctx):
    pairs = ctx.get("pairs")
    declared = getattr(session, "WAVE_COUNTERS", None)
    if not pairs or len(pairs["score"]) == 0 or declared is None:
        return None
    values = {}
    for name in (PAIRS, STEPS):
        counter = REGISTRY.get(name) if name in declared else None
        if counter is None or counter.value <= 0:
            raise RuntimeError(f"no {name!r} in the program's registry for "
                               f"{len(pairs['score'])} pairs returned")
        values[name] = counter.value
    return values[STEPS] / values[PAIRS]
