"""Seconds the program spent compiling, from its counter.

``engine_compile_seconds_total`` in the program's metrics registry
(``repro.obs.metrics``): host seconds inside the first call of each
executable, where JAX traces and compiles it or reads its persistent
cache (the session's ``wave.compile`` spans).  The counter is cumulative
per process; one run is one process, and no compile falls inside the
window, so this is the part of the run's set-up spent compiling.  A
program that predates the counter (no ``WAVE_COUNTERS``) gives no
reading; one that lists it but counted nothing while pairs came back
fails the run."""
from repro.core import session
from repro.obs.metrics import REGISTRY

COMPILE = "engine_compile_seconds_total"


def read(ctx):
    pairs = ctx.get("pairs")
    declared = getattr(session, "WAVE_COUNTERS", None)
    if not pairs or len(pairs["score"]) == 0 or declared is None:
        return None
    counter = REGISTRY.get(COMPILE) if COMPILE in declared else None
    if counter is None or counter.value <= 0:
        raise RuntimeError(f"no {COMPILE!r} in the program's registry for "
                           f"{len(pairs['score'])} pairs returned")
    return counter.value
