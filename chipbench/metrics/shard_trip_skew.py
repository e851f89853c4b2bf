"""How much longer the slowest shard's extend loop runs than the average
shard's, in percent, from the program's counters.

100 × (``kernel_shard_trips_max_total`` / ``kernel_shard_trips_mean_total``
− 1) in the program's metrics registry (``repro.obs.metrics``).  As each
wave split over more than one shard retires, the session adds the extend
trips of its slowest shard to the first and the mean over its shards to
the second; a wave is done when its slowest shard is.  The counters are
cumulative per process; one run is one process, and its two warm-up
waves come from the same pool as the window, so the ratio stands for the
window's mix.  A program that does not declare these counters in
``WAVE_COUNTERS`` (it predates them) gives no reading; one that declares
them but wrote none while pairs came back fails the run."""
from repro.core import session
from repro.obs.metrics import REGISTRY

MAX, MEAN = "kernel_shard_trips_max_total", "kernel_shard_trips_mean_total"


def read(ctx):
    pairs = ctx.get("pairs")
    declared = getattr(session, "WAVE_COUNTERS", ())
    if (not pairs or len(pairs["score"]) == 0
            or not {MAX, MEAN} <= set(declared)):
        return None
    values = {}
    for name in (MAX, MEAN):
        counter = REGISTRY.get(name)
        if counter is None or counter.value <= 0:
            raise RuntimeError(f"no {name!r} in the program's registry for "
                               f"{len(pairs['score'])} pairs returned")
        values[name] = counter.value
    return 100.0 * (values[MAX] / values[MEAN] - 1.0)
