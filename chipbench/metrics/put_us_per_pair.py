"""Host microseconds of the host-to-device copy per pair aligned.

The program's ``wave.put`` spans (the wave's ``device_put``, split over
every device of the mesh, inside ``wave.dispatch``), as profiler
annotations on the traced run's host plane, clipped to the window and
summed, over the pairs returned in the window.  A ``device_put`` returns
once the copies are enqueued, so this is the host's share of the copy.
A program that does not declare the span in ``WAVE_SPANS`` (it predates
it) gives no reading; one that declares it but left none in the window
while pairs came back fails the run."""
from repro.core import session

SPAN = "wave.put"


def read(ctx):
    pairs = ctx.get("pairs")
    if (not pairs or len(pairs["score"]) == 0
            or SPAN not in getattr(session, "WAVE_SPANS", ())):
        return None
    red = ctx["reduction"]
    found, total_ns = 0, 0.0
    for name, start, dur in red.trace["host"]:
        if name == SPAN:
            inside = min(start + dur, red.hi) - max(start, red.lo)
            if inside > 0:
                found += 1
                total_ns += inside
    if not found:
        raise RuntimeError(f"no {SPAN!r} spans in the window for "
                           f"{len(pairs['score'])} pairs returned")
    return total_ns * 1e-3 / len(pairs["score"])
