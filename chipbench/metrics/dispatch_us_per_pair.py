"""Host microseconds of packing and dispatch per pair aligned.

The program's ``wave.pack`` and ``wave.dispatch`` spans (a compile, when
one happens, sits inside the dispatch), as profiler annotations on the
traced run's host plane, clipped to the window and summed, over the pairs
returned in the window.  A program that predates these spans (no
``WAVE_SPANS``) gives no reading; one that lists them but left none in
the window while pairs came back fails the run."""
from repro.core import session

SPANS = ("wave.pack", "wave.dispatch")


def read(ctx):
    pairs = ctx.get("pairs")
    declared = getattr(session, "WAVE_SPANS", None)
    if not pairs or len(pairs["score"]) == 0 or declared is None:
        return None
    renamed = [n for n in SPANS if n not in declared]
    if renamed:
        raise RuntimeError(f"the program writes no {renamed} spans")
    red = ctx["reduction"]
    found, total_ns = set(), 0.0
    for name, start, dur in red.trace["host"]:
        if name in SPANS:
            found.add(name)
            total_ns += max(0.0, min(start + dur, red.hi) - max(start,
                                                                red.lo))
    missing = [n for n in SPANS if n not in found]
    if missing:
        raise RuntimeError(f"no {missing} spans in the window for "
                           f"{len(pairs['score'])} pairs returned")
    return total_ns * 1e-3 / len(pairs["score"])
