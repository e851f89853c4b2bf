"""Device microseconds of the alignment executable per pair aligned.

The alignment executable's module events in the device trace, summed over
devices, over the pairs returned in the traced window."""


def read(ctx):
    pairs = ctx.get("pairs")
    if not pairs or len(pairs["score"]) == 0:
        return None
    secs = ctx["reduction"].module_seconds(ctx["align_module"])
    if secs <= 0:
        # pairs came back, so the executable ran: its module was renamed
        raise RuntimeError(f"no device time under {ctx['align_module']!r} "
                           f"for {len(pairs['score'])} pairs returned")
    return secs * 1e6 / len(pairs["score"])
