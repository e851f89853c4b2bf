"""The alignment executable's share of its roofline, in percent.

The least time the chip could take for the pairs returned in the traced
window (``work.least_seconds``: the algorithm's int32 operations over the
peak int32 vector rate, or its bytes over HBM bandwidth, whichever is
larger) over the executable's device time, summed over devices.  A
device missing from ``peaks.json`` is an error."""
import work


def read(ctx):
    pairs = ctx.get("pairs")
    if not pairs or len(pairs["score"]) == 0:
        return None
    secs = ctx["reduction"].module_seconds(ctx["align_module"])
    if secs <= 0:
        # pairs came back, so the executable ran: its module was renamed
        raise RuntimeError(f"no device time under {ctx['align_module']!r} "
                           f"for {len(pairs['score'])} pairs returned")
    kind = ctx["device"]["kind"]
    peaks = ctx["peaks"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    least, bound = work.least_seconds(pairs["plen"], pairs["tlen"],
                                      pairs["score"], pairs["pen"],
                                      pairs["output"], peaks[kind])
    ctx["log"].append(f"wfa_roofline: least time {least:.6f} s, bound by "
                      f"{bound}, over {secs:.6f} device-s")
    return 100.0 * least / secs
