"""Share of the traced window in which no op ran on the device, in
percent, averaged over the devices: 1 - (union of op intervals) / window."""


def read(ctx):
    return 100.0 * ctx["reduction"].idle_share
