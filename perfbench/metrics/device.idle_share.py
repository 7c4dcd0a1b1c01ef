"""Percent of the traced window in which no operation ran on the device:
100 x (1 - busy union / window); the largest over the devices."""

from perfbench.lib import trace as tracelib


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or not tr.window():
        return None
    lo, hi = tr.window()
    return max(100.0 * (1.0 - tracelib.busy(ops, lo, hi) / (hi - lo))
               for ops in tr.devices.values())
