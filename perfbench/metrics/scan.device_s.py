"""Device seconds of one whole call: the union of the intervals in which an
operation ran on the device, inside each call's host span, averaged over
the traced calls; the largest over the devices."""

from perfbench.lib import trace as tracelib


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or not tr.calls():
        return None
    calls = tr.calls()
    per_device = [sum(tracelib.busy(ops, s, e) for s, e in calls) / len(calls)
                  for ops in tr.devices.values()]
    return max(per_device) * 1e-9
