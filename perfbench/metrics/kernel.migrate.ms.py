"""Device milliseconds per call of the defrag search's Pallas kernels: the
per-class pass and the victim pass of ``migrate_refine``, matched by
name; the largest over the devices.  Nothing when no such kernel ran."""

from perfbench.lib import trace as tracelib

PATTERN = r"migrate_refine|migrate_class"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or not tr.calls():
        return None
    lo, hi = tr.window()
    times = [tracelib.op_time(ops, PATTERN, lo, hi) for ops in tr.devices.values()]
    times = [t for t in times if t is not None]
    return max(times) * 1e-6 / len(tr.calls()) if times else None
