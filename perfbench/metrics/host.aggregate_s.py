"""Host seconds of ``BatchedProgram.aggregate`` on the last call's fetched
trace: the host reduction that turns per-event decisions into the numbers
``api.simulate`` returns.  Host clock, after the traced window."""

import time


def read(ctx):
    if not ctx.captured:
        return None
    prog, trace, _ = ctx.captured[-1]
    t0 = time.perf_counter()
    prog.aggregate(trace)
    return time.perf_counter() - t0
