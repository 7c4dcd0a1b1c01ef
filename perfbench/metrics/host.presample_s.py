"""Host seconds of ``batched_program`` for the cell's configuration: the
presampling of every replica's stream on the host, plus validation.

Host clock around the harness's own call, after the traced window."""

import time


def read(ctx):
    t0 = time.perf_counter()
    ctx.batched.batched_program(ctx.cell.policy, ctx.make_cfg(), ctx.cell.replicas)
    return time.perf_counter() - t0
