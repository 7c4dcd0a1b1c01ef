"""Percent of the traced window in which the device is idle and none of the
program's phase spans (``presample``, ``transfer``, ``dispatch``, ``scan``,
``fetch``, ``aggregate``; the root ``run_batched`` does not count) is open:
idle time the program's own spans do not explain.  The spans go onto the
trace's clock by the ``aggregate`` anchor (``lib/program.py``); the largest
over the devices.  Nothing when the program keeps no record of its spans."""

from perfbench.lib import program


def read(ctx):
    spans = program.phase_spans(ctx)
    return None if spans is None else program.idle_unattributed(ctx.trace, spans)
