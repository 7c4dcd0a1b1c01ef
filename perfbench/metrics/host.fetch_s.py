"""Host seconds per traced call of the program's ``fetch`` span: the scan's outputs brought back to the host (``device_get``).  Nothing when the program
keeps no record of its spans."""

from perfbench.lib import program


def read(ctx):
    return program.span_seconds(ctx, "fetch")
