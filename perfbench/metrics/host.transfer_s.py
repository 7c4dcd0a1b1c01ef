"""Host seconds per traced call of the program's ``transfer`` span: the event stream placed on the device (``jnp.asarray`` and the replica mesh).  Nothing when the program
keeps no record of its spans."""

from perfbench.lib import program


def read(ctx):
    return program.span_seconds(ctx, "transfer")
