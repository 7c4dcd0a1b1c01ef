"""Device milliseconds per call of the scan's ops that no ``EngineCore``
stage owns: the loop's carry copies and its own control, and the step's
decode and trace assembly (the stage map's ``none``); the largest over the
devices.  Nothing when the program has no stage map."""

from perfbench.lib import program


def read(ctx):
    return (program.stages(ctx) or {}).get("none")
