"""Device milliseconds per call of the scan's ``stage.select`` ops: the placement choice (the fused `select_from_base` kernel, or the jnp select and `delta_from_base` on the faulted path).

Self time of the traced call's ops that the program's stage map
(``repro.obs.stage_map``, from the executable the call ran) gives to this
stage; the largest over the devices.  Nothing when the program has no
stage map or the stage did not run."""

from perfbench.lib import program


def read(ctx):
    return (program.stages(ctx) or {}).get("select")
