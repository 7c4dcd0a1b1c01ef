"""Device milliseconds per call of per-model dispatch around the select,
delta and migrate kernels: the per-group row gathers and padding, the
scatters back and the cross-group merge of the winners, one launch per
distinct device model of the fleet.

Self time of the traced call's ops that the program's scope map
(``repro.obs.scope_map``) puts under the ``dispatch`` scope, less the
Pallas kernels themselves (ops named after their ``pallas_call``); the
largest over the devices.  Nothing when the program has no scope map or no
kernel ran."""

from perfbench.lib import cell


def read(ctx):
    return (cell.module("metrics", "scan.rescore.ms").scopes(ctx) or {}).get("dispatch")
