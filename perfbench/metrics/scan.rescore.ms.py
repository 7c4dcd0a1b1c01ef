"""Device milliseconds per call of the scan's rescoring of the GPUs each
event touched (the expire stage's released rows, the commit stage's GPU, a
migration's landing GPU): the ``fragscore`` kernel and its inputs where the
fleet is homogeneous, the base-derived score in jnp on a mixed fleet.

Self time of the traced call's ops that the program's scope map
(``repro.obs.scope_map``, from the executable the call ran) puts under the
``rescore`` scope; the largest over the devices.  Nothing when the program
has no scope map."""

import re
import sys

from perfbench.lib import program

#: the engine's Pallas kernels, each op named after its ``pallas_call``
KERNEL = re.compile(r"^(select_from_base|delta_from_base|migrate_refine|migrate_class|fragscore)\.\d+$")


def scopes(ctx):
    """Device ms per call by the program's sub-scope (``rescore``,
    ``dispatch``, ``none``), with the Pallas kernels under ``dispatch`` apart
    as ``dispatch.kernels``; ``None`` without a scope map.  Read once per
    run (``select.dispatch.ms`` shares it)."""
    def compute():
        o, tr = program.obs(), ctx.trace
        if (o is None or not hasattr(o, "scope_map") or tr is None or not tr.devices
                or not tr.calls() or not ctx.captured):
            return None
        smap = {k: v + ".kernels" if v == "dispatch" and KERNEL.match(k) else v
                for k, v in o.scope_map(ctx.captured[-1][0]).items()}
        out = program.stage_times(tr, smap, len(tr.calls()))
        print(f"scope split ms {out}", file=sys.stderr)
        return out
    return program._memo("scopes", ctx, compute)


def read(ctx):
    return (scopes(ctx) or {}).get("rescore")
