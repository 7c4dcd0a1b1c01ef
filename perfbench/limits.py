"""Readings that the limits of ``lib/check.py`` are set from.

    python3 perfbench/limits.py --workload <cell> --seeds 1,2,... --control-seeds 7,8,9

For each seed of ``--seeds``: one whole ``api.simulate`` call of the cell
on the chip, held against the reference (the program's readings: the
lower end of each limit).  For each seed of ``--control-seeds``: the
control (the reference with bfloat16 sums in the program's place) held
against the float64 reference (the upper end).  One JSON line per reading
and a last line with the worst of each.  The benchmark's own runs do not
run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import run  # noqa: E402
from perfbench.lib import cell as cells  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    control = [int(x) for x in args.control_seeds.split(",") if x]
    worst = {"program": {}, "control": {}}
    if seeds:
        run.device.require(cell.chips)
        run.enable_cache()
        api, batched, make_cfg = run.program(cell)
        probe = run.Probe(batched)
        for seed in seeds:
            probe.calls.clear()
            pseed = run.stream.program_seed(cell.fleet, cell.sim, cell.replicas, seed, cell.shape)
            t0 = time.perf_counter()
            api.simulate(cell.policy, make_cfg(pseed), engine="batched", runs=cell.replicas)
            call_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            numbers, _ = run.reference_numbers(cell, pseed, probe)
            print(json.dumps({"side": "program", "seed": seed, "numbers": numbers,
                              "first_call_s": call_s,
                              "reference_s": time.perf_counter() - t0}), flush=True)
            for k, v in numbers.items():
                worst["program"][k] = max(worst["program"].get(k, v), v)
        probe.close()
    for seed in control:
        numbers = run.control_numbers(cell, seed)
        print(json.dumps({"side": "control", "seed": seed, "numbers": numbers}), flush=True)
        for k, v in numbers.items():
            worst["control"][k] = min(worst["control"].get(k, v), v)
    print(json.dumps({"workload": cell.name, "program_max": worst["program"],
                      "control_min": worst["control"], "limits": run.check.LIMITS}))


if __name__ == "__main__":
    main()
