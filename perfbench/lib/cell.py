"""A cell of ``BENCHMARK.json`` resolved to what one run needs.

A cell names a configuration (``configs/<name>.json``: fleet, device
tables, demand mixes, policy) and a traffic mix (``traffic/<name>.json``:
protocol, load, replicas, faults, and keys for ``api.simulate``).  The
reference finds the policy's file and the protocol's file by name
(``reference/policies/<policy>.py``, ``reference/protocols/<protocol>.py``),
and each per-layer metric is read by ``metrics/<name>.py``.  Nothing here
names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import List, Optional

from perfbench.lib.fleet import Fleet

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "perfbench"


def module(kind: str, name: str):
    """The file ``perfbench/<kind>/<name>.py``, loaded once."""
    path = HERE / kind / f"{name}.py"
    key = "perfbench_" + "".join(c if c.isalnum() else "_" for c in f"{kind}/{name}")
    if key not in sys.modules:
        if not path.exists():
            raise SystemExit(f"perfbench: no {path.relative_to(ROOT)}")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    policy: str
    fleet: Fleet
    sim: dict               # SimConfig fields of the configuration and the mix
    simulate: dict          # further keyword arguments of api.simulate (the mix's)
    replicas: int
    shape: dict             # stream shape every seed is mapped to (stream.program_seed)
    fault: Optional[dict]   # FaultModel fields, faulted mixes only
    end_to_end: List[dict]
    per_layer: List[dict]


def _for(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_file.read_text())
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"perfbench: no workload {name!r} in {bench_file.name}")
    w = found[0]
    config = json.loads((HERE / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), policy=config["policy"],
        fleet=Fleet.from_config(config), sim={**config["sim"], **traffic["sim"]},
        simulate=traffic.get("simulate", {}), replicas=int(traffic["replicas"]),
        shape=traffic["shape"], fault=traffic.get("fault_model"),
        end_to_end=_for(bench["end_to_end"], name), per_layer=_for(bench["per_layer"], name),
    )
