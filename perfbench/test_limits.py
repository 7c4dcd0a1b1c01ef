"""``correct`` comes out true for the program, and false for the control and
for each fault a cell can have: a run driven end to end at a small size on
the CPU, with the look for a chip skipped.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/test_limits.py
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import run  # noqa: E402
from perfbench.lib import cell as cells  # noqa: E402
from perfbench.lib import stream  # noqa: E402
from repro.sim import batched  # noqa: E402

SEED = 2**31 + 99
#: each cell of BENCHMARK.json at a size a CPU test can hold (faulted scans
#: of 8+ replicas do not compile on some CPU hosts)
SMALL = {"paper100-mfi.steady": 2, "paper100-mfi-defrag.steady": 2, "paper100-mfi.faulted": 3}


def small(name):
    """The cell at a CPU test's size, its shape that of the seed's first
    candidate stream."""
    c = cells.load(name)
    c.replicas, c.chips = SMALL[name], 1
    c.shape = stream.shape(stream.presample(c.fleet, c.sim, c.replicas, SEED * 2**20))
    return c


@pytest.fixture(autouse=True)
def fresh_programs():
    """Programs traced under a planted fault must not serve other tests."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def correct(cell):
    return run.run(cell, SEED, 0.05, False, require_tpu=False)["correct"]


@pytest.mark.parametrize("name", SMALL)
def test_program_is_correct(name):
    assert correct(small(name))


@pytest.mark.parametrize("name", SMALL)
def test_control_is_not_correct(name, monkeypatch):
    """The reference with bfloat16 sums, in the place of the device scan."""
    import ml_dtypes

    cell = small(name)
    _, ctl, _ = run.replay(cell, SEED * 2**20, ml_dtypes.bfloat16)
    monkeypatch.setattr(batched, "_simulate",
                        lambda events, **kw: (None, batched.EventTrace(**ctl)))
    assert not correct(cell)


def _state_unchanged(monkeypatch):
    step = batched.EngineCore.step
    monkeypatch.setattr(batched.EngineCore, "step", lambda self, st, x: (st, step(self, st, x)[1]))


def _half_batch(monkeypatch):
    agg = batched.BatchedProgram.aggregate

    def half(self, trace):
        h = self.events.pid.shape[1] // 2
        cut = lambda x: x[:, :h]  # noqa: E731
        return agg(self._replace(events=jax.tree.map(cut, self.events)),
                   jax.tree.map(cut, trace))

    monkeypatch.setattr(batched.BatchedProgram, "aggregate", half)


def _answer_altered(monkeypatch):
    sim = batched._simulate

    def altered(events, **kw):
        st, tr = sim(events, **kw)
        ok = np.array(tr.ok)
        e = int(np.flatnonzero(ok[:, 0])[0])
        ok[e, 0] = False
        return st, tr._replace(ok=jnp.asarray(ok))

    monkeypatch.setattr(batched, "_simulate", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _answer_altered],
                         ids=["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("name", SMALL)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    assert not correct(small(name))


#: a fleet, a policy and a mix key that no cell uses, each given as data only
EXTENSIONS = {
    "mixed-fleet.steady": ("paper100-mfi.steady", "mfi", {}),
    "mixed-fleet.defrag": ("paper100-mfi.steady", "mfi-defrag", {}),
    "mixed-fleet.faulted": ("paper100-mfi.faulted", "mfi", {}),
    "mixed-fleet.chunked": ("paper100-mfi.steady", "mfi", {"chunk_size": 256}),
}


@pytest.mark.parametrize("ext", EXTENSIONS)
def test_data_only_extension_is_correct(ext):
    """A configuration file with a mixed fleet of five device models (one of
    12 slices), another policy on it, and a mix key passed through to
    ``api.simulate``: the harness runs and checks each with no code of its
    own changed."""
    import dataclasses

    from perfbench.lib.fleet import Fleet

    base, policy, simulate = EXTENSIONS[ext]
    config = json.loads((ROOT / "perfbench" / "testdata" / "mixed-fleet.json").read_text())
    c = dataclasses.replace(cells.load(base), policy=policy, fleet=Fleet.from_config(config),
                            simulate=simulate, replicas=2, chips=1)
    c.sim = {**config["sim"], **c.sim, "offered_load": 0.95}
    c.shape = stream.shape(stream.presample(c.fleet, c.sim, c.replicas, SEED * 2**20))
    assert correct(c)


def test_check_reads_meaning_not_layout():
    """The program's stream and decisions padded past each replica's
    sentinel, as a bucketed layout would, compare equal; an end slot or a
    GPU changed does not."""
    from types import SimpleNamespace

    from perfbench.lib import check

    cell = small("paper100-mfi.steady")
    s, ref, agg = run.replay(cell, SEED * 2**20)
    pad = 37

    def padded(x, fill):
        x = np.asarray(x)
        return np.concatenate([x, np.full((pad,) + x.shape[1:], fill, x.dtype)])

    events = SimpleNamespace(pid=padded(s.pid, -1))
    meta = SimpleNamespace(slot=padded(s.slot, s.total_slots), end=padded(s.end, 0))
    trace = SimpleNamespace(**{k: padded(v, 0) for k, v in ref.items()})
    nums = check.compare([(events, meta, trace, agg)], s, ref, agg, cell.fleet.num_gpus)
    assert check.passed(nums)
    e, r = np.argwhere(s.pid >= 0)[5]
    meta.end = meta.end.copy()
    meta.end[e, r] += 1
    assert check.compare([(events, meta, trace, agg)], s, ref, agg, 100)["stream_mismatch"] == 1
    meta.end[e, r] -= 1
    trace.gpu = trace.gpu.copy()
    e, r = np.argwhere(ref["ok"])[3]
    trace.gpu[e, r] += 1
    assert check.compare([(events, meta, trace, agg)], s, ref, agg, 100)["decision_mismatch"] == 1


FOUR_DEVICES = r"""
import json, sys
sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[1] + "/src")
import jax, jax.numpy as jnp
from perfbench import run
from perfbench.lib import cell as cells, stream
from repro.sim import batched
assert len(jax.devices()) == 4
c = cells.load("paper100-mfi.steady")
c.replicas, c.chips = 4, 4
c.shape = stream.shape(stream.presample(c.fleet, c.sim, c.replicas, int(sys.argv[2]) * 2**20))
out = {"sound": run.run(c, int(sys.argv[2]), 0.05, False, require_tpu=False)["correct"]}
sim = batched._simulate
def gathered_from_device_0(events, **kw):
    st, tr = sim(events, **kw)
    q = tr.ok.shape[1] // 4
    return st, jax.tree.map(lambda x: jnp.concatenate([x[:, :q]] * 4, axis=1), tr)
batched._simulate = gathered_from_device_0
jax.clear_caches()
out["exchange_left_out"] = run.run(c, int(sys.argv[2]), 0.05, False, require_tpu=False)["correct"]
print(json.dumps(out))
"""


def test_exchange_between_chips_left_out_is_not_correct():
    """The steady cell on four host devices, replicas sharded over them as
    on a four-chip host; the fault hands back device 0's replicas in place
    of every device's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(ROOT), str(SEED)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == {
        "sound": True, "exchange_left_out": False}
