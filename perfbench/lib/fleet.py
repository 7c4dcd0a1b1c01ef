"""A fleet of MIG GPUs as its configuration file states it.

A configuration (``configs/<name>.json``) holds, besides the policy:

* ``classes``: the demand classes, each with its canonical memory slices
  (the paper's Table I sizes, which the load arithmetic normalises by);
* ``devices``: each device model's memory slices and, per demand class,
  the slices its realization takes and its legal anchors (``[]`` where the
  class does not fit the model);
* ``fleet``: ``[model, count]`` entries in GPU id order;
* ``distributions``: the named demand mixes (Table II), one probability
  per class.

Everything the stream generator and the reference know of the hardware is
read from there, so a fleet or device model is a new configuration file.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Fleet:
    class_mem: np.ndarray                     # (P,) canonical memory slices per class
    fleet: List[Tuple[str, int]]              # (model, count) in GPU id order
    models: List[str]                         # distinct models, first-appearance order
    slices: np.ndarray                        # (K,) memory slices of each model
    mem: np.ndarray                           # (K, P) slices of class p's realization on model k
    anchors: List[List[Tuple[int, ...]]]      # [k][p] legal anchors
    model_of: np.ndarray                      # (M,) model index of each GPU
    distributions: Dict[str, np.ndarray]

    @classmethod
    def from_config(cls, config: dict) -> "Fleet":
        class_mem = np.array([c["mem"] for c in config["classes"]], dtype=np.int64)
        fleet = [(str(m), int(n)) for m, n in config["fleet"]]
        models: List[str] = []
        for m, _ in fleet:
            if m not in models:
                models.append(m)
        devs = [config["devices"][m] for m in models]
        for m, d in zip(models, devs):
            if len(d["classes"]) != len(class_mem):
                raise ValueError(f"device {m}: one realization per demand class needed")
        return cls(
            class_mem=class_mem, fleet=fleet, models=models,
            slices=np.array([d["slices"] for d in devs], dtype=np.int64),
            mem=np.array([[c["mem"] for c in d["classes"]] for d in devs], dtype=np.int64),
            anchors=[[tuple(c["anchors"]) for c in d["classes"]] for d in devs],
            model_of=np.concatenate([np.full(n, models.index(m)) for m, n in fleet]),
            distributions={k: np.array(v, dtype=np.float64)
                           for k, v in config["distributions"].items()},
        )

    @property
    def num_gpus(self) -> int:
        return len(self.model_of)

    @property
    def num_classes(self) -> int:
        return len(self.class_mem)

    @property
    def capacity(self) -> int:
        """Memory slices of the whole fleet."""
        return int(self.slices[self.model_of].sum())

    def spec_text(self) -> str:
        """The fleet as ``model:count,...``."""
        return ",".join(f"{m}:{n}" for m, n in self.fleet)

    def probs(self, distribution: str,
              model_distributions: Optional[Dict[str, str]] = None) -> np.ndarray:
        """The fleet-wide demand-class probabilities: the named mix, or with
        per-model mixes their mixture weighted by each model's share of the
        fleet's slices."""
        if not model_distributions:
            return self.distributions[distribution]
        probs = np.zeros(self.num_classes, dtype=np.float64)
        for k, m in enumerate(self.models):
            weight = int((self.model_of == k).sum()) * int(self.slices[k]) / float(self.capacity)
            probs += weight * self.distributions[model_distributions.get(m, distribution)]
        return probs / probs.sum()
