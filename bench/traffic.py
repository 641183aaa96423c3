"""The one traffic generator: turns a configuration and a mix file into
units of work on the program.

A configuration (``configs/<name>.json``) fixes the deployment: the job
stream of the paper's §6.1 (job type, J, arrival law), the policy grid, the
reserved pool r and the market family. A mix (``traffic/<name>.json``) is
data: which kind of unit one unit of work is, how many market scenarios it
scores, how many warm-up units set-up runs and how much of the window the
reference re-computes.

A unit kind is code of its own, ``units/<kind>.py``, found by the mix's
``unit`` name, so that a new kind is a new file. It defines ``Unit(cfg,
mix, seed)`` with:

* ``warm()``            — the warm-up units, on markets the window skips;
* ``unit(k)``           — window unit k; returns the work it completed;
* ``rates(work, units, window_s)`` — the cell's end-to-end rates;
* ``shapes``            — the sizes the per-layer readers need;
* ``backend``           — the engine backend the program resolved;
* ``checked``           — a line saying what the reference re-computes;
* ``reseed(seed)``      — move the window to another seed's markets;
* ``check(seed)``       — the numbers ``correct`` is decided on;
* ``control(seed, dtype)`` — the same numbers for the reference computed in
  ``dtype`` put in the program's place.

The helpers below are shared by the kinds: the frozen stream turned into
the program's chain jobs, the policy grid, and the fresh-market spec.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np

from repro.core.scheduler import Policy
from repro.core.transform import transform
from repro.core.types import DAGJob, Task
from repro.engine import ScenarioSpec

import reference
import stream

__all__ = ["make", "Stream"]

HERE = os.path.dirname(os.path.abspath(__file__))


class Stream:
    """The configuration's job stream and policy grid, as the program and
    the reference each take them."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.dag_jobs = stream.generate(cfg, cfg["n_jobs"], cfg["stream_seed"])
        self.jobs = [transform(DAGJob(
            arrival=d.arrival, deadline=d.deadline,
            tasks=tuple(Task(z=float(z), delta=float(dl))
                        for z, dl in zip(d.z, d.delta)),
            preds=d.preds)) for d in self.dag_jobs]
        self.policies = [Policy(beta=b2, bid=b, beta0=b0)
                         for b2, b, b0 in reference.policy_grid(cfg)]
        self.horizon = max(j.deadline for j in self.jobs) + 1.0
        m = cfg["market"]
        if m["family"] != "fresh":
            raise ValueError(f"market family {m['family']!r}: only 'fresh'")
        self.spec = ScenarioSpec(
            "fresh", self.horizon, 2 ** 31 - 1, seed=m["seed"],
            slots_per_unit=m["slots_per_unit"], p_ondemand=m["p_ondemand"],
            price_mean=m["price_mean"], price_lo=m["price_lo"],
            price_hi=m["price_hi"])
        self._chains = None

    @property
    def chains(self):
        """The reference's own chain pseudo-jobs of the stream."""
        if self._chains is None:
            self._chains = [reference.chain(d.arrival, d.deadline, d.z,
                                            d.delta, d.preds)
                            for d in self.dag_jobs]
        return self._chains

    @property
    def ref_n_slots(self) -> int:
        """The market slot count, worked out by the reference's own chains."""
        horizon = max(c[1] for c in self.chains) + 1.0
        return int(np.ceil(horizon * self.cfg["market"]["slots_per_unit"])) + 1

    @property
    def workload(self) -> np.ndarray:
        return np.array([c[2].sum() for c in self.chains])

    def shapes(self, S: int) -> dict:
        r_total = self.cfg["r_total"]
        return {"J": len(self.jobs), "P": len(self.policies), "S": S,
                "L": max(j.l for j in self.jobs),
                "n_slots": self.spec.n_slots,
                "bids": len({p.bid for p in self.policies}),
                "groups": len({(p.dealloc_param(r_total), p.beta0, p.bid)
                               for p in self.policies})}


def window_base(seed: int) -> int:
    """First global scenario index of a seed's markets."""
    return int(np.random.default_rng(seed).integers(0, 2 ** 30))


@functools.lru_cache(maxsize=None)
def load_kind(name: str):
    """The module of a unit kind (loaded once per process)."""
    path = os.path.join(HERE, "units", name + ".py")
    if not os.path.exists(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, "units"))
                       if f.endswith(".py"))
        raise ValueError(f"unknown unit {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location("bench_unit_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make(cfg: dict, mix: dict, seed: int):
    return load_kind(mix["unit"]).Unit(cfg, mix, seed)
