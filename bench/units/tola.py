"""Unit kind ``tola``: one run of ``repro.core.run_tola_scenarios``, the
paper's Alg. 4 from a job stream in to chosen policies out, over the mix's
``scenarios`` fresh markets.

Run k reads the market family's global scenario indices ``first = base +
S (k + warm-up runs) ..`` (``base`` from the seed), made into ``SpotMarket``
objects in set-up for the first ``premade_units`` runs (later runs make
theirs when they start), and its learner in market s draws from the seed
``first + s``. Every run has the same shapes, so nothing compiles after the
warm-up runs.

``correct`` re-computes ``checked_units`` of the window's runs, drawn from
the seed, with the reference's own Alg. 4 (``reference.tola``): both
engine rounds' cost tensors, the drawn policies, the final weights and the
realized run against the shared pool. The program hands back only the last
round's costs, so while a run lasts the engine's entry point is wrapped to
keep each round's ``unit_cost`` as it returns.
"""

from __future__ import annotations

import numpy as np

import repro.engine
from repro.core import run_tola_scenarios

import reference
import traffic


class Unit:
    """Closed loop of TOLA runs over one fixed stream."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix = cfg, mix
        self.st = traffic.Stream(cfg)
        self.S = mix["scenarios"]
        self.backend = None
        self.checked = (f"runs_checked={mix['checked_units']} "
                        f"markets_each={self.S}")
        self._rounds: list | None = None
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Move the window to the markets of another seed."""
        self.base = traffic.window_base(seed)
        self.results: list = []
        n = self.S * (self.mix["warmup_units"] + self.mix["premade_units"])
        self._made = self.st.spec.materialize(self.base, self.base + n)

    def _first(self, k: int) -> int:
        """Global index of the first market of window run k (the warm-up
        runs take the indices before it)."""
        return self.base + self.S * (k + self.mix["warmup_units"])

    def _markets(self, first: int):
        i = first - self.base
        if i + self.S <= len(self._made):
            return self._made[i:i + self.S]
        return self.st.spec.materialize(first, first + self.S)

    def _run(self, first: int):
        engine = repro.engine
        inner, rounds = engine.evaluate_grid, self._rounds

        def evaluate_grid(*a, **kw):   # keeps each round's costs
            r = inner(*a, **kw)
            self.backend = r.backend
            if rounds is not None:
                rounds.append(np.asarray(r.unit_cost))
            return r

        engine.evaluate_grid = evaluate_grid
        try:
            return run_tola_scenarios(
                self.st.jobs, self.st.policies, self._markets(first),
                self.cfg["r_total"], seed=first,
                pool_iters=self.mix["pool_iters"],
                backend=self.mix["backend"], learner=self.mix["learner"])
        finally:
            engine.evaluate_grid = inner

    def warm(self) -> None:
        """Warm-up runs, on markets the window never reads."""
        for k in range(self.mix["warmup_units"]):
            self._run(self.base + self.S * k)

    def unit(self, k: int) -> int:
        """Window run k; returns 1, the runs it completed."""
        self._rounds = []
        try:
            res = self._run(self._first(k))
            self.results.append((k, {
                "C": self._rounds,
                "chosen": np.stack([r.chosen for r in res]),
                "weights": np.stack([r.weights for r in res]),
                "cost": np.stack([r.realized.total_cost for r in res]),
                "selfowned": np.stack([r.realized.selfowned_work
                                       for r in res])}))
        finally:
            self._rounds = None
        return 1

    @staticmethod
    def rates(work: int, units: int, window_s: float) -> dict:
        """End-to-end rates of a window: seconds per completed TOLA run."""
        return {"tola_s": window_s / units}

    @property
    def shapes(self) -> dict:
        return self.st.shapes(self.S)

    def _sample(self, seed: int):
        """The window's runs the reference re-computes, drawn from the
        seed: [(first market index, the program's answers)]."""
        n = min(self.mix["checked_units"], len(self.results))
        pick = np.random.default_rng([seed, 1]).choice(len(self.results),
                                                        size=n, replace=False)
        return [(self._first(self.results[i][0]), self.results[i][1])
                for i in sorted(pick)]

    def reference(self, first: int, dtype=np.float64) -> dict:
        idx = first + np.arange(self.S)
        return reference.tola(self.cfg, self.st.chains, idx, idx,
                              self.st.ref_n_slots, dtype=dtype)

    @staticmethod
    def _worst(readings: list) -> dict:
        return {k: max(r[k] for r in readings) for k in readings[0]}

    def check(self, seed: int) -> dict:
        """Numbers ``correct`` is decided on: the sampled runs against the
        float64 reference, the worst over them."""
        return self._worst([
            reference.compare_tola(got, self.reference(first),
                                   self.st.workload)
            for first, got in self._sample(seed)])

    def control(self, seed: int, dtype) -> dict:
        """The same numbers for the reference with its cost tensors in
        ``dtype`` put in the program's place."""
        return self._worst([
            reference.compare_tola(self.reference(first, dtype),
                                   self.reference(first), self.st.workload)
            for first, _ in self._sample(seed)])
