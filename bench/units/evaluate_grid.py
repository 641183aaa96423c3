"""Unit kind ``evaluate_grid``: one call of ``repro.engine.evaluate_grid``
over the whole fixed stream and grid, against the mix's ``scenarios`` fresh
markets.

Call k reads the market family's global scenario indices ``base + S k ..``
where ``base`` comes from the seed: every call sees markets it has not
seen, and the spec (hence every compiled program) stays the same, so
nothing compiles after the warm-up calls. ``correct`` compares the mix's
``checked_markets`` markets of the window, drawn from the seed, with the
float64 reference.
"""

from __future__ import annotations

import numpy as np

from repro.engine import evaluate_grid
from repro.engine.scenarios import ScenarioSource, SynthBatch

import reference
import traffic


class _Window(ScenarioSource):
    """S scenarios of one fresh-market spec, read at the global indices
    [start, start + S)."""

    def __init__(self, spec, start: int, n: int):
        self.spec, self.start, self.n_scenarios = spec, start, n
        self.slots_per_unit = spec.slots_per_unit
        self.p_ondemand = spec.p_ondemand
        self.n_slots = spec.n_slots

    def chunks(self, chunk, device=False, mesh=None):
        yield 0, self.n_scenarios, SynthBatch(
            self.spec, self.start, self.start + self.n_scenarios,
            device=device, mesh=mesh)


class Unit:
    """Closed loop of ``evaluate_grid`` calls over one fixed stream."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix = cfg, mix
        self.st = traffic.Stream(cfg)
        self.S = mix["scenarios"]
        self.backend = None
        self.checked = f"markets_checked={mix['checked_markets']}"
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Move the window to the markets of another seed."""
        self.base = traffic.window_base(seed)
        self.results: list = []

    def _first(self, k: int) -> int:
        """Global index of the first scenario of window call k (the warm-up
        calls take the indices before it)."""
        return self.base + self.S * (k + self.mix["warmup_units"])

    def _call(self, first: int):
        return evaluate_grid(self.st.jobs, self.st.policies,
                             _Window(self.st.spec, first, self.S),
                             self.cfg["r_total"], backend=self.mix["backend"])

    def warm(self) -> None:
        """Warm-up calls, on indices the window never reads."""
        for k in range(self.mix["warmup_units"]):
            self.backend = self._call(self.base + self.S * k).backend

    def unit(self, k: int) -> int:
        """Window call k; returns the cells it scored."""
        r = self._call(self._first(k))
        self.backend = r.backend
        self.results.append((k, np.asarray(r.unit_cost)))
        return int(r.unit_cost.size)

    @staticmethod
    def rates(work: int, units: int, window_s: float) -> dict:
        """End-to-end rates of a window: cost cells scored per second."""
        return {"cells_per_s": work / window_s}

    @property
    def shapes(self) -> dict:
        return self.st.shapes(self.S)

    def _sample(self, seed: int):
        """The window's markets the reference re-computes, drawn from the
        seed: (global scenario indices, the program's (n, J, P) costs)."""
        pairs = [(i, s) for i in range(len(self.results))
                 for s in range(self.S)]
        n = min(self.mix["checked_markets"], len(pairs))
        pick = np.random.default_rng([seed, 1]).choice(len(pairs), size=n,
                                                        replace=False)
        pick = [pairs[p] for p in sorted(pick)]
        idx = np.array([self._first(self.results[i][0]) + s
                        for i, s in pick])
        return idx, np.stack([self.results[i][1][s] for i, s in pick])

    def reference(self, idx, dtype=np.float64):
        """Reference unit costs (n, J, P) in the markets of global indices
        ``idx``."""
        return reference.unit_costs(self.cfg, self.st.chains, idx,
                                    self.st.ref_n_slots, dtype=dtype)

    def check(self, seed: int) -> dict:
        """Numbers ``correct`` is decided on: a sample of the window's
        calls drawn from the seed, against the float64 reference."""
        idx, got = self._sample(seed)
        return reference.compare(got, self.reference(idx), self.st.workload)

    def control(self, seed: int, dtype) -> dict:
        """The same numbers for the reference computed in ``dtype`` put in
        the program's place."""
        idx, _ = self._sample(seed)
        return reference.compare(self.reference(idx, dtype),
                                 self.reference(idx), self.st.workload)
