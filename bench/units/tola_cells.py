"""Unit kind ``tola_cells``: the ``tola`` kind's closed loop of TOLA runs,
counted in the cost cells each run scored, so that a TOLA cell reports the
benchmark's ``cells_per_s``.

A run of ``run_tola_scenarios`` scores every job under every policy in
every market once per engine round: J x P x S cells in round 0 and as many
again in each of the ``pool_iters`` refinement rounds (none without a
self-owned pool). Cells per second over the window is then a constant
multiple of completed runs per second: what is timed, checked and traced
is the ``tola`` kind's, unchanged.
"""

from __future__ import annotations

import traffic

_Tola = traffic.load_kind("tola").Unit


class Unit(_Tola):
    """TOLA runs, each counted as the cost cells of its engine rounds."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        super().__init__(cfg, mix, seed)
        self.rounds = 1 + (mix["pool_iters"] if cfg["r_total"] > 0 else 0)
        sh = self.st.shapes(self.S)
        self.cells = sh["J"] * sh["P"] * self.S * self.rounds

    def unit(self, k: int) -> int:
        """Window run k; returns the cost cells it scored."""
        super().unit(k)
        return self.cells

    @staticmethod
    def rates(work: int, units: int, window_s: float) -> dict:
        """End-to-end rates of a window: cost cells scored per second."""
        return {"cells_per_s": work / window_s}

    @property
    def shapes(self) -> dict:
        return dict(super().shapes, rounds=self.rounds)
