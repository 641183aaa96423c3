"""Scenario subsystem — declarative specs, on-device market synthesis, and
chunked scenario streams (DESIGN.md §8).

A *scenario* is one realized spot-price path; the engine evaluates the whole
(policy x job) grid against S scenarios in a single pass (the scenario axis
is a batch dimension for the jax backend and a grid dimension for the pallas
kernel). Five families:

* ``fresh``       — i.i.d. redraws of the paper's price law under new seeds
  (sampling noise of the market itself);
* ``regime``      — the price-law mean swept across a range (regime shifts:
  cheap/expensive spot epochs), exercising policies under markets their
  beta grid was not tuned for;
* ``replay``      — recorded per-slot traces (the replay-trace adapter);
* ``adversarial`` — square-wave lure/spike paths built to drive worst-case
  regret for TOLA: long cheap epochs bait the learner toward low-bid,
  spot-heavy policies, then the price spikes to the on-demand ceiling for
  a stretch comparable to a task window, so work sampled into the lure
  lands its window on the spike and pays the full on-demand backstop. The
  spike period is swept across scenarios (no single policy-window length
  is safe), which is what makes the family a regret stress test rather
  than one unlucky trace.
* ``adaptive``    — the adversarial family with the period chosen by
  WATCHING the learner: each chunk's realized regret is fed back through
  ``ScenarioStream.observe`` and the next chunk's spikes concentrate on
  the period that hurt the learner most so far. The round trip is defined
  at the chunk boundary, so the compiled interior stays pure.

Two representations coexist:

* ``list[SpotMarket]`` — the legacy materialized path (``make_scenarios``,
  ``replay_scenarios``): one host Python object per scenario, exact f64.
* ``ScenarioSpec`` — a declarative, hashable description of a family. Its
  randomness is a stateless counter hash of (seed, scenario index, slot),
  NOT numpy's Generator, so any chunk of scenarios can be synthesized
  independently, in any order, on host (f64 — the bit-exact oracle,
  identical to wrapping ``spec.prices()`` rows in ``SpotMarket.from_prices``)
  or on device (one jitted program from PRNG levels to the stacked per-bid
  A/C cumulative tensors; f32 value noise, but per-slot AVAILABILITY is
  decided by an exact integer threshold comparison so no knife-edge slot
  ever flips between the host and device paths).

Both are consumed through ``ScenarioSource.chunks`` — ``(s0, s1, batch)``
triples whose ``ScenarioBatch`` caches the stacked (S_chunk, n_slots+1)
A/C tensors per bid (keyed on ``round(bid, 12)`` like the GridPlan dedup),
so no backend ever restacks a bid's views.

All scenarios of a batch share the slot grid and horizon so their cumulative
arrays stack into one (S, n_slots+1) tensor.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Sequence

import numpy as np

from repro.obs import METRICS, record_jit, span

from repro.core.market import (
    P_ONDEMAND,
    PRICE_HI,
    PRICE_LO,
    PRICE_MEAN,
    SLOTS_PER_UNIT,
    SpotMarket,
    stacked_view_arrays,
)

__all__ = ["ScenarioSpec", "ScenarioStream", "ScenarioBatch",
           "MarketListBatch", "SynthBatch", "as_source",
           "make_scenarios", "adversarial_scenarios", "replay_scenarios",
           "check_scenarios", "stack_views", "SCENARIO_KINDS"]

SCENARIO_KINDS = ("fresh", "regime", "replay", "adversarial", "adaptive")

_M32 = 0xFFFFFFFF
_GOLD = np.uint32(0x9E3779B9)   # odd golden-ratio constants decorrelate the
_COL = np.uint32(0x85EBCA6B)    # row/column/stream counters before mixing
_MIX1 = np.uint32(0x7FEB352D)
_MIX2 = np.uint32(0x846CA68B)


# --------------------------------------------------------------------------
# Counter-based randomness: 24-bit levels from a stateless uint32 hash.
# --------------------------------------------------------------------------

def _mix(x):
    """lowbias32 finalizer, elementwise on numpy OR jax uint32 arrays.

    Pure uint32 arithmetic (wraparound multiplies), so the host f64 oracle
    and the jitted device generator draw bit-identical levels — the entire
    randomness of the spec-based scenario families flows through here.
    """
    x = x ^ (x >> 16)
    x = x * _MIX1
    x = x ^ (x >> 15)
    x = x * _MIX2
    x = x ^ (x >> 16)
    return x


def _mix_int(x: int) -> int:
    """Python-int twin of ``_mix`` (numpy SCALAR uint32 overflow warns)."""
    x &= _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    x ^= x >> 16
    return x


def _levels(seed: int, stream: int, idx, n_cols: int, xp=np):
    """(len(idx), n_cols) uint32 levels in [0, 2^24).

    ``idx`` holds GLOBAL scenario indices, so any chunk reproduces exactly
    the rows a monolithic synthesis would produce — chunked-vs-monolithic
    bit-identity is by construction, not by bookkeeping. 24 bits because
    ``level * 2^-24`` is exactly representable in BOTH f32 and f64: the two
    paths start from identical uniforms.
    """
    base = np.uint32(_mix_int((seed & _M32) ^ ((stream * 0x9E3779B9) & _M32)))
    row = _mix(xp.asarray(idx).astype(xp.uint32) * _GOLD ^ base)
    col = xp.arange(n_cols, dtype=xp.uint32) * _COL
    return _mix(row[:, None] ^ col[None, :]) >> np.uint32(8)


def _exp_prices(u, mean, lo, hi, xp=np):
    """Inverse-CDF shifted-exponential price law, clipped at the ceiling."""
    return xp.minimum(lo + mean * (-xp.log1p(-u)), hi)


@functools.lru_cache(maxsize=4096)  # bounded: distinct bid levels
def _avail_threshold(mean: float, lo: float, hi: float, bid: float) -> int:
    """Largest 24-bit level whose f64 price clears ``bid``.

    Replicates ``price <= bid + 1e-12`` (the SpotMarket availability rule)
    EXACTLY: the analytic inverse-CDF estimate is corrected by walking the
    actual f64 price formula across the boundary, so the device path's
    integer comparison ``level <= threshold`` selects precisely the slots
    the host f64 comparison would — no f32 knife edge can flip a slot.
    """
    b = float(bid) + 1e-12

    def price(h: int) -> float:
        return min(lo + mean * (-np.log1p(-(h * 2.0 ** -24))), hi)

    top = (1 << 24) - 1
    if price(0) > b:
        return -1
    if price(top) <= b:
        return top
    t = int((1.0 - np.exp(-(b - lo) / mean)) * 2.0 ** 24)
    t = max(0, min(t, top - 1))
    while t + 1 <= top and price(t + 1) <= b:
        t += 1
    while t >= 0 and price(t) > b:
        t -= 1
    return t


# --------------------------------------------------------------------------
# ScenarioSpec — the declarative family description.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """Declarative, hashable description of a scenario family.

    A spec fully determines every price path of the family (see the module
    docstring for the counter-hash randomness), so it can serve as a cache
    key, travel between processes, and synthesize any chunk of its
    scenarios on demand — host f64 (``prices`` / ``materialize``, the
    bit-exact oracle) or on device (``SynthBatch``). ``traces`` is only
    used by ``kind="replay"`` (one tuple per scenario, right-padded to the
    longest — see :func:`replay_scenarios` for the padding contract).
    """

    kind: str
    horizon_units: float
    n_scenarios: int
    seed: int = 0
    slots_per_unit: int = SLOTS_PER_UNIT
    p_ondemand: float = P_ONDEMAND
    price_mean: float = PRICE_MEAN
    price_lo: float = PRICE_LO
    price_hi: float = PRICE_HI
    mean_range: tuple = (0.125, 0.22)
    spike_range: tuple = (0.5, 4.0)
    spike_frac: float = 0.5
    n_periods: int = 8              # adaptive: size of the spike-period menu
    n_phases: int = 6               # adaptive: candidate phase offsets
    traces: tuple = ()

    def __post_init__(self):
        if self.kind not in SCENARIO_KINDS:
            raise ValueError(f"unknown scenario kind {self.kind!r}; pick "
                             f"from {SCENARIO_KINDS}")
        if self.n_scenarios < 1:
            raise ValueError("need at least one scenario "
                             f"(n_scenarios={self.n_scenarios})")
        if self.kind == "replay":
            if not self.traces:
                raise ValueError("kind='replay' needs at least one trace")
            object.__setattr__(self, "traces", tuple(
                tuple(float(x) for x in t) for t in self.traces))
            if len(self.traces) != self.n_scenarios:
                raise ValueError(
                    f"replay spec carries {len(self.traces)} traces for "
                    f"{self.n_scenarios} scenarios")
        elif self.traces:
            raise ValueError(f"traces are only valid with kind='replay' "
                             f"(got kind={self.kind!r})")
        object.__setattr__(self, "mean_range", tuple(self.mean_range))
        object.__setattr__(self, "spike_range", tuple(self.spike_range))

    @classmethod
    def from_traces(cls, traces, slots_per_unit: int = SLOTS_PER_UNIT,
                    p_ondemand: float = P_ONDEMAND) -> "ScenarioSpec":
        traces = tuple(tuple(float(x) for x in t) for t in traces)
        if not traces:
            raise ValueError("need at least one trace")
        n = max(len(t) for t in traces)
        return cls(kind="replay", horizon_units=n / slots_per_unit,
                   n_scenarios=len(traces), slots_per_unit=slots_per_unit,
                   p_ondemand=p_ondemand, traces=traces)

    # -- slot-grid geometry (shared with SpotMarket) -----------------------
    @property
    def slot(self) -> float:
        return 1.0 / self.slots_per_unit

    @property
    def n_slots(self) -> int:
        if self.kind == "replay":
            return max(len(t) for t in self.traces)
        return int(np.ceil(self.horizon_units * self.slots_per_unit)) + 1

    @property
    def generative(self) -> bool:
        """Whether price paths come from the counter hash (device-synthesizable)."""
        return self.kind != "replay"

    # -- family parameters over GLOBAL scenario indices --------------------
    def regime_means(self) -> np.ndarray:
        """(S,) price-law mean per scenario of the regime sweep."""
        return np.linspace(*self.mean_range, self.n_scenarios)

    def period_menu(self) -> np.ndarray:
        """Adaptive spike-period menu (time units, geometric over the range)."""
        return np.geomspace(*self.spike_range, self.n_periods)

    def default_periods(self, idx: np.ndarray) -> np.ndarray:
        """Feedback-free spike periods (time units) for global indices.

        ``adversarial`` sweeps the range geometrically across the WHOLE
        batch (mirroring :func:`adversarial_scenarios`); ``adaptive`` with
        no feedback yet cycles its period menu round-robin.
        """
        if self.kind == "adaptive":
            return self.period_menu()[np.asarray(idx) % self.n_periods]
        if self.n_scenarios == 1:
            sweep = np.array([np.sqrt(self.spike_range[0]
                                      * self.spike_range[1])])
        else:
            sweep = np.geomspace(*self.spike_range, self.n_scenarios)
        return sweep[np.asarray(idx)]

    def wave_slots(self, periods: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(period_slots, spike_slots) int arrays from periods in time units."""
        pslots = np.maximum(np.round(np.asarray(periods, np.float64)
                                     * self.slots_per_unit), 2).astype(np.int64)
        sslots = np.maximum(np.round(self.spike_frac * pslots), 1) \
            .astype(np.int64)
        return pslots, sslots

    # -- host synthesis (f64 oracle) ---------------------------------------
    def prices(self, start: int = 0, stop: int | None = None,
               periods: np.ndarray | None = None,
               offsets: np.ndarray | None = None) -> np.ndarray:
        """(stop-start, n_slots) f64 per-slot prices for global scenarios
        ``start..stop-1`` — the bit-exact oracle every other path is tested
        against. ``periods`` overrides the spike periods (time units) of the
        adversarial/adaptive wave for these rows, and ``offsets`` the phase
        offsets in slots (entries < 0 keep the hash-random phase) — the
        ScenarioStream's feedback hooks; other kinds ignore both.
        """
        stop = self.n_scenarios if stop is None else stop
        if not 0 <= start < stop <= self.n_scenarios:
            raise ValueError(f"bad scenario slice [{start}, {stop}) of "
                             f"{self.n_scenarios}")
        idx = np.arange(start, stop)
        n = self.n_slots
        if self.kind == "replay":
            # Padded once per spec (cached): chunked streaming must not
            # re-pad the whole trace set per chunk (O(S^2)) or re-fire the
            # padding warning.
            return _padded_spec_traces(self)[start:stop]
        h = _levels(self.seed, 0, idx, n)
        u = h * 2.0 ** -24
        if self.kind == "fresh":
            return _exp_prices(u, self.price_mean, self.price_lo,
                               self.price_hi)
        if self.kind == "regime":
            means = self.regime_means()[idx][:, None]
            return _exp_prices(u, means, self.price_lo, self.price_hi)
        # adversarial / adaptive: lure from a halved-mean law + spike wave.
        lure = _exp_prices(u, 0.5 * self.price_mean, self.price_lo,
                           self.price_hi)
        if periods is None:
            periods = self.default_periods(idx)
        pslots, sslots = self.wave_slots(periods)
        rand = (_levels(self.seed, 1, idx, 1)[:, 0].astype(np.int64)
                % pslots)
        if offsets is None:
            offs = rand
        else:
            offsets = np.asarray(offsets, np.int64)
            offs = np.where(offsets >= 0, offsets % pslots, rand)
        phase = (np.arange(n)[None, :] + offs[:, None]) % pslots[:, None]
        return np.where(phase < sslots[:, None], self.price_hi, lure)

    def materialize(self, start: int = 0,
                    stop: int | None = None) -> list[SpotMarket]:
        """The spec's scenarios as concrete ``SpotMarket`` objects (today's
        ``from_prices`` path) — the host oracle the streamed/device paths
        are parity-tested against, and the adapter for host-only consumers
        (the greedy baseline, the realized shared-pool replay)."""
        return [SpotMarket.from_prices(row, slots_per_unit=self.slots_per_unit,
                                       p_ondemand=self.p_ondemand)
                for row in self.prices(start, stop)]

    def lure_mean(self) -> float:
        return 0.5 * self.price_mean

    def thresholds(self, bid: float, idx: np.ndarray) -> np.ndarray:
        """(len(idx),) int32 availability thresholds for one bid.

        The exact-integer edition of ``price <= bid + 1e-12`` per scenario
        (regime sweeps get a per-row mean; the spike phases of the
        adversarial families are excluded separately by the wave mask).
        """
        if self.kind == "regime":
            means = self.regime_means()[np.asarray(idx)]
            return np.array([_avail_threshold(float(m), self.price_lo,
                                              self.price_hi, float(bid))
                             for m in means], np.int32)
        mean = self.lure_mean() if self.kind in ("adversarial", "adaptive") \
            else self.price_mean
        t = _avail_threshold(float(mean), self.price_lo, self.price_hi,
                             float(bid))
        return np.full(len(idx), t, np.int32)


# --------------------------------------------------------------------------
# Device synthesis: spec -> (levels, prices, spike mask) -> per-bid views,
# all jitted and cached per spec (ScenarioSpec is hashable by design).
# --------------------------------------------------------------------------

# Bounded: a long-lived process sweeping many specs must not accumulate
# one compiled XLA program per spec forever (LRU eviction caps retention;
# bench_pipeline's synthesis sweep additionally cache_clear()s per size).
@functools.lru_cache(maxsize=32)
def _device_synth_fn(spec: ScenarioSpec, mesh=None):
    """Jitted generator: global indices (+ wave params) -> chunk tensors.

    Returns ``(levels int32 (K, n), prices f32 (K, n), spike bool (K, n))``
    on device. Levels are bit-identical to the host hash; prices are the
    f32 evaluation of the same transform (value noise ~1e-7, harmless —
    availability never reads them, see ``_device_views_fn``).

    With ``mesh`` (a ``GridMesh``) the generator is ``shard_map``ed over
    the scenario axis — ``"data"`` only; on a 2-D mesh the ``"model"``
    axis sees replicated synthesis, since groups don't exist yet at this
    stage. Each shard hashes only its own GLOBAL indices, so per-shard
    synthesis is bit-identical to monolithic by construction and the
    program contains zero cross-device collectives (asserted in
    tests/test_shard.py). Row counts must be padded to ``data_shards`` —
    ``SynthBatch`` owns that contract.
    """
    import jax
    import jax.numpy as jnp

    n = spec.n_slots
    kind = spec.kind
    lo, hi = spec.price_lo, spec.price_hi
    S = spec.n_scenarios

    def gen(idx, pslots, sslots, offsets):
        h = _levels(spec.seed, 0, idx, n, xp=jnp)           # (K, n) uint32
        u = h.astype(jnp.float32) * jnp.float32(2.0 ** -24)
        if kind == "fresh":
            price = _exp_prices(u, spec.price_mean, lo, hi, xp=jnp)
            spike = jnp.zeros(price.shape, bool)
        elif kind == "regime":
            a, b = spec.mean_range
            frac = idx.astype(jnp.float32) / jnp.float32(max(S - 1, 1))
            means = (jnp.float32(a) + jnp.float32(b - a) * frac)[:, None]
            price = _exp_prices(u, means, lo, hi, xp=jnp)
            spike = jnp.zeros(price.shape, bool)
        else:                                               # adversarial*
            lure = _exp_prices(u, spec.lure_mean(), lo, hi, xp=jnp)
            ph = _levels(spec.seed, 1, idx, 1, xp=jnp)[:, 0]
            rand = (ph % pslots.astype(jnp.uint32)).astype(jnp.int32)
            offs = jnp.where(offsets >= 0, offsets % pslots, rand)
            phase = (jnp.arange(n, dtype=jnp.int32)[None, :]
                     + offs[:, None]) % pslots[:, None]
            spike = phase < sslots[:, None]
            price = jnp.where(spike, jnp.float32(hi), lure)
        return h.astype(jnp.int32), price, spike

    if mesh is None:
        return jax.jit(gen)
    dp = mesh.spec("scenario")
    return jax.jit(jax.shard_map(gen, mesh=mesh.mesh,
                                 in_specs=(dp, dp, dp, dp), out_specs=dp))


@functools.lru_cache(maxsize=32)   # bounded: one entry per (slot, mesh)
def _device_views_fn(slot: float, mesh=None):
    """Jitted (levels, prices, spike, thresholds) -> stacked (A, C) views.

    Availability is the EXACT integer comparison ``level <= threshold`` —
    the same slot set the f64 oracle selects (``_avail_threshold``). A_cum
    is exact-count * slot (one f32 rounding, no cumsum drift on the array
    the cost kernels' searchsorted queries are knife-edge-sensitive to);
    C_cum is an f32 cumsum of the payment steps (value-only, tolerance
    covered by the engine's 1e-5 parity contract).

    With ``mesh`` the view build is ``shard_map``ed per scenario shard
    (cumsums run along the SLOT axis, within a row — no cross-scenario,
    hence no cross-device, dependence).
    """
    import jax
    import jax.numpy as jnp

    def views(h, price, spike, thresh, spike_clears):
        avail = (h <= thresh[:, None]) & (~spike | spike_clears)
        counts = jnp.cumsum(avail.astype(jnp.int32), axis=-1)
        pad = jnp.zeros(h.shape[:-1] + (1,), jnp.float32)
        A = jnp.concatenate(
            [pad, counts.astype(jnp.float32) * jnp.float32(slot)], axis=-1)
        # C from the shared traceable twin (one definition of the payment
        # arithmetic); its f32-cumsum A is dead code XLA drops — the exact
        # integer-count A above is what the searchsorted queries consume.
        _, C = stacked_view_arrays(price, avail, slot, xp=jnp)
        return A, C

    if mesh is None:
        return jax.jit(views)
    dp = mesh.spec("scenario")
    rp = mesh.spec()   # empty P(): replicated, valid for rank-0 scalars
    return jax.jit(jax.shard_map(views, mesh=mesh.mesh,
                                 in_specs=(dp, dp, dp, dp, rp),
                                 out_specs=dp))


# --------------------------------------------------------------------------
# Batches — what the backends consume (stacked views, cached per bid).
# --------------------------------------------------------------------------

def _bid_key(bid: float) -> float:
    # Same rounding rule as the GridPlan dedup (plan.py::_bid_key): views
    # cached, listed and looked up on one rounded value.
    return round(float(bid), 12)


def _stack_bid_views(markets: Sequence[SpotMarket], bid: float):
    """The one definition of host per-bid view stacking (one ``view`` call
    per market; both the list and the spec-host batches delegate here)."""
    views = [m.view(bid) for m in markets]
    return (np.stack([v.A_cum for v in views]),
            np.stack([v.C_cum for v in views]))


class ScenarioBatch:
    """One chunk of scenarios presented as stacked per-bid view tensors.

    ``stacked(bid)`` returns the (S_chunk, n_slots+1) A/C cumulative
    arrays, computed once per bid and cached (the no-recompute contract —
    repeated calls hand back the same arrays). ``markets`` lazily adapts
    the chunk to host-only consumers (the numpy oracle backend).

    With a ``GridMesh`` the stacked tensors are padded to ``n_rows``
    (a multiple of ``data_shards``; the last scenario repeated) and placed
    sharded over the mesh's ``"data"`` axis (replicated over ``"model"``)
    — consumers slice results back to ``n_scenarios`` valid rows (the
    DESIGN.md §9 padding contract).
    """

    slot: float
    slots_per_unit: int
    p_ondemand: float
    n_slots: int
    n_scenarios: int
    device: bool = False

    def __init__(self, mesh=None):
        self._stacked: dict[float, tuple] = {}
        self.mesh = mesh

    @property
    def n_rows(self) -> int:
        """Row count of the stacked tensors (padded under a mesh)."""
        if self.mesh is None:
            return self.n_scenarios
        return self.mesh.pad(self.n_scenarios)

    def dispatch(self) -> "ScenarioBatch":
        """Launch (but do not await) the chunk's synthesis — the
        double-buffering hook: a no-op wherever synthesis is host work."""
        return self

    def prepare(self) -> "ScenarioBatch":
        """Synthesize/realize the chunk's price paths (timed by the API)."""
        return self

    def stacked(self, bid: float):
        key = _bid_key(bid)
        if key not in self._stacked:
            from repro.engine import cache as _cache

            # Cross-call reuse (DESIGN.md §11): batches whose views are a
            # pure function of (spec, chunk range, bid) publish a cache
            # key and survive the batch; feedback-driven chunks and meshed
            # batches return None and keep the per-batch memo only.
            ck = self._view_key(bid) if _cache.enabled() else None
            views = _cache.VIEW_CACHE.get(ck) if ck is not None else None
            if views is None:
                A, C = self._build_views(bid)
                if self.mesh is not None and isinstance(A, np.ndarray):
                    # Host-built views under a mesh: pad + place sharded
                    # once, here, so every backend consumes one layout.
                    A, C = self.mesh.put_rows(A), self.mesh.put_rows(C)
                views = (A, C)
                if ck is not None:
                    _cache.VIEW_CACHE.put(ck, views)
            self._stacked[key] = views
        return self._stacked[key]

    def _view_key(self, bid: float):
        """Cross-call identity of this chunk's per-bid views, or None when
        they have none (materialized market lists would need a content
        hash per call; feedback-driven synthesis depends on state outside
        any key; meshed tensors are placed for one device topology)."""
        return None

    def _build_views(self, bid: float):
        raise NotImplementedError

    @property
    def markets(self) -> list[SpotMarket]:
        raise NotImplementedError


class MarketListBatch(ScenarioBatch):
    """Materialized scenarios: a list of ``SpotMarket`` objects."""

    def __init__(self, markets: Sequence[SpotMarket], *, checked=False,
                 mesh=None):
        super().__init__(mesh=mesh)
        self._markets = list(markets)
        if not checked:
            check_scenarios(self._markets)
        m0 = self._markets[0]
        self.slot = m0.slot
        self.slots_per_unit = m0.slots_per_unit
        self.p_ondemand = m0.p_ondemand
        self.n_slots = m0.n_slots
        self.n_scenarios = len(self._markets)

    @property
    def markets(self) -> list[SpotMarket]:
        return self._markets

    def _build_views(self, bid: float):
        return _stack_bid_views(self._markets, bid)


class SynthBatch(ScenarioBatch):
    """A chunk of a ``ScenarioSpec``, synthesized on demand.

    ``device=False`` keeps everything host f64 (prices from the oracle
    hash; ``markets`` wraps them in ``SpotMarket.from_prices`` — bit-exact
    with the materialized path by construction). ``device=True`` runs the
    jitted generator once per chunk and builds per-bid views on device —
    no per-scenario Python objects, no host staging.
    """

    def __init__(self, spec: ScenarioSpec, start: int, stop: int,
                 periods: np.ndarray | None = None,
                 offsets: np.ndarray | None = None, device: bool = False,
                 mesh=None):
        super().__init__(mesh=mesh)
        if device and not spec.generative:
            raise ValueError("replay traces are host data; device synthesis "
                             "supports the generative families only")
        self.spec = spec
        self.start, self.stop = start, stop
        self.device = device
        self.slot = spec.slot
        self.slots_per_unit = spec.slots_per_unit
        self.p_ondemand = spec.p_ondemand
        self.n_slots = spec.n_slots
        self.n_scenarios = stop - start
        self._idx = np.arange(start, stop)
        self._periods = periods
        self._offsets = offsets
        self._parts = None
        self._markets: list[SpotMarket] | None = None

    def _pad(self, a: np.ndarray) -> np.ndarray:
        """Pad a per-scenario parameter row to ``n_rows`` (repeat the last
        entry — the padded rows synthesize a real, duplicated scenario)."""
        if self.mesh is None or len(a) == self.n_rows:
            return a
        return np.concatenate(
            [a, np.repeat(a[-1:], self.n_rows - len(a), axis=0)])

    def dispatch(self) -> "SynthBatch":
        """Launch the device synthesis WITHOUT blocking on the result.

        jax dispatch is async: the synthesis of this chunk runs while the
        caller is still consuming the previous one (the double-buffering
        win ``EngineResult.timings['overlap']`` tracks). ``prepare`` then
        only pays the residual wait. Host synthesis stays synchronous (no
        async runtime to hand it to) and keeps its work in ``prepare``.
        """
        if not self.device or self._parts is not None:
            return self
        import jax.numpy as jnp

        if self.spec.kind in ("adversarial", "adaptive"):
            periods = self._periods if self._periods is not None \
                else self.spec.default_periods(self._idx)
            pslots, sslots = self.spec.wave_slots(periods)
        else:
            pslots = np.full(self.n_scenarios, 2, np.int64)
            sslots = np.ones(self.n_scenarios, np.int64)
        offsets = np.full(self.n_scenarios, -1, np.int64) \
            if self._offsets is None else self._offsets
        fn = _device_synth_fn(self.spec, self.mesh)
        args = (jnp.asarray(self._pad(self._idx), jnp.int32),
                jnp.asarray(self._pad(pslots), jnp.int32),
                jnp.asarray(self._pad(sslots), jnp.int32),
                jnp.asarray(self._pad(offsets), jnp.int32))
        record_jit("scenarios.synth:" + self.spec.kind
                   + (":sharded" if self.mesh is not None else ""),
                   fn, *args)
        with span("synth.dispatch", s0=self.start, s1=self.stop,
                  kind=self.spec.kind):
            self._parts = fn(*args)
        return self

    def prepare(self) -> "SynthBatch":
        if not self.device:
            self.markets  # noqa: B018 — realize the oracle rows (timed)
            return self
        if self._parts is None:
            self.dispatch()
        import jax

        # Under overlap the dispatch already ran during the previous
        # chunk's eval, so this span measures only the RESIDUAL wait — the
        # quantity EngineResult.timings["synth"] reports per chunk.
        with span("synth.wait", s0=self.start, s1=self.stop):
            self._parts = jax.block_until_ready(self._parts)
        return self

    @property
    def markets(self) -> list[SpotMarket]:
        # Oracle rows wrapped in from_prices — bit-exact with the spec's
        # materialized path by construction (same f64 price arrays).
        if self._markets is None:
            self._markets = [
                SpotMarket.from_prices(row,
                                       slots_per_unit=self.slots_per_unit,
                                       p_ondemand=self.p_ondemand)
                for row in self.spec.prices(self.start, self.stop,
                                            periods=self._periods,
                                            offsets=self._offsets)]
        return self._markets

    def _view_key(self, bid: float):
        if self.mesh is not None or self._periods is not None \
                or self._offsets is not None:
            # Explicit periods/offsets mean an adaptive adversary planned
            # this chunk from feedback — no cross-call identity.
            return None
        return (self.spec, self.start, self.stop, self.device,
                _bid_key(bid))

    def _build_views(self, bid: float):
        if not self.device:
            return _stack_bid_views(self.markets, bid)
        import jax
        import jax.numpy as jnp

        self.prepare()
        h, price, spike = self._parts
        thresh = jnp.asarray(
            self.spec.thresholds(bid, self._pad(self._idx)))
        spike_clears = self.spec.price_hi <= bid + 1e-12
        fn = _device_views_fn(self.slot, self.mesh)
        record_jit("scenarios.views"
                   + (":sharded" if self.mesh is not None else ""),
                   fn, h, price, spike, thresh, spike_clears)
        with span("views", bid=bid, s0=self.start, s1=self.stop):
            return jax.block_until_ready(
                fn(h, price, spike, thresh, spike_clears))


# --------------------------------------------------------------------------
# Sources — the chunk streams the engine iterates.
# --------------------------------------------------------------------------

class ScenarioSource:
    """Common protocol: slot-grid metadata + ``chunks(K, device)``."""

    n_scenarios: int
    slots_per_unit: int
    p_ondemand: float
    n_slots: int

    @property
    def slot(self) -> float:
        return 1.0 / self.slots_per_unit

    @property
    def reactive(self) -> bool:
        """True when chunk k+1's CONTENT depends on feedback about chunk k
        (the adaptive adversary) — such a stream cannot be prefetched, so
        the engine's double-buffering is disabled for it."""
        return False

    def chunks(self, chunk: int, device: bool = False, mesh=None):
        raise NotImplementedError

    def observe(self, values: np.ndarray) -> None:
        """Adaptive feedback hook — a no-op for every other source."""

    @property
    def markets(self) -> list[SpotMarket]:
        raise NotImplementedError


class _ListSource(ScenarioSource):
    """Materialized markets, chunked by slicing. The whole-list batch is
    cached so repeated full-batch evaluations (policy sweeps, TOLA
    refinement rounds) reuse the stacked per-bid views across calls."""

    def __init__(self, markets: Sequence[SpotMarket]):
        self._whole = MarketListBatch(markets)
        self.n_scenarios = self._whole.n_scenarios
        self.slots_per_unit = self._whole.slots_per_unit
        self.p_ondemand = self._whole.p_ondemand
        self.n_slots = self._whole.n_slots

    @property
    def markets(self) -> list[SpotMarket]:
        return self._whole.markets

    def chunks(self, chunk: int, device: bool = False, mesh=None):
        S = self.n_scenarios
        if chunk >= S and mesh is None:
            yield 0, S, self._whole
            return
        if chunk >= S:
            # Fresh batch under a mesh: the cached whole-list batch's
            # per-bid views are unsharded host arrays — mixing layouts in
            # one cache would hand a later unsharded call padded tensors.
            yield 0, S, MarketListBatch(self._whole.markets, checked=True,
                                        mesh=mesh)
            return
        for s0 in range(0, S, chunk):
            s1 = min(s0 + chunk, S)
            yield s0, s1, MarketListBatch(self._whole.markets[s0:s1],
                                          checked=True, mesh=mesh)


class ScenarioStream(ScenarioSource):
    """Chunk stream over a ``ScenarioSpec`` — stateful only for ``adaptive``.

    The adaptive adversary watches the learner through
    ``observe(regret_per_scenario)`` at every chunk boundary and escalates
    in three stages:

    1. **period sweep** — the spec's geometric period menu round-robin
       (random phases), until every period has been observed at least once;
    2. **phase sweep** — all spikes at the period with the highest mean
       observed regret, cycling ``n_phases`` evenly spaced phase offsets —
       the lever no FIXED square-wave family has (their phases are
       randomized), which is what lets the adaptive family's realized
       regret exceed the best fixed member on the same scenario budget;
    3. **locked** — every remaining scenario plays the (period, phase)
       cell with the highest mean observed regret, still accumulating
       statistics.

    The round trip happens strictly at chunk boundaries, so the synthesized
    interior of every chunk stays a pure function of
    (spec, indices, periods, offsets) — compiled code never sees the
    adversary's state.
    """

    def __init__(self, spec: ScenarioSpec):
        self.spec = spec
        self.n_scenarios = spec.n_scenarios
        self.slots_per_unit = spec.slots_per_unit
        self.p_ondemand = spec.p_ondemand
        self.n_slots = spec.n_slots
        self._menu = spec.period_menu() if spec.kind == "adaptive" else None
        self._p_harm = np.zeros(spec.n_periods)
        self._p_count = np.zeros(spec.n_periods, np.int64)
        self._f_harm = np.zeros(spec.n_phases)
        self._f_count = np.zeros(spec.n_phases, np.int64)
        self._locked_period: int | None = None
        self._pending: tuple[str, np.ndarray] | None = None
        self._last_stage: str | None = None
        self.chunk_periods: list[np.ndarray] = []  # audit trail (time units)
        self.chunk_offsets: list[np.ndarray] = []  # audit trail (slots)
        self._materialized: list[SpotMarket] | None = None

    @property
    def markets(self) -> list[SpotMarket]:
        """Full materialization with DEFAULT (feedback-free) periods —
        host-only consumers; the streamed chunks are the real path."""
        if self._materialized is None:
            self._materialized = self.spec.materialize()
        return self._materialized

    @property
    def stage(self) -> str:
        if self.spec.kind != "adaptive":
            return "stateless"
        if np.any(self._p_count == 0):
            return "periods"
        if np.any(self._f_count == 0):
            return "phases"
        return "locked"

    def _phase_candidates(self, period_idx: int) -> np.ndarray:
        pslots = int(self.spec.wave_slots(self._menu[[period_idx]])[0][0])
        return (np.arange(self.spec.n_phases) * pslots
                // self.spec.n_phases).astype(np.int64)

    def _best_period(self) -> int:
        mean = np.where(self._p_count > 0,
                        self._p_harm / np.maximum(self._p_count, 1), -np.inf)
        return int(np.argmax(mean))

    def _plan_chunk(self, idx: np.ndarray):
        if self.spec.kind != "adaptive":
            return None, None
        stage = self.stage
        if METRICS.enabled:
            METRICS.counter("scenarios.adaptive_chunks").inc(stage=stage)
            if self._last_stage is not None and stage != self._last_stage:
                METRICS.counter("scenarios.adaptive_escalations").inc(
                    to=stage)
        self._last_stage = stage
        if stage == "periods":
            menu_idx = idx % self.spec.n_periods
            periods = self._menu[menu_idx]
            offsets = np.full(len(idx), -1, np.int64)   # hash-random phases
            self._pending = ("periods", menu_idx)
        else:
            p = self._best_period()
            if self._locked_period != p:
                # (Re)target the phase stats at the current worst period —
                # offsets are period-relative, stale stats would lie.
                self._locked_period = p
                self._f_harm[:] = 0.0
                self._f_count[:] = 0
            cand = self._phase_candidates(p)
            if np.any(self._f_count == 0):              # phase sweep
                phase_idx = idx % self.spec.n_phases
            else:                                       # locked
                mean = np.where(self._f_count > 0, self._f_harm
                                / np.maximum(self._f_count, 1), -np.inf)
                phase_idx = np.full(len(idx), int(np.argmax(mean)))
            periods = self._menu[np.full(len(idx), p)]
            offsets = cand[phase_idx]
            self._pending = ("phases", phase_idx)
        self.chunk_periods.append(periods)
        self.chunk_offsets.append(offsets)
        return periods, offsets

    def observe(self, values: np.ndarray) -> None:
        """Feed back per-scenario learner regret for the LAST issued chunk."""
        if self.spec.kind != "adaptive" or self._pending is None:
            return
        kind, cells = self._pending
        values = np.asarray(values, np.float64)
        if len(values) != len(cells):
            raise ValueError(
                f"observe got {len(values)} values for a chunk of "
                f"{len(cells)} scenarios")
        if kind == "periods":
            np.add.at(self._p_harm, cells, values)
            np.add.at(self._p_count, cells, 1)
        else:
            np.add.at(self._f_harm, cells, values)
            np.add.at(self._f_count, cells, 1)
            # Phase-stage scenarios also refine the period estimate.
            self._p_harm[self._locked_period] += values.sum()
            self._p_count[self._locked_period] += len(values)
        self._pending = None

    @property
    def reactive(self) -> bool:
        return self.spec.kind == "adaptive"

    def chunks(self, chunk: int, device: bool = False, mesh=None):
        S = self.n_scenarios
        device = device and self.spec.generative
        for s0 in range(0, S, chunk):
            s1 = min(s0 + chunk, S)
            periods, offsets = self._plan_chunk(np.arange(s0, s1))
            yield s0, s1, SynthBatch(self.spec, s0, s1, periods=periods,
                                     offsets=offsets, device=device,
                                     mesh=mesh)


def as_source(scenarios) -> ScenarioSource:
    """Normalize any accepted scenario argument into a ``ScenarioSource``.

    Accepts a ``ScenarioSource`` (passed through — this is how a stateful
    adaptive stream survives across engine calls), a ``ScenarioSpec``, a
    single ``SpotMarket``, or a sequence of them.
    """
    if isinstance(scenarios, ScenarioSource):
        return scenarios
    if isinstance(scenarios, ScenarioSpec):
        return ScenarioStream(scenarios)
    if isinstance(scenarios, SpotMarket):
        return _ListSource([scenarios])
    return _ListSource(list(scenarios))


# --------------------------------------------------------------------------
# Materialized-list constructors (the legacy families).
# --------------------------------------------------------------------------

def make_scenarios(
    horizon_units: float,
    n_scenarios: int,
    seed: int = 0,
    kind: str = "fresh",
    price_model: str = "shifted",
    mean_range: tuple[float, float] = (0.125, 0.22),
    spike_range: tuple[float, float] = (0.5, 4.0),
    spike_frac: float = 0.5,
) -> list[SpotMarket]:
    """Build S materialized markets over a common horizon (legacy path).

    ``kind="fresh"``: same price law, seeds seed..seed+S-1.
    ``kind="regime"``: price mean swept linearly over ``mean_range`` (one
    regime per scenario, fresh seed each) — with ``price_model="truncate"``
    this is the truncated-exp regime sweep; the default "shifted" model keeps
    the paper's reading of the price law (DESIGN.md §4).
    ``kind="adversarial"``: lure/spike square waves — the spike period is
    swept geometrically over ``spike_range`` (time units, bracketing the
    Dealloc window lengths of the paper's policy grid) with ``spike_frac``
    of each period pinned at the on-demand ceiling; the cheap epochs draw
    from a halved-mean price law so every bid of the grid clears during the
    lure and none clears inside the spike.

    This family keeps numpy's ``Generator`` streams (bit-compatible with
    every earlier PR); declarative, chunkable, device-synthesizable
    families live in :class:`ScenarioSpec` (``kind="adaptive"`` only exists
    there — it needs the chunk-boundary feedback of a stream).
    """
    if n_scenarios < 1:
        raise ValueError("need at least one scenario")
    if kind == "fresh":
        return [SpotMarket(horizon_units, seed=seed + s,
                           price_model=price_model)
                for s in range(n_scenarios)]
    if kind == "regime":
        means = np.linspace(*mean_range, n_scenarios)
        return [SpotMarket(horizon_units, seed=seed + s,
                           price_mean=float(means[s]),
                           price_model=price_model)
                for s in range(n_scenarios)]
    if kind == "adversarial":
        return adversarial_scenarios(horizon_units, n_scenarios, seed=seed,
                                     spike_range=spike_range,
                                     spike_frac=spike_frac)
    if kind == "adaptive":
        raise ValueError(
            "kind='adaptive' needs chunk-boundary feedback — build a "
            "ScenarioSpec(kind='adaptive', ...) and stream it (e.g. "
            "repro.learn.replay_stream) instead of materializing a list")
    raise ValueError(f"unknown scenario kind {kind!r}")


def adversarial_scenarios(
    horizon_units: float,
    n_scenarios: int,
    seed: int = 0,
    slots_per_unit: int | None = None,
    spike_range: tuple[float, float] = (0.5, 4.0),
    spike_frac: float = 0.5,
) -> list[SpotMarket]:
    """Worst-case-regret price paths (ROADMAP scenario family).

    Scenario s is a square wave with period ``P_s`` (geometric sweep over
    ``spike_range`` time units): a cheap *lure* phase whose prices are drawn
    from the paper's law with half the usual mean (so every bid in B
    clears and spot looks like free money to the learner), then a *spike*
    phase of ``spike_frac * P_s`` pinned at ``PRICE_HI`` — above every bid,
    so any task whose Dealloc window straddles the spike exhausts its
    flexibility against zero availability and pays the on-demand backstop
    for the remainder. Phase offsets are randomized per scenario so job
    arrivals cannot be systematically in phase with the lure.
    """
    from repro.core.market import SLOTS_PER_UNIT

    if n_scenarios < 1:
        raise ValueError("need at least one scenario")
    spu = slots_per_unit or SLOTS_PER_UNIT
    n_slots = int(np.ceil(horizon_units * spu)) + 1
    if n_scenarios == 1:
        periods = [float(np.sqrt(spike_range[0] * spike_range[1]))]
    else:
        periods = np.geomspace(*spike_range, n_scenarios)
    markets = []
    for s in range(n_scenarios):
        rng = np.random.default_rng(seed + s)
        lure = np.minimum(PRICE_LO + rng.exponential(0.5 * PRICE_MEAN,
                                                     n_slots), PRICE_HI)
        period_slots = max(int(round(periods[s] * spu)), 2)
        spike_slots = max(int(round(spike_frac * period_slots)), 1)
        phase = (np.arange(n_slots) + rng.integers(period_slots)) \
            % period_slots
        price = np.where(phase < spike_slots, PRICE_HI, lure)
        markets.append(SpotMarket.from_prices(price, slots_per_unit=spu))
    return markets


@functools.lru_cache(maxsize=8)   # bounded — replay specs can carry big traces
def _padded_spec_traces(spec: "ScenarioSpec") -> np.ndarray:
    """(S, n_slots) padded trace rows of a replay spec, built once."""
    return _pad_traces(list(spec.traces), spec.n_slots,
                       max(spec.price_hi, spec.p_ondemand))


def _pad_traces(traces: list, n: int, pad_price: float) -> np.ndarray:
    """(len(traces), n) f64 rows, right-padded; warns naming the padding."""
    out = np.empty((len(traces), n))
    short = 0
    padded_slots = 0
    for i, t in enumerate(traces):
        t = np.asarray(t, dtype=np.float64)
        if len(t) < n:
            short += 1
            padded_slots += n - len(t)
            t = np.concatenate([t, np.full(n - len(t), pad_price)])
        out[i] = t
    if short:
        warnings.warn(
            f"replay traces right-padded to the longest ({n} slots): "
            f"{short} trace(s) padded with {padded_slots} total slots at "
            f"price {pad_price} (spot never clears there — padded tail "
            f"work pays the on-demand backstop)", stacklevel=3)
    return out


def replay_scenarios(
    traces: Sequence[np.ndarray],
    slots_per_unit: int = 12,
    p_ondemand: float = 1.0,
) -> list[SpotMarket]:
    """Replay-trace adapter: one scenario per recorded per-slot price trace.

    Padding contract: all scenarios of a batch must share one slot grid, so
    traces shorter than the longest are right-padded with
    ``max(PRICE_HI, p_ondemand)`` — a price above every bid, i.e. spot is
    never available in the padded tail and any work scheduled there pays
    the on-demand backstop. A ``UserWarning`` names how many traces/slots
    were padded; pre-trim or pre-extend traces to silence it.
    """
    if not traces:
        raise ValueError("need at least one trace")
    n = max(len(t) for t in traces)
    padded = _pad_traces(list(traces), n, max(PRICE_HI, p_ondemand))
    return [SpotMarket.from_prices(row, slots_per_unit=slots_per_unit,
                                   p_ondemand=p_ondemand)
            for row in padded]


def check_scenarios(markets: Sequence[SpotMarket]) -> None:
    """Scenarios of one batch must share the slot grid and horizon."""
    if len(markets) == 0:
        raise ValueError(
            "scenario batch is empty: 'markets' needs at least one "
            "SpotMarket (or pass a ScenarioSpec)")
    m0 = markets[0]
    for m in markets[1:]:
        if m.n_slots != m0.n_slots or m.slots_per_unit != m0.slots_per_unit:
            raise ValueError(
                "scenario markets must share slot grid and horizon "
                f"(got n_slots {m.n_slots} vs {m0.n_slots})")
        if abs(m.p_ondemand - m0.p_ondemand) > 1e-12:
            raise ValueError("scenario markets must share p_ondemand")


def stack_views(markets: Sequence[SpotMarket], bid: float):
    """(S, n_slots+1) stacked A/C cumulative arrays for one bid.

    One-shot utility; the engine's backends go through ``ScenarioBatch``
    instead, whose per-bid cache avoids restacking across calls."""
    return MarketListBatch(markets).stacked(bid)
