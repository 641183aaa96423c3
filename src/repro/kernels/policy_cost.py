"""Pallas TPU kernel for the paper's evaluation hot loop: batched
closed-form task-cost evaluation (Definition 3.2 over a realized market).

TOLA (Alg. 4) scores every job under every policy of the grid — O(n_jobs x
n_policies) independent closed-form task simulations, each a pair of
monotone piecewise-linear inversions over the market's cumulative arrays
(A = availability time, C = spot payment, H = t - A; see
core/simulate.py). That inner evaluation is this kernel.

TPU adaptation (vs the numpy searchsorted implementation):
  * the three cumulative arrays of one (bid, scenario) are stacked into one
    (3, n_pad) VMEM block (~0.4 MB of data per array at ~30k slots),
    loaded once per grid cell;
  * searchsorted becomes a comparison-count reduction (monotone array:
    index = #{k : cum[k] < target}) and every point gather (cum[k0],
    cum[k0+1], ...) a masked select-and-sum, both swept 128-lane tile by
    tile with a fori_loop — no data-dependent control flow, exact in f32;
  * task rows live on SUBLANES as (BT, 1) columns, so one sweep step
    compares a (1, 128) lane tile of the slot arrays against BT row
    targets and accumulates into (BT, 128) lane partials that are reduced
    across lanes once per sweep.  Row vectors enter and leave in their
    lane-dense HBM layout and are turned into columns (and back) with a
    diagonal select-and-reduce, which Mosaic lowers to plain VPU/XLU ops.

Block shapes follow the TPU tiling rule: every block's last two dims are
multiples of (8, 128) or the whole array dims (leading dims squeezed with
``None``).

Oracle: kernels/ref.py::policy_cost_ref / chain_costs_ref (vectorized jnp)
and core/simulate.py (numpy, exact) — see tests/test_kernels.py and
tests/test_plan_batch.py; tests/test_tpu_compile.py compiles both kernels
for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.simulate import FLEX_ABS as _FLEX_ABS
from repro.core.simulate import FLEX_REL as _FLEX_REL

__all__ = ["policy_cost", "policy_cost_chain"]

_LANES = 128
_UNROLL = 4                       # lane tiles per sweep-loop iteration
_CHUNK = _LANES * _UNROLL         # slot arrays are padded to this multiple
_BIG = 3.4e38                     # pad value: above every query target
_A, _C, _H = 0, 1, 2              # rows of the stacked (3, n_pad) slot block


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def to_col(row, n: int):
    """(1, n) lane vector -> (n, 1) sublane column (any dtype)."""
    zero = jnp.zeros((), row.dtype)
    return jnp.sum(jnp.where(_eye(n), row, zero), axis=1, keepdims=True)


def to_row(col, n: int):
    """(n, 1) sublane column -> (1, n) lane vector (any dtype)."""
    zero = jnp.zeros((), col.dtype)
    return jnp.sum(jnp.where(_eye(n), col, zero), axis=0, keepdims=True)


def _sweep(cum_ref, BT: int, gathers=(), counts=()):
    """One pass over the stacked slot arrays.

    ``gathers``: ``(idx, rows)`` pairs — return ``cum[r][idx]`` for every
    ``r`` in ``rows`` (one lane mask shared by the rows). ``counts``:
    ``(target, r)`` pairs — return ``#{k : cum[r][k] < target}``. All
    operands are (BT, 1) columns; results come back in the same order.
    """
    n_pad = cum_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (BT, _LANES), 1)
    n_g = sum(len(rows) for _, rows in gathers)

    def body(c, carry):
        g_acc, c_acc = list(carry[0]), list(carry[1])
        for u in range(_UNROLL):
            base = pl.multiple_of(c * _CHUNK + u * _LANES, _LANES)
            tile = [cum_ref[pl.ds(r, 1), pl.ds(base, _LANES)]
                    for r in range(3)]
            gi = 0
            for idx, rows in gathers:
                hit = lane == (idx - base)
                for r in rows:
                    g_acc[gi] = g_acc[gi] + jnp.where(hit, tile[r], 0.0)
                    gi += 1
            for ci, (tgt, r) in enumerate(counts):
                c_acc[ci] = c_acc[ci] + (tile[r] < tgt).astype(jnp.int32)
        return tuple(g_acc), tuple(c_acc)

    init = (tuple(jnp.zeros((BT, _LANES), jnp.float32) for _ in range(n_g)),
            tuple(jnp.zeros((BT, _LANES), jnp.int32) for _ in counts))
    g_acc, c_acc = jax.lax.fori_loop(0, n_pad // _CHUNK, body, init)
    lane_sum = lambda a: jnp.sum(a, axis=1, keepdims=True)
    return [lane_sum(a) for a in g_acc], [lane_sum(a) for a in c_acc]


def _task_costs(cum_ref, start, end, z_t, d_eff, *, n_slots: int,
                slot: float, p_od: float, BT: int):
    """Closed-form costs of BT tasks, mirroring ``kernels/ref.py::_task_sim``
    (same targets, same tie handling). Operands are (BT, 1) columns."""
    d_safe = jnp.where(d_eff > 0, d_eff, 1.0)
    need = z_t / d_safe

    # Pass 1: interpolated A0/C0 at `start`.
    k0 = jnp.clip((start / slot).astype(jnp.int32), 0, n_slots - 1)
    (a_k0, c_k0, a_k1, c_k1), _ = _sweep(
        cum_ref, BT, gathers=[(k0, (_A, _C)), (k0 + 1, (_A, _C))])
    frac = start - k0.astype(jnp.float32) * slot
    A0 = a_k0 + (a_k1 - a_k0) / slot * frac
    C0 = c_k0 + (c_k1 - c_k0) / slot * frac
    H0 = start - A0

    # Pass 2: the two inverse-query counts.
    h_target = H0 + (end - start) - need
    a_target = A0 + need
    _, (cntH, cntA) = _sweep(cum_ref, BT,
                             counts=[(h_target, _H), (a_target, _A)])

    # Pass 3: invert H and A at the counted indices.
    iH = jnp.clip(cntH, 1, n_slots)
    iA = jnp.clip(cntA, 1, n_slots)
    (h_prev, a_prev), _ = _sweep(cum_ref, BT,
                                 gathers=[(iH - 1, (_H,)), (iA - 1, (_A,))])
    # Flexibility epsilon (same constants as core.simulate.FLEX_REL /
    # FLEX_ABS): zero-slack tasks must turn at start deterministically in f32.
    no_flex = (end - start) - need <= jnp.maximum(
        jnp.float32(1e-15),
        jnp.maximum(_FLEX_REL * (end - start), _FLEX_ABS * end))
    t_turn = (iH - 1).astype(jnp.float32) * slot + (h_target - h_prev)
    t_turn = jnp.where(no_flex, start, t_turn)
    t_turn = jnp.where(jnp.logical_and(cntH > n_slots, ~no_flex),
                       jnp.inf, t_turn)
    t_fin = (iA - 1).astype(jnp.float32) * slot + (a_target - a_prev)
    t_fin = jnp.where(a_target <= 0.0, 0.0, t_fin)
    t_fin = jnp.where(cntA > n_slots, jnp.inf, t_fin)

    on_spot = t_fin <= t_turn
    t_end = jnp.minimum(jnp.where(on_spot, t_fin, t_turn), end)

    # Pass 4: A/C at t_end.
    ke = jnp.clip((t_end / slot).astype(jnp.int32), 0, n_slots - 1)
    (a_e0, c_e0, a_e1, c_e1), _ = _sweep(
        cum_ref, BT, gathers=[(ke, (_A, _C)), (ke + 1, (_A, _C))])
    frace = t_end - ke.astype(jnp.float32) * slot
    A_end = a_e0 + (a_e1 - a_e0) / slot * frace
    C_end = c_e0 + (c_e1 - c_e0) / slot * frace

    active = z_t > 1e-15
    spot_work = jnp.minimum(d_eff * jnp.maximum(A_end - A0, 0.0), z_t)
    spot_cost = d_eff * jnp.maximum(C_end - C0, 0.0)
    od_work = z_t - spot_work
    zeros = jnp.zeros_like(z_t)
    return {
        "spot_cost": jnp.where(active, spot_cost, zeros),
        "ondemand_cost": jnp.where(active, p_od * od_work, zeros),
        "spot_work": jnp.where(active, spot_work, zeros),
        "ondemand_work": jnp.where(active, od_work, zeros),
        "finish": jnp.where(active, jnp.where(on_spot, t_fin, end), start),
    }


def _stack_cum(A_cum, C_cum, slot: float):
    """(..., n_slots+1) A/C -> (..., 3, n_pad) stacked [A, C, H] slot
    arrays, padded with a value above every query target."""
    n1 = A_cum.shape[-1]
    H_cum = jnp.arange(n1, dtype=jnp.float32) * slot - A_cum
    cum = jnp.stack([A_cum, C_cum, H_cum], axis=-2)
    n_pad = -(-n1 // _CHUNK) * _CHUNK
    widths = [(0, 0)] * (cum.ndim - 1) + [(0, n_pad - n1)]
    return jnp.pad(cum, widths, constant_values=_BIG)


_TASK_KEYS = ("spot_cost", "ondemand_cost", "spot_work", "finish")
_CHAIN_KEYS = ("spot_cost", "ondemand_cost", "spot_work", "ondemand_work")


def _kernel(cum_ref, task_ref, out_ref, *, n_slots: int, slot: float,
            p_od: float, BT: int):
    start, end, z_t, d_eff = (to_col(task_ref[pl.ds(i, 1), :], BT)
                              for i in range(4))
    r = _task_costs(cum_ref, start, end, z_t, d_eff, n_slots=n_slots,
                    slot=slot, p_od=p_od, BT=BT)
    for i, key in enumerate(_TASK_KEYS):
        out_ref[pl.ds(i, 1), :] = to_row(r[key], BT)


def policy_cost(A_cum, C_cum, start, end, z_t, d_eff, *,
                slot: float = 1.0 / 12.0, p_od: float = 1.0,
                block_tasks: int = 128, interpret: bool = False):
    """Batched closed-form task costs under one bid's market arrays.

    A_cum/C_cum: (n_slots+1,) f32 cumulative availability / payment;
    start/end/z_t/d_eff: (T,) task windows and cloud workloads.
    Returns dict(spot_cost, ondemand_cost, spot_work, finish) of (T,).
    ``block_tasks`` is rounded up to a multiple of 128 lanes.
    """
    n_slots = A_cum.shape[0] - 1
    T = start.shape[0]
    BT = -(-block_tasks // _LANES) * _LANES
    Tp = -(-T // BT) * BT
    tasks = jnp.pad(jnp.stack([jnp.asarray(a, jnp.float32)
                               for a in (start, end, z_t, d_eff)]),
                    ((0, 0), (0, Tp - T)))
    cum = _stack_cum(jnp.asarray(A_cum, jnp.float32),
                     jnp.asarray(C_cum, jnp.float32), slot)
    kernel = functools.partial(_kernel, n_slots=n_slots, slot=slot,
                               p_od=p_od, BT=BT)
    out = pl.pallas_call(
        kernel,
        grid=(Tp // BT,),
        in_specs=[pl.BlockSpec(cum.shape, lambda i: (0, 0)),
                  pl.BlockSpec((4, BT), lambda i: (0, i))],
        out_specs=pl.BlockSpec((4, BT), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, Tp), jnp.float32),
        interpret=interpret,
    )(cum, tasks)
    return {k: out[i, :T] for i, k in enumerate(_TASK_KEYS)}


def _chain_kernel(cum_ref, arr_ref, ends_ref, z_ref, d_ref, pin_ref,
                  out_ref, *, n_slots: int, L: int, slot: float,
                  p_od: float, BT: int):
    def step(k, carry):
        cur, sc, oc, sw, ow = carry
        end, z_raw, d_raw, pin_f = (to_col(r[pl.ds(k, 1), :], BT)
                                    for r in (ends_ref, z_ref, d_ref, pin_ref))
        d_eff = jnp.maximum(d_raw, 0.0)
        pin = pin_f > 0.5
        # Early-start chain semantics (simulate_chains_early): the task runs
        # in [min(cur, end), end]; tasks whose window already elapsed carry
        # no cloud work.
        live = end > cur - 1e-15
        start = jnp.minimum(cur, end)
        z_t = jnp.where(live, z_raw, 0.0)
        r = _task_costs(cum_ref, start, end, z_t, d_eff, n_slots=n_slots,
                        slot=slot, p_od=p_od, BT=BT)
        fin = jnp.where(pin, end, r["finish"])
        moved = (z_raw > 1e-15) | pin
        cur = jnp.where(moved, fin, cur)
        return (cur, sc + r["spot_cost"], oc + r["ondemand_cost"],
                sw + r["spot_work"], ow + r["ondemand_work"])

    zeros = jnp.zeros((BT, 1), jnp.float32)
    carry = (to_col(arr_ref[...], BT), zeros, zeros, zeros, zeros)
    _, *sums = jax.lax.fori_loop(0, L, step, carry)
    for i, v in enumerate(sums):
        out_ref[pl.ds(i, 1), :] = to_row(v, BT)


def policy_cost_chain(A_cum, C_cum, arrival, ends, z_t, d_eff, pins, *,
                      slot: float = 1.0 / 12.0, p_od: float = 1.0,
                      block_rows: int = 128, interpret: bool = False):
    """Batched early-start CHAIN costs over B bids x S market scenarios.

    The grid-evaluation extension of ``policy_cost``: the whole
    (bid x scenario x policy x job) grid of a sweep is ONE kernel launch —
    rows are flattened (policy, job) cells, the chain recurrence over the L
    planned windows runs inside the kernel (fori_loop carrying the realized
    start), and (bid, scenario) are grid dimensions selecting which
    cumulative arrays are resident in VMEM.

    A_cum/C_cum: (B, S, n_slots+1) bid- and scenario-stacked cumulative
    arrays — or (S, n_slots+1) / (n_slots+1,) for a single bid (the result
    then drops the bid axis). arrival: (B, R); ends: (B, R, L) padded
    plans; z_t/d_eff/pins: (B, R, L), or (B, S, R, L) when the plans are
    scenario-specific (per-scenario availability refinement). Rows may be
    zero-padded (z_t == 0) to equalize row counts across bids. Returns dict
    of (B, S, R) per-row aggregates ((S, R) in single-bid mode).
    ``block_rows`` is rounded up to a multiple of 128 lanes.
    """
    A_cum = jnp.atleast_2d(jnp.asarray(A_cum, jnp.float32))
    C_cum = jnp.atleast_2d(jnp.asarray(C_cum, jnp.float32))
    single_bid = A_cum.ndim == 2
    if single_bid:
        A_cum, C_cum = A_cum[None], C_cum[None]
        arrival = jnp.asarray(arrival, jnp.float32)[None]
        ends = jnp.asarray(ends, jnp.float32)[None]
        z_t, d_eff, pins = (jnp.asarray(a, jnp.float32)[None]
                            for a in (z_t, d_eff, pins))
    B, S, n1 = A_cum.shape
    ends = jnp.asarray(ends, jnp.float32)
    R, L = ends.shape[-2:]
    BT = -(-block_rows // _LANES) * _LANES
    pt = -(-R // BT) * BT - R
    arrival = jnp.pad(jnp.asarray(arrival, jnp.float32),
                      ((0, 0), (0, pt)))[:, None]          # (B, 1, Rp)

    # Plans -> lane-dense (B, S_p, L, Rp): the chain loop reads one window
    # row per step. S_p == S only when the caller passed
    # scenario-specific plans.
    def to_lr(a):
        a = jnp.asarray(a, jnp.float32)
        if a.ndim == 3:
            a = a[:, None]
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pt), (0, 0)))
        return jnp.swapaxes(a, 2, 3)
    ends_p, z_p, d_p, pins_p = map(to_lr, (ends, z_t, d_eff, pins))
    S_p = z_p.shape[1]
    cum = _stack_cum(A_cum, C_cum, slot)                   # (B, S, 3, n_pad)

    kernel = functools.partial(_chain_kernel, n_slots=n1 - 1, L=L,
                               slot=slot, p_od=p_od, BT=BT)
    plan_idx = (lambda b, s, i: (b, s, 0, i)) if S_p == S and S > 1 \
        else (lambda b, s, i: (b, 0, 0, i))
    plan_spec = pl.BlockSpec((None, None, L, BT), plan_idx)
    out = pl.pallas_call(
        kernel,
        grid=(B, S, (R + pt) // BT),
        in_specs=[
            pl.BlockSpec((None, None) + cum.shape[2:],
                         lambda b, s, i: (b, s, 0, 0)),
            pl.BlockSpec((None, 1, BT), lambda b, s, i: (b, 0, i)),
            pl.BlockSpec((None, None, L, BT), lambda b, s, i: (b, 0, 0, i)),
            plan_spec,
            plan_spec,
            plan_spec,
        ],
        out_specs=pl.BlockSpec((None, None, 4, BT),
                               lambda b, s, i: (b, s, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, S, 4, R + pt), jnp.float32),
        interpret=interpret,
    )(cum, arrival, ends_p, z_p, d_p, pins_p)
    res = {k: out[:, :, i, :R] for i, k in enumerate(_CHAIN_KEYS)}
    if single_bid:
        res = {k: v[0] for k, v in res.items()}
    return res
