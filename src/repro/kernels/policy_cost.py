"""Pallas TPU kernel for the paper's evaluation hot loop: batched
closed-form task-cost evaluation (Definition 3.2 over a realized market).

TOLA (Alg. 4) scores every job under every policy of the grid — O(n_jobs x
n_policies) independent closed-form task simulations, each a pair of
monotone piecewise-linear inversions over the market's cumulative arrays
(A = availability time, C = spot payment, H = t - A; see
core/simulate.py). That inner evaluation is this kernel.

TPU adaptation (vs the numpy searchsorted implementation):
  * the three cumulative arrays of one (bid, scenario) are cut into lane
    tiles of 128 slots and laid out as one (n_tiles, 3 x 128) VMEM block,
    row j holding tile j of A, C and H side by side, next to a small
    (3, n_tiles) block of tile heads (each tile's first entry); n_tiles is
    lane-padded to a multiple of 128, the slots past the horizon hold a
    value above every query target; both are loaded once per grid cell;
  * every slot lookup reads two levels, about two lane tiles instead of
    the whole horizon: searchsorted (index = #{k : cum[k] < target} of a
    monotone array) counts the heads below the target, which names the
    one tile that holds the crossing, then counts inside that tile; a
    point gather (cum[k0], cum[k0+1], ...) reads tile k // 128 at lane
    k % 128, and the head of the next tile where k + 1 crosses into it;
  * each row's tile is fetched by a one-hot matmul on the MXU
    (onehot(BT, n_tiles) @ tiles at HIGHEST precision, f32 result), which
    returns the stored f32 values bit for bit — no data-dependent control
    flow, exact in f32;
  * task rows live on SUBLANES as (BT, 1) columns, so the heads count and
    the in-tile count compare a lane row against BT row targets at once.
    Row vectors enter and leave in their lane-dense HBM layout and are
    turned into columns (and back) with a diagonal select-and-reduce,
    which Mosaic lowers to plain VPU/XLU ops.

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

__all__ = ["lookup_tiles", "policy_cost", "policy_cost_chain"]

_LANES = 128
# Pad value: above every query target, and a power of two, so it stays
# finite and exact in every bf16 part of the MXU's f32 passes.
_BIG = 2.0 ** 100
_A, _C, _H = 0, 1, 2              # slot arrays, in their order in a tile row
_HI = jax.lax.Precision.HIGHEST


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


def _n_tiles(n_slots: int) -> int:
    """Lane tiles of the padded slot arrays, lane-padded to a multiple of
    128 so the tile heads form whole lane tiles."""
    t = -(-(n_slots + 1) // _LANES)
    return -(-t // _LANES) * _LANES


def lookup_tiles(n_slots: int) -> int:
    """Lane tiles one slot lookup reads: the heads row, plus one tile."""
    return _n_tiles(n_slots) // _LANES + 1


def _fetch(tiles_ref, t, first: int, n: int, BT: int):
    """Lane tile ``t`` ((BT, 1) tile indices) of the ``n`` slot arrays from
    ``first`` on, one row per task: a one-hot matmul. Returns (BT, n*128)."""
    nt = tiles_ref.shape[0]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (BT, nt), 1) == t
              ).astype(jnp.float32)
    rhs = tiles_ref[:, pl.ds(first * _LANES, n * _LANES)]
    return jnp.dot(onehot, rhs, precision=_HI,
                   preferred_element_type=jnp.float32)


def _pick(vals, idx):
    """vals[i, idx[i]] of a (BT, w) block (or a (1, w) row) for (BT, 1)
    indices; an index outside [0, w) picks 0."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], vals.shape[1]),
                                    1)
    return jnp.sum(jnp.where(lane == idx, vals, 0.0), axis=1, keepdims=True)


def _gather2(tiles_ref, heads_ref, k, BT: int):
    """(A[k], C[k], A[k+1], C[k+1]) for (BT, 1) slot indices 0 <= k <
    n_slots: tile k // 128 holds k, and k + 1 too unless k % 128 == 127,
    where it is the next tile's head."""
    t = k // _LANES
    lane = k - t * _LANES
    ac = _fetch(tiles_ref, t, _A, 2, BT)
    wrap = lane == _LANES - 1
    out0, out1 = [], []
    for r in (_A, _C):
        tile = ac[:, r * _LANES:(r + 1) * _LANES]
        out0.append(_pick(tile, lane))
        out1.append(jnp.where(wrap, _pick(heads_ref[pl.ds(r, 1), :], t + 1),
                              _pick(tile, lane + 1)))
    return (*out0, *out1)


def _count(tiles_ref, heads_ref, target, r: int, BT: int):
    """#{k : cum[r][k] < target} of a nondecreasing cum[r], for (BT, 1)
    targets: count the tile heads below the target, then the lanes below
    it in the last tile whose head is (tile 0 if none is). Returns the
    count, that tile's index and the tile."""
    below = (heads_ref[pl.ds(r, 1), :] < target).astype(jnp.int32)
    t = jnp.maximum(jnp.sum(below, axis=1, keepdims=True) - 1, 0)
    tile = _fetch(tiles_ref, t, r, 1, BT)
    lanes = jnp.sum((tile < target).astype(jnp.int32), axis=1, keepdims=True)
    return t * _LANES + lanes, t, tile


def _task_costs(tiles_ref, heads_ref, start, end, z_t, d_eff, *,
                n_slots: int, slot: float, p_od: float, BT: int):
    """Closed-form costs of BT tasks, mirroring ``kernels/ref.py::_task_sim``
    (same targets, same tie handling). Operands are (BT, 1) columns."""
    d_safe = jnp.where(d_eff > 0, d_eff, 1.0)
    need = z_t / d_safe

    # Pass 1: interpolated A0/C0 at `start`.
    k0 = jnp.clip((start / slot).astype(jnp.int32), 0, n_slots - 1)
    a_k0, c_k0, a_k1, c_k1 = _gather2(tiles_ref, heads_ref, k0, BT)
    frac = start - k0.astype(jnp.float32) * slot
    A0 = a_k0 + (a_k1 - a_k0) / slot * frac
    C0 = c_k0 + (c_k1 - c_k0) / slot * frac
    H0 = start - A0

    # Pass 2: the two inverse-query counts.
    h_target = H0 + (end - start) - need
    a_target = A0 + need
    cntH, tH, tileH = _count(tiles_ref, heads_ref, h_target, _H, BT)
    cntA, tA, tileA = _count(tiles_ref, heads_ref, a_target, _A, BT)

    # Pass 3: invert H and A at the counted indices. Index cnt - 1 lies in
    # the tile the count read wherever the result is used (1 <= cnt <=
    # n_slots: that tile's head is below the target; cnt == 0: tile 0).
    iH = jnp.clip(cntH, 1, n_slots)
    iA = jnp.clip(cntA, 1, n_slots)
    h_prev = _pick(tileH, iH - 1 - tH * _LANES)
    a_prev = _pick(tileA, iA - 1 - tA * _LANES)
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
    a_e0, c_e0, a_e1, c_e1 = _gather2(tiles_ref, heads_ref, ke, BT)
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


def _two_level(cum):
    """(..., 3, n_slots+1) stacked [A, C, H] -> the two levels of the slot
    lookup: ``tiles`` (..., n_tiles, 3*128), row j holding lane tile j of
    A, C and H side by side, and ``heads`` (..., 3, n_tiles), each tile's
    first entry; padded with a value above every query target."""
    n1 = cum.shape[-1]
    nt = _n_tiles(n1 - 1)
    widths = [(0, 0)] * (cum.ndim - 1) + [(0, nt * _LANES - n1)]
    cum = jnp.pad(cum, widths, constant_values=_BIG)
    lead = cum.shape[:-2]
    cum = cum.reshape(lead + (3, nt, _LANES))
    tiles = jnp.swapaxes(cum, -3, -2).reshape(lead + (nt, 3 * _LANES))
    return tiles, cum[..., 0]


def _stack_cum(A_cum, C_cum, slot: float):
    """(..., n_slots+1) A/C -> ``_two_level`` of [A, C, H = t - A]."""
    n1 = A_cum.shape[-1]
    H_cum = jnp.arange(n1, dtype=jnp.float32) * slot - A_cum
    return _two_level(jnp.stack([A_cum, C_cum, H_cum], axis=-2))


_TASK_KEYS = ("spot_cost", "ondemand_cost", "spot_work", "finish")
_CHAIN_KEYS = ("spot_cost", "ondemand_cost", "spot_work", "ondemand_work")


def _kernel(tiles_ref, heads_ref, task_ref, out_ref, *, n_slots: int,
            slot: float, p_od: float, BT: int):
    start, end, z_t, d_eff = (to_col(task_ref[pl.ds(i, 1), :], BT)
                              for i in range(4))
    r = _task_costs(tiles_ref, heads_ref, start, end, z_t, d_eff,
                    n_slots=n_slots, slot=slot, p_od=p_od, BT=BT)
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
    tiles, heads = _stack_cum(jnp.asarray(A_cum, jnp.float32),
                              jnp.asarray(C_cum, jnp.float32), slot)
    kernel = functools.partial(_kernel, n_slots=n_slots, slot=slot,
                               p_od=p_od, BT=BT)
    out = pl.pallas_call(
        kernel,
        grid=(Tp // BT,),
        in_specs=[pl.BlockSpec(tiles.shape, lambda i: (0, 0)),
                  pl.BlockSpec(heads.shape, lambda i: (0, 0)),
                  pl.BlockSpec((4, BT), lambda i: (0, i))],
        out_specs=pl.BlockSpec((4, BT), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, Tp), jnp.float32),
        interpret=interpret,
    )(tiles, heads, tasks)
    return {k: out[i, :T] for i, k in enumerate(_TASK_KEYS)}


def _chain_kernel(tiles_ref, heads_ref, arr_ref, ends_ref, z_ref, d_ref,
                  pin_ref, out_ref, *, n_slots: int, L: int, slot: float,
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
        r = _task_costs(tiles_ref, heads_ref, start, end, z_t, d_eff,
                        n_slots=n_slots, slot=slot, p_od=p_od, BT=BT)
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
    tiles, heads = _stack_cum(A_cum, C_cum, slot)  # (B, S, nt, 384),
                                                   # (B, S, 3, nt)

    kernel = functools.partial(_chain_kernel, n_slots=n1 - 1, L=L,
                               slot=slot, p_od=p_od, BT=BT)
    plan_idx = (lambda b, s, i: (b, s, 0, i)) if S_p == S and S > 1 \
        else (lambda b, s, i: (b, 0, 0, i))
    plan_spec = pl.BlockSpec((None, None, L, BT), plan_idx)
    out = pl.pallas_call(
        kernel,
        grid=(B, S, (R + pt) // BT),
        in_specs=[
            pl.BlockSpec((None, None) + tiles.shape[2:],
                         lambda b, s, i: (b, s, 0, 0)),
            pl.BlockSpec((None, None) + heads.shape[2:],
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
    )(tiles, heads, arrival, ends_p, z_p, d_p, pins_p)
    res = {k: out[:, :, i, :R] for i, k in enumerate(_CHAIN_KEYS)}
    if single_bid:
        res = {k: v[0] for k, v in res.items()}
    return res
