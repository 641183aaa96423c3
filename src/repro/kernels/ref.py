"""Pure-jnp oracles for the Pallas kernels.

Deliberately naive implementations (materialized score matrix; sequential
token-by-token SSD recurrence) — structurally different algorithms from the
kernels, so agreement is meaningful.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.simulate import FLEX_ABS, FLEX_REL

__all__ = ["attention_ref", "ssd_ref", "policy_cost_ref", "chain_costs_ref",
           "hedge_replay_ref", "varying_like"]


def varying_like(tree, *operands):
    """Mark every leaf of ``tree`` as varying over each manual mesh axis
    that any of ``operands`` varies over (a no-op outside ``shard_map``).

    A ``lax.scan`` carry initialised from constants starts out unvarying,
    but its body mixes in sharded operands; ``shard_map``'s type check
    needs the carry's input and output types to be equal.
    """
    axes = set()
    for o in operands:
        axes |= set(jax.typeof(o).vma)

    def cast(x):
        missing = tuple(sorted(axes - set(jax.typeof(x).vma)))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree_util.tree_map(cast, tree)


def hedge_replay_ref(C, etas, u, n_done):
    """Vectorized numpy oracle for the fused Hedge replay kernel.

    Same two-pass factorization as ``kernels/weight_update.py`` but exact
    float64 and loop-free: the log-space renormalization cancels inside the
    softmax, so the trajectory is just the running sum ``W[k] = sum_{i<k}
    eta_i * C[i]`` and the state at job j's sample is ``softmax(-W[n_done
    [j]])``. Sampling is inverse-CDF (``searchsorted`` side="right") on the
    shared uniform stream — the exact arithmetic ``Generator.choice`` uses.

    C: (J, P) unit costs; etas/u/n_done: (J,). One replay instance.
    Returns dict(chosen, p_chosen, expected_cost, weights).
    """
    C = np.asarray(C, dtype=np.float64)
    J, P = C.shape
    W = np.concatenate([np.zeros((1, P)),
                        np.cumsum(np.asarray(etas)[:, None] * C, axis=0)])
    logw = -W[np.asarray(n_done)]
    logw -= logw.max(axis=1, keepdims=True)
    p = np.exp(logw)
    p /= p.sum(axis=1, keepdims=True)
    cdf = np.cumsum(p, axis=1)
    cdf /= cdf[:, -1:]
    chosen = np.minimum((cdf <= np.asarray(u)[:, None]).sum(axis=1), P - 1)
    wf = -W[J] + W[J].min()
    w = np.exp(wf)
    w /= w.sum()
    return {
        "chosen": chosen.astype(np.int64),
        "p_chosen": p[np.arange(J), chosen],
        "expected_cost": (p * C).sum(axis=1),
        "weights": w,
    }


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  prefix: int = 0):
    """q: (BH, Sq, dh), k/v: (BK, Sk, dh); naive softmax attention."""
    BH, Sq, dh = q.shape
    BK, Sk, _ = k.shape
    g = BH // BK
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(dh)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    bad = jnp.zeros((Sq, Sk), bool)
    if causal:
        bad |= k_pos > q_pos
    if window > 0:
        oow = (q_pos - k_pos) >= window
        if prefix > 0:
            oow &= k_pos >= prefix
        bad |= oow
    s = jnp.where(bad[None], -1e30, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x, dt, A, B, C, init_state=None):
    """Token-by-token SSD recurrence (the definition, not the chunked form).

    x: (Bb, S, H, P); dt: (Bb, S, H); A: (H,); B/C: (Bb, S, G, N).
    Returns (y, final_state) — y: (Bb, S, H, P), state: (Bb, H, P, N).
    """
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)   # (Bb, S, H, N)
    Ch = jnp.repeat(C, rep, axis=2)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        decay = jnp.exp(A[None, :] * dt_t)                 # (Bb, H)
        upd = jnp.einsum("bhp,bhn->bhpn", x_t * dt_t[..., None], B_t)
        state = state * decay[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", state, C_t)
        return state, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    if init_state is None:
        init_state = jnp.zeros((Bb, H, P, N), jnp.float32)
    state, ys = jax.lax.scan(step, init_state.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), state


def _task_sim(A_cum, C_cum, start, end, z_t, d_eff, slot, p_od):
    """Closed-form task sim on one bid's cumulative arrays (jnp, batched).

    All task arrays share one shape; ``A_cum``/``C_cum`` are (n_slots+1,).
    Mirrors ``repro.core.simulate.simulate_tasks`` exactly (same targets,
    same tie handling).
    """
    n = A_cum.shape[0] - 1
    horizon = n * slot
    boundaries = jnp.arange(n + 1) * slot
    H_cum = boundaries - A_cum

    def interp(cum, t):
        t = jnp.clip(t, 0.0, horizon)
        k = jnp.clip((t / slot).astype(jnp.int32), 0, n - 1)
        frac = t - k * slot
        slope = (cum[k + 1] - cum[k]) / slot
        return cum[k] + slope * frac

    def invert(cum, target):
        k = jnp.searchsorted(cum, target.ravel(), side="left").reshape(
            target.shape)
        k = jnp.clip(k, 1, n)
        return jnp.where(target <= cum[0], boundaries[0],
                         boundaries[k - 1] + (target - cum[k - 1]))

    active = z_t > 1e-15
    d_safe = jnp.where(d_eff > 0, d_eff, 1.0)
    need = z_t / d_safe
    A0 = interp(A_cum, start)
    C0 = interp(C_cum, start)
    H0 = start - A0
    h_target = H0 + (end - start) - need
    # Flexibility epsilon: zero-slack tasks (z == d * window, an atom under
    # Dealloc) must turn at start in every backend regardless of float
    # rounding — the oracle's constants, applied identically.
    no_flex = (end - start) - need <= jnp.maximum(
        1e-15, jnp.maximum(FLEX_REL * (end - start), FLEX_ABS * end))
    t_turn = jnp.where(no_flex, start, invert(H_cum, h_target))
    t_fin = invert(A_cum, A0 + need)
    on_spot = t_fin <= t_turn
    t_end = jnp.minimum(jnp.where(on_spot, t_fin, t_turn), end)
    spot_avail = jnp.maximum(interp(A_cum, t_end) - A0, 0.0)
    spot_work = jnp.minimum(d_eff * spot_avail, z_t)
    spot_cost = d_eff * jnp.maximum(interp(C_cum, t_end) - C0, 0.0)
    od_work = z_t - spot_work
    zeros = jnp.zeros_like(z_t)
    return {
        "spot_cost": jnp.where(active, spot_cost, zeros),
        "ondemand_cost": jnp.where(active, p_od * od_work, zeros),
        "spot_work": jnp.where(active, spot_work, zeros),
        "ondemand_work": jnp.where(active, od_work, zeros),
        "finish": jnp.where(active, jnp.where(on_spot, t_fin, end), start),
    }


def policy_cost_ref(A_cum, C_cum, start, end, z_t, d_eff, p_od=1.0,
                    slot=1.0 / 12.0):
    """Closed-form per-task spot/on-demand costs (mirrors
    repro.core.simulate.simulate_tasks, jnp edition).

    A_cum/C_cum: (n_slots+1,) cumulative availability / spot-payment arrays
    on the slot grid (slot length = 1/12 by default); boundaries are implicit
    (k * slot). Returns dict of per-task arrays.
    """
    return _task_sim(A_cum, C_cum, start, end, z_t, d_eff, slot, p_od)


def chain_costs_ref(A_cum, C_cum, arrival, ends, z_t, d_eff, pins,
                    p_od=1.0, slot=1.0 / 12.0):
    """Early-start chain execution under one bid, batched over rows (jnp).

    Mirrors ``repro.core.simulate.simulate_chains_early``: task k of each
    row starts at its predecessor's realized finish, pinned tasks (holding
    self-owned reservations) finish at their planned deadline. A *row* is one
    (policy, job) cell of the evaluation grid — the batched policy axis of the
    engine is folded into this leading dimension.

    arrival: (R,); ends/z_t/d_eff: (R, L) padded plans; pins: (R, L) bool.
    Returns per-row aggregates (spot/on-demand cost and work) plus the
    realized chain ``finish``.
    """
    A_cum, C_cum = jnp.asarray(A_cum), jnp.asarray(C_cum)
    xs = (jnp.moveaxis(jnp.asarray(ends), 1, 0),
          jnp.moveaxis(jnp.asarray(z_t), 1, 0),
          jnp.moveaxis(jnp.asarray(d_eff), 1, 0),
          jnp.moveaxis(jnp.asarray(pins), 1, 0))

    def step(carry, inp):
        cur, sc, oc, sw, ow = carry
        end_k, z_k, d_k, pin_k = inp
        live = end_k > cur - 1e-15
        start_k = jnp.minimum(cur, end_k)
        sim = _task_sim(A_cum, C_cum, start_k, end_k,
                        jnp.where(live, z_k, 0.0),
                        jnp.maximum(d_k, 0.0), slot, p_od)
        fin = jnp.where(pin_k, end_k, sim["finish"])
        moved = (z_k > 1e-15) | pin_k
        cur = jnp.where(moved, fin, cur)
        return (cur, sc + sim["spot_cost"], oc + sim["ondemand_cost"],
                sw + sim["spot_work"], ow + sim["ondemand_work"]), None

    arrival = jnp.asarray(arrival, jnp.result_type(ends))
    zeros = jnp.zeros_like(arrival)
    init = varying_like((arrival, zeros, zeros, zeros, zeros),
                        A_cum, C_cum, arrival, *xs)
    (cur, sc, oc, sw, ow), _ = jax.lax.scan(step, init, xs)
    return {"spot_cost": sc, "ondemand_cost": oc, "spot_work": sw,
            "ondemand_work": ow, "finish": cur}
