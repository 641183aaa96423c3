"""Pallas-kernel backend — the TPU fast path.

Early-start grids go through ``kernels/policy_cost.py::policy_cost_chain``:
ONE kernel launch covers the whole (bid x scenario x policy x job) sweep —
bids and scenarios are grid dimensions selecting the VMEM-resident
cumulative arrays, (policy, job) cells are flattened rows (zero-padded to
the widest bid), and the chain recurrence runs inside the kernel. Planned-
start grids (early_start=False) use the original per-task ``policy_cost``
kernel on the flattened task batch.

On the CPU the kernels run in interpret mode (slow, parity-testing only;
``repro.kernels.default_interpret``). Each launch, wrapper layout work
included, is one jitted program announced to ``repro.obs`` as
``engine.eval.pallas_chain`` / ``engine.eval.pallas_task``; on a TPU its
compiled text holds the Mosaic kernel as a ``tpu_custom_call``. Inside
``METRICS.collecting()`` each launch sets the gauge
``engine.eval.lookup_tiles{kernel=chain|task}``: the lane tiles one slot
lookup of the kernel reads, from the static slot count.
"""

from __future__ import annotations

import functools

import numpy as np

from repro.engine.plan import OUT_KEYS, concat_rows, scenario_cat
from repro.obs import METRICS, record_jit, span

__all__ = ["run"]


@functools.lru_cache(maxsize=1)
def _kernels():
    """(chain, task) kernel launches, each jitted once per process."""
    import jax

    from repro.kernels.policy_cost import policy_cost, policy_cost_chain

    return (jax.jit(policy_cost_chain, static_argnames=(
                "slot", "p_od", "block_rows", "interpret")),
            jax.jit(policy_cost, static_argnames=(
                "slot", "p_od", "block_tasks", "interpret")))


def run(gplan, batch, early_start: bool, out, block_rows: int = 128) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import default_interpret
    from repro.kernels.policy_cost import lookup_tiles

    chain, task = _kernels()
    interpret = default_interpret()
    slot = batch.slot
    p_od = batch.p_ondemand
    J = gplan.n_jobs
    S = batch.n_scenarios
    L = gplan.L
    bids = gplan.bids
    groups_per_bid = [gplan.groups_for_bid(b) for b in bids]

    if early_start:
        # Stack every bid's row batch into one (B, R_max, L) tensor (rows
        # zero-padded past the bid's own groups) -> ONE kernel launch for
        # the whole sweep.
        B = len(bids)
        per_scenario = gplan.per_scenario
        R_max = max(len(gs) for gs in groups_per_bid) * J
        # Per-bid views first: their own ``views`` spans time them.
        AC = [batch.stacked(bid) for bid in bids]
        with span("eval.stack"):
            arrival = np.zeros((B, R_max))
            for bi, groups in enumerate(groups_per_bid):
                arrival[bi, :len(groups) * J] = np.tile(gplan.arrival,
                                                        len(groups))
            if batch.device:
                # Device-synthesized chunk: the per-bid views are already
                # f32 jax arrays — stack them with jnp so the kernel
                # consumes them without a host round trip.
                A = jnp.stack([a for a, _ in AC])
                C = jnp.stack([c for _, c in AC])
            else:
                A = np.zeros((B, S, batch.n_slots + 1), np.float32)
                C = np.zeros_like(A)
                for bi, (a, c) in enumerate(AC):
                    A[bi], C[bi] = a, c
            if gplan.device:
                # Device grid plan: build the zero-padded (B, ..., R_max, L)
                # stacks with jnp so the plan tensors feed the kernel
                # without a host round trip.
                def pad(a, raxis):
                    if a.shape[raxis] == R_max:
                        return a
                    w = [(0, 0)] * a.ndim
                    w[raxis] = (0, R_max - a.shape[raxis])
                    return jnp.pad(a, w)

                raxis = 1 if per_scenario else 0  # row axis of the s-o stacks

                def cat(groups, attr):
                    if per_scenario:
                        return scenario_cat(groups, attr, S)
                    return concat_rows([getattr(g, attr) for g in groups])

                ends = jnp.stack(
                    [pad(concat_rows([g.plan.ends for g in gs]), 0)
                     for gs in groups_per_bid])
                z_t = jnp.stack([pad(cat(gs, "z_t"), raxis)
                                 for gs in groups_per_bid])
                d_eff = jnp.stack([pad(cat(gs, "d_eff"), raxis)
                                   for gs in groups_per_bid])
                pins = jnp.stack([pad(cat(gs, "pins"), raxis)
                                  for gs in groups_per_bid])
            else:
                ends = np.zeros((B, R_max, L))
                pshape = (B, S, R_max, L) if per_scenario else (B, R_max, L)
                z_t = np.zeros(pshape)
                d_eff = np.zeros(pshape)
                pins = np.zeros(pshape, dtype=bool)
                for bi, groups in enumerate(groups_per_bid):
                    R = len(groups) * J
                    ends[bi, :R] = np.concatenate(
                        [g.plan.ends for g in groups])
                    if per_scenario:
                        sl = (bi, slice(None), slice(0, R))
                        cat = lambda attr: scenario_cat(groups, attr, S)
                    else:
                        sl = (bi, slice(0, R))
                        cat = lambda attr: np.concatenate(
                            [getattr(g, attr) for g in groups])
                    z_t[sl] = cat("z_t")
                    d_eff[sl] = cat("d_eff")
                    pins[sl] = cat("pins")
        args = (A, C, arrival, ends, z_t, d_eff, pins)
        kw = dict(slot=slot, p_od=p_od, block_rows=block_rows,
                  interpret=interpret)
        record_jit("engine.eval.pallas_chain", chain, *args, **kw)
        if METRICS.enabled:
            METRICS.gauge("engine.eval.lookup_tiles").set(
                lookup_tiles(batch.n_slots), kernel="chain")
        res = chain(*args, **kw)
        with span("eval.wait"):
            jax.block_until_ready(res)
        with span("eval.fetch"):
            vals = {key: np.asarray(res[key], np.float64)  # (B, S, R_max)
                    for key in OUT_KEYS}
        with span("eval.scatter"):
            for key in OUT_KEYS:
                for bi, groups in enumerate(groups_per_bid):
                    per_g = vals[key][bi, :, :len(groups) * J].reshape(
                        S, len(groups), J)
                    for gi, g in enumerate(groups):
                        out[key][:, :, g.policy_idx] = per_g[:, gi, :, None]
        return

    for bid, groups in zip(bids, groups_per_bid):
        A, C = batch.stacked(bid)               # (S, n_slots+1)
        with span("eval.stack"):
            starts = concat_rows([g.plan.starts for g in groups])
            ends = concat_rows([g.plan.ends for g in groups])
            R, L = ends.shape
            if gplan.per_scenario:
                z_all = scenario_cat(groups, "z_t", S)       # (S, R, L)
                d_all = scenario_cat(groups, "d_eff", S)
            else:
                z_one = concat_rows([g.z_t for g in groups])
                d_one = concat_rows([g.d_eff for g in groups])
        launched = []
        for s in range(S):
            z_t = z_all[s] if gplan.per_scenario else z_one
            d_eff = d_all[s] if gplan.per_scenario else d_one
            flat = lambda a: jnp.asarray(a.reshape(R * L), jnp.float32)
            args = (jnp.asarray(A[s], jnp.float32),
                    jnp.asarray(C[s], jnp.float32),
                    flat(starts), flat(ends), flat(z_t), flat(d_eff))
            kw = dict(slot=slot, p_od=p_od, interpret=interpret)
            record_jit("engine.eval.pallas_task", task, *args, **kw)
            if METRICS.enabled:
                METRICS.gauge("engine.eval.lookup_tiles").set(
                    lookup_tiles(batch.n_slots), kernel="task")
            r = task(*args, **kw)
            r["ondemand_work"] = (
                r["ondemand_cost"] / p_od if p_od > 0
                else jnp.maximum(flat(z_t) - r["spot_work"], 0.0)
                * (flat(z_t) > 1e-15))
            launched.append(r)
        with span("eval.wait"):
            jax.block_until_ready(launched)
        with span("eval.fetch"):
            vals = {key: np.stack([np.asarray(r[key], np.float64)
                                   .reshape(len(groups), J, L).sum(axis=2)
                                   for r in launched])
                    for key in OUT_KEYS}
        with span("eval.scatter"):
            for key in OUT_KEYS:
                v = vals[key]
                for gi, g in enumerate(groups):
                    out[key][:, :, g.policy_idx] = v[:, gi, :, None]
