"""Vectorized jnp backend.

One fused computation per bid: every evaluation group sharing the bid is
stacked into a (G*J,) row batch, the S market scenarios are vmapped over
the stacked cumulative arrays, and the chain recurrence runs as a
``lax.scan`` over the L planned windows (``kernels/ref.py::chain_costs_ref``).
Float32 (matches the pallas kernel); the numpy backend is the float64
oracle.

When the grid plan was built against per-scenario availability queries
(TOLA's batched pool refinement) the self-owned arrays (z_t, d_eff, pins)
are (S, R, L) stacks and the ``_ps`` entry points vmap them alongside the
market arrays; the common scenario-shared case keeps them closed over
(one host->device copy, no S-fold broadcast).

Device grid plans (``plan_backend="device"``) arrive as jax arrays and are
consumed directly — ``concat_rows``/``scenario_cat`` stack them with jnp,
so the plan tensors never take a host round trip between the plan jit and
the cost jit.

The jitted entry points live at module scope and take every plan array as
a traced argument, so repeated ``evaluate_grid`` calls reuse the compile
cache (one compilation per distinct batch shape, not per call).

Donation note (DESIGN.md §11): the eval entry points deliberately do NOT
use ``donate_argnums``. Their inputs are exactly the tensors the
cross-call caches keep alive — device plan arrays in ``PLAN_CACHE``
groups, stacked views in ``VIEW_CACHE`` — and the f32 conversions below
are aliases (``jnp.asarray`` on an already-f32 device array is a no-op),
so donating them would invalidate cached buffers mid-cache-lifetime.
There is also nothing to donate INTO: no output shares a donatable
input's shape+dtype (outputs are (S, R)-shaped cost dicts). The streamed
regret fold in ``learn/replay.py`` is where donation pays — its
accumulator is a genuine same-shape carry.

Sharded path (DESIGN.md §9): with a ``GridMesh`` the same four batch
bodies are ``shard_map``ed over the 2-D (scenario x group) mesh — stacked
views arrive padded and sharded over ``"data"`` (``ScenarioBatch.n_rows``
rows), plan row batches are padded to whole groups and sharded over
``"model"`` (edge-repeat group padding, ``pad_groups``), per-scenario
self-owned stacks shard over BOTH axes, and scalars replicate. Every
(data, model) shard scores only its own scenario-slab x group-block and
the compiled program contains ZERO cross-device collectives (neither axis
reduces inside the cost tensor). Results come back through one unpermute
gather (the ``np.asarray`` below) and padded lanes are masked at the
splice: ``[:S]`` drops scenario padding, indexing only the real groups
drops group padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.plan import OUT_KEYS, concat_rows, scenario_cat
from repro.kernels.ref import chain_costs_ref, policy_cost_ref
from repro.obs import record_jit, span

__all__ = ["run"]


def _chain_body(A, C, arrival, ends, z_t, d_eff, pins, p_od, slot):
    """(S, n+1) stacked views x (R, L) row batch -> dict of (S, R)."""
    fn = jax.vmap(
        lambda a, c: chain_costs_ref(a, c, arrival, ends, z_t, d_eff, pins,
                                     p_od=p_od, slot=slot),
        in_axes=(0, 0))
    return fn(A, C)


def _task_body(A, C, starts, ends, z_t, d_eff, p_od, slot):
    """Planned-start (per-task) edition -> dict of (S, R*L)."""
    fn = jax.vmap(
        lambda a, c: policy_cost_ref(a, c, starts, ends, z_t, d_eff,
                                     p_od=p_od, slot=slot),
        in_axes=(0, 0))
    return fn(A, C)


def _chain_body_ps(A, C, arrival, ends, z_t, d_eff, pins, p_od, slot):
    """Per-scenario-plan edition: z_t/d_eff/pins are (S, R, L) stacks."""
    fn = jax.vmap(
        lambda a, c, z, d, p: chain_costs_ref(a, c, arrival, ends, z, d, p,
                                              p_od=p_od, slot=slot),
        in_axes=(0, 0, 0, 0, 0))
    return fn(A, C, z_t, d_eff, pins)


def _task_body_ps(A, C, starts, ends, z_t, d_eff, p_od, slot):
    """Planned-start with per-scenario (S, R*L) cloud workloads."""
    fn = jax.vmap(
        lambda a, c, z, d: policy_cost_ref(a, c, starts, ends, z, d,
                                           p_od=p_od, slot=slot),
        in_axes=(0, 0, 0, 0))
    return fn(A, C, z_t, d_eff)


_chain_batch = jax.jit(_chain_body)
_task_batch = jax.jit(_task_body)
_chain_batch_ps = jax.jit(_chain_body_ps)
_task_batch_ps = jax.jit(_task_body_ps)


@functools.lru_cache(maxsize=8)   # bounded: one entry per live mesh
def _sharded_fns(mesh):
    """The four batch bodies shard_map'ed over a ``GridMesh``.

    Views (leading scenario axis) shard over ``"data"``; plan row batches
    (leading group-row axis) shard over ``"model"``; per-scenario
    self-owned stacks shard over both; scalars replicate. On a 1-D mesh
    ``spec("group")`` degrades to replicated and this is exactly the PR 6
    scenario-only placement. Cached per mesh so repeated calls reuse the
    compiled program exactly like the unsharded module-scope jits.
    """
    dp = mesh.spec("scenario")            # P("data")
    gp = mesh.spec("group")               # P("model"); P(None) on 1-D mesh
    dgp = mesh.spec("scenario", "group")  # P("data", "model")
    rp = mesh.spec()                      # empty P(): replicated, any rank
    sm = functools.partial(jax.shard_map, mesh=mesh.mesh)
    chain = jax.jit(sm(
        _chain_body,
        in_specs=(dp, dp, gp, gp, gp, gp, gp, rp, rp), out_specs=dgp))
    task = jax.jit(sm(
        _task_body,
        in_specs=(dp, dp, gp, gp, gp, gp, rp, rp), out_specs=dgp))
    chain_ps = jax.jit(sm(
        _chain_body_ps,
        in_specs=(dp, dp, gp, gp, dgp, dgp, dgp, rp, rp), out_specs=dgp))
    task_ps = jax.jit(sm(
        _task_body_ps,
        in_specs=(dp, dp, gp, gp, dgp, dgp, rp, rp), out_specs=dgp))
    return {"chain": chain, "task": task,
            "chain_ps": chain_ps, "task_ps": task_ps}


def _scen_rows(a, rows: int):
    """Edge-repeat a leading-scenario stack to the mesh-padded row count
    (device arrays stay on device; the padded rows duplicate the last
    scenario and are sliced off at the splice)."""
    k = a.shape[0]
    if rows == k:
        return a
    xp = np if isinstance(a, np.ndarray) else jnp
    return xp.concatenate([a, xp.repeat(a[-1:], rows - k, axis=0)], axis=0)


def run(gplan, batch, early_start: bool, out, mesh=None) -> None:
    slot = batch.slot
    p_od = batch.p_ondemand
    J = gplan.n_jobs
    S = batch.n_scenarios
    rows = batch.n_rows if mesh is not None else S
    ps = gplan.per_scenario
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    if mesh is not None:
        fns = _sharded_fns(mesh)
        chain_fn, task_fn = fns["chain"], fns["task"]
        chain_ps_fn, task_ps_fn = fns["chain_ps"], fns["task_ps"]
        scalar = jnp.float32
    else:
        chain_fn, task_fn = _chain_batch, _task_batch
        chain_ps_fn, task_ps_fn = _chain_batch_ps, _task_batch_ps
        scalar = lambda x: x

    sfx = ":sharded" if mesh is not None else ""
    for bid in gplan.bids:
        groups = gplan.groups_for_bid(bid)
        G = len(groups)
        # Group padding for the "model" axis: repeat the LAST group so
        # every model shard owns the same number of whole groups. Padded
        # groups are real (duplicated) work, masked at the splice below.
        Gp = mesh.pad_groups(G) if mesh is not None else G
        gpad = groups if Gp == G else groups + [groups[-1]] * (Gp - G)
        with span("eval.bid", bid=bid, groups=G):
            # (rows, n_slots+1) stacked views, cached on the batch per
            # bid — already-f32 device tensors when the chunk was
            # synthesized on device (a spec source), host f64 otherwise;
            # padded + sharded over "data" under a mesh.
            A, C = batch.stacked(bid)
            with span("eval.stack"):
                A, C = f32(A), f32(C)
                ends = concat_rows([g.plan.ends for g in gpad])
                if ps:
                    z_t = _scen_rows(scenario_cat(gpad, "z_t", S), rows)
                    d_eff = _scen_rows(scenario_cat(gpad, "d_eff", S), rows)
                else:
                    z_t = concat_rows([g.z_t for g in gpad])
                    d_eff = concat_rows([g.d_eff for g in gpad])
                if early_start:
                    arrival = np.tile(gplan.arrival, Gp)
                    if ps:
                        pins = _scen_rows(scenario_cat(gpad, "pins", S), rows)
                    else:
                        pins = concat_rows([g.pins for g in gpad])
                    args = (A, C, f32(arrival), f32(ends), f32(z_t),
                            f32(d_eff), jnp.asarray(pins), scalar(p_od),
                            scalar(slot))
                else:
                    starts = concat_rows([g.plan.starts for g in gpad])
                    R, L = ends.shape
                    if ps:
                        args = (A, C, f32(starts.ravel()), f32(ends.ravel()),
                                f32(z_t).reshape(rows, R * L),
                                f32(d_eff).reshape(rows, R * L),
                                scalar(p_od), scalar(slot))
                    else:
                        args = (A, C, f32(starts.ravel()), f32(ends.ravel()),
                                f32(z_t.reshape(R * L)),
                                f32(d_eff.reshape(R * L)), scalar(p_od),
                                scalar(slot))
            if early_start:
                key, fn = (("chain_ps", chain_ps_fn) if ps
                           else ("chain", chain_fn))
            else:
                key, fn = ("task_ps", task_ps_fn) if ps else ("task", task_fn)
            record_jit("engine.eval." + key + sfx, fn, *args)
            res = fn(*args)
            if not early_start:
                res = {k: v.reshape(rows, R, L).sum(axis=2)
                       for k, v in res.items() if k != "finish"}
            with span("eval.wait"):
                jax.block_until_ready(res)
            # [:S] drops the mesh padding rows (duplicates of the last
            # scenario) before the host scatter; indexing only the real
            # ``groups`` below masks the padded group lanes.
            with span("eval.fetch"):
                vals = {k: np.asarray(res[k], np.float64)[:S].reshape(
                            (S, Gp, J))
                        for k in OUT_KEYS}
            with span("eval.scatter"):
                for k in OUT_KEYS:
                    for gi, g in enumerate(groups):
                        out[k][:, :, g.policy_idx] = vals[k][:, gi, :, None]
