"""Batched replay of the online-learning recurrence over a cost tensor.

The paper's Alg. 4 is a sequential recurrence over a merged event stream:
when job j ARRIVES a policy is sampled from the learner's current
distribution; once its window has fully ELAPSED (``t = a_j + d``) its
counterfactual costs become observable and the learner state is updated.
The engine (``repro.engine``) already produces the full (scenarios x jobs x
policies) counterfactual cost tensor in one batched pass; this module
replays ANY learner of ``learners.py`` over that tensor:

* ``backend="numpy"`` — the sequential float64 event loop, the exact
  oracle. For ``hedge`` with the ``alg4`` schedule it is bit-compatible
  with the original in-module Alg. 4 loop (same logw arithmetic, same
  uniform-stream consumption as ``rng.choice`` — see ``_sample_cdf``).
* ``backend="jax"``  — the same event stream as ONE ``jax.lax.scan``,
  compiled once per learner kind and vmapped across scenarios x (learner,
  schedule-grid) instances, so an entire learner-comparison sweep is a
  single compiled call.
* ``backend="pallas"`` — hedge-family instances route to the fused
  ``kernels/weight_update.py`` TPU kernel (trajectory pass + one-hot-matmul
  sample gather); other kinds fall back to the jax scan.

Sampling is inverse-CDF against a per-scenario uniform stream drawn up
front in numpy: ``searchsorted(cdf, u, side="right")`` is exactly what
``np.random.Generator.choice(m, p=w)`` computes internally, so all
backends consume the SAME randomness and produce the SAME sampled-policy
trace (up to float ties) — and all learners of a sweep share the stream
(common random numbers, which is what makes their comparison low-variance).
"""

from __future__ import annotations

import functools

import numpy as np

from repro.learn.learners import (
    FULL_INFO_KINDS,
    LearnerSpec,
    as_spec,
    init_state,
    sample_probs,
    update_state,
)
from repro.learn.regret import LearnResult, StreamLearnResult
from repro.obs import METRICS, maybe_snapshot, record_jit, span

__all__ = ["replay", "replay_stream", "build_events", "available_backends",
           "resolve_backend"]


def available_backends() -> list[str]:
    """Replay backends usable in this process (same probe as the engine)."""
    from repro.engine import available_backends as engine_backends

    return engine_backends()


def resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "jax" if "jax" in available_backends() else "numpy"
    if backend not in ("numpy", "jax", "pallas"):
        raise ValueError(f"unknown replay backend {backend!r}")
    return backend


def build_events(arrivals: np.ndarray, d: float):
    """Merged (sample, update) event stream, exactly as Alg. 4 orders it.

    Returns ``(ev_kind, ev_j, n_done)``: per-event kind (0 = sample at
    ``a_j``, 1 = update at ``a_j + d``) and job index, in the same
    lexicographic (t, kind, j) order the legacy loop used — at equal times
    samples precede updates — plus ``n_done[j]``, the number of updates
    already applied when job j samples (the delayed-feedback offsets the
    trajectory-based kernels consume).
    """
    n = len(arrivals)
    events = sorted(
        [(float(arrivals[j]), 0, j) for j in range(n)]
        + [(float(arrivals[j] + d), 1, j) for j in range(n)]
    )
    ev_kind = np.array([k for _, k, _ in events], dtype=np.int32)
    ev_j = np.array([j for _, _, j in events], dtype=np.int32)
    upd_before = np.concatenate([[0], np.cumsum(ev_kind)])[:-1]
    n_done = np.zeros(n, dtype=np.int32)
    sample_pos = ev_kind == 0
    n_done[ev_j[sample_pos]] = upd_before[sample_pos]
    return ev_kind, ev_j, n_done


def _sample_cdf(p: np.ndarray, u: float) -> int:
    """What ``np.random.Generator.choice(m, p)`` does with one uniform."""
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    return min(int(np.searchsorted(cdf, u, side="right")), len(p) - 1)


def _replay_numpy_one(C, spec, u, ev_kind, ev_j, etas, gammas):
    """Sequential float64 event loop for one (scenario, learner) instance."""
    n, m = C.shape
    st = init_state(m, np)
    chosen = np.zeros(n, dtype=np.int64)
    p_sel = np.zeros(n)
    e_cost = np.zeros(n)
    for kind, j in zip(ev_kind, ev_j):
        if kind == 0:
            p = sample_probs(spec.kind, st, gammas[j], np)
            c = _sample_cdf(p, u[j])
            chosen[j] = c
            p_sel[j] = p[c]
            e_cost[j] = float(p @ C[j])
        else:
            oh = np.where(np.arange(m) == chosen[j], 1.0, 0.0)
            st = update_state(spec.kind, st, C[j], oh, p_sel[j], etas[j], np)
    weights = sample_probs(spec.kind, st, gammas[-1], np)
    return chosen, p_sel, e_cost, weights


def _scan_one(kind: str, ring: int):
    """The single-(scenario, instance) event scan — the traceable core
    shared by the unsharded ``_compiled_scan`` jit and the sharded fold.

    The scan carry holds only the learner state plus a small ring buffer of
    in-flight (chosen, p_chosen) pairs — the sample of job j and its
    delayed update are at most ``ring`` jobs apart, so ``j % ring`` slots
    never collide; per-job outputs leave through the scan's stacked ys
    instead of (J,)-sized carries (which would cost a dynamic-update copy
    per event).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.ref import varying_like

    def one(C2, u1, eta1, gamma1, ev_kind, ev_j):
        m = C2.shape[-1]

        def step(carry, x):
            st, rb_c, rb_p = carry
            ev_k, j = x
            slot = j % ring
            c_row = C2[j]
            p = sample_probs(kind, st, gamma1[j], jnp)
            cdf = jnp.cumsum(p)
            cdf = cdf / cdf[-1]
            c = jnp.minimum(
                jnp.searchsorted(cdf, u1[j], side="right"), m - 1)
            is_sample = ev_k == 0
            rb_c = rb_c.at[slot].set(jnp.where(is_sample, c, rb_c[slot]))
            rb_p = rb_p.at[slot].set(jnp.where(is_sample, p[c], rb_p[slot]))
            oh = jnp.where(jnp.arange(m) == rb_c[slot], 1.0, 0.0)
            new = update_state(kind, st, c_row, oh, rb_p[slot], eta1[j], jnp)
            st = jax.tree_util.tree_map(
                lambda a, b: jnp.where(is_sample, a, b), st, new)
            return (st, rb_c, rb_p), (rb_c[slot], rb_p[slot], p @ c_row)

        carry0 = varying_like(
            (init_state(m, jnp), jnp.zeros(ring, jnp.int32),
             jnp.zeros(ring)), C2, u1, eta1, gamma1, ev_kind, ev_j)
        (st, _, _), ys = jax.lax.scan(step, carry0, (ev_kind, ev_j))
        weights = sample_probs(kind, st, gamma1[-1], jnp)
        return ys[0], ys[1], ys[2], weights

    return one


def _event_ring(ev_kind: np.ndarray) -> int:
    """Max jobs simultaneously sampled-but-not-updated (+1 so the sample
    event itself fits): update j reads slot j % ring strictly before any
    sample j' >= j + ring could overwrite it."""
    inflight = np.cumsum(np.where(ev_kind == 0, 1, -1))
    return int(inflight.max(initial=0)) + 1


@functools.lru_cache(maxsize=64)   # bounded: one entry per (kind, ring)
def _compiled_scan(kind: str, ring: int):
    """Jitted vmapped event scan for one learner kind, cached across replay
    calls (a fresh closure per call would force an XLA recompile per call).
    Retraces only on new (kind, ring) or new array shapes."""
    import jax

    f = jax.vmap(_scan_one(kind, ring),
                 in_axes=(None, None, 0, 0, None, None))       # grid axis
    f = jax.vmap(f, in_axes=(0, 0, None, None, None, None))    # scenarios
    return jax.jit(f)


def _replay_jax_kind(kind, C, u, etas_k, gammas_k, ev_kind, ev_j):
    """One compiled scan per learner kind, vmapped over S scenarios x K
    schedule-grid instances. C: (S, J, P); u: (S, J); etas/gammas: (K, J)."""
    import jax.numpy as jnp

    ring = _event_ring(ev_kind)
    fn = _compiled_scan(kind, ring)
    args = (jnp.asarray(C, jnp.float32), jnp.asarray(u),
            jnp.asarray(etas_k), jnp.asarray(gammas_k),
            jnp.asarray(ev_kind), jnp.asarray(ev_j))
    record_jit("learn.scan:" + kind, fn, *args)
    with span("replay.scan", kind=kind):
        ch_e, ps_e, ec_e, weights = fn(*args)
    # Sample events occur in job order: selecting them from the per-event
    # ys yields the per-job traces.
    sample_pos = np.nonzero(ev_kind == 0)[0]
    return (np.asarray(ch_e)[..., sample_pos],
            np.asarray(ps_e)[..., sample_pos],
            np.asarray(ec_e)[..., sample_pos], weights)


@functools.lru_cache(maxsize=16)   # bounded: one entry per fold config
def _sharded_fold(smesh, kinds_sig: tuple, ring: int, k0_pos: int):
    """Sharded replay-and-fold program: scan + regret stats + ONE psum.

    Every shard replays the learners over ITS scenario slice of the padded
    cost block (grouped by learner kind in ``kinds_sig`` order — tuples of
    ``(kind, n_instances)``), computes the per-scenario regret statistics
    locally, masks the padding rows via ``valid``, reduces over its local
    scenario axis, and packs every per-learner sum into ONE flat vector so
    the chunk's entire cross-device traffic is a single ``lax.psum`` over
    the ``"data"`` axis — the one collective the DESIGN.md §9 contract
    allows per chunk. On a 2-D ``GridMesh`` the ``"model"`` axis sees
    replicated inputs (specs below never mention it), so every model
    column computes identical sums and the psum stays ONE all-reduce over
    ``"data"`` only — the group axis adds no traffic. The second
    output (per-scenario realized regret of original learner 0, position
    ``k0_pos`` in grouped order) stays sharded — it is the adaptive
    adversary's feedback signal and never crosses devices.

    ``acc`` is the running flat accumulator CARRIED across chunks and
    DONATED (``donate_argnums=(0,)``): the returned ``acc + sums`` vector
    reuses the input's device buffer — an exact shape+dtype alias, so the
    donation is warning-free and the per-chunk accumulator costs zero
    allocations. The host reads the running value back each chunk and
    differences consecutive readings to recover the per-chunk sums
    (``replay_stream`` below), keeping the adaptive feedback loop and the
    per-chunk telemetry identical in structure to the undonated fold.
    """
    import jax
    import jax.numpy as jnp

    def fold(acc, C, u, valid, etas, gammas, ev_kind, ev_j, sample_pos, Z):
        parts = []
        i = 0
        for kind, cnt in kinds_sig:
            f = jax.vmap(_scan_one(kind, ring),
                         in_axes=(None, None, 0, 0, None, None))
            f = jax.vmap(f, in_axes=(0, 0, None, None, None, None))
            parts.append(f(C, u, etas[i:i + cnt], gammas[i:i + cnt],
                           ev_kind, ev_j))
            i += cnt
        ch = jnp.concatenate([p[0] for p in parts], axis=1)
        ec = jnp.concatenate([p[2] for p in parts], axis=1)
        w = jnp.concatenate([p[3] for p in parts], axis=1)
        # Sample events occur in job order: selecting them from the
        # per-event ys yields the (S_l, K, J) per-job traces.
        ch = jnp.take(ch, sample_pos, axis=2)
        ec = jnp.take(ec, sample_pos, axis=2)
        zsum = Z.sum()
        per_job = jnp.take_along_axis(
            C[:, None], ch[..., None], axis=3)[..., 0]          # (S_l, K, J)
        realized = (per_job * Z).sum(axis=2) / zsum             # (S_l, K)
        expected = (ec * Z).sum(axis=2) / zsum
        fixed_cum = (C * Z[:, None]).cumsum(axis=1)             # (S_l, J, P)
        best_fixed = fixed_cum[:, -1].min(axis=1) / zsum        # (S_l,)
        regret = realized - best_fixed[:, None]                 # (S_l, K)
        cum_real = jnp.cumsum(per_job * Z, axis=2)              # (S_l, K, J)
        p_star = jnp.argmin(fixed_cum[:, -1], axis=1)           # (S_l,)
        cum_best = jnp.take_along_axis(
            fixed_cum, p_star[:, None, None], axis=2)[..., 0]   # (S_l, J)
        curve = (cum_real - cum_best[:, None]) / jnp.cumsum(Z)
        top_w = w.max(axis=2)                                   # (S_l, K)
        v = valid.astype(C.dtype)
        v1 = v[:, None]
        v2 = v[:, None, None]
        sums = jnp.concatenate([
            (realized * v1).sum(0),
            (expected * v1).sum(0),
            (regret * v1).sum(0),
            (regret ** 2 * v1).sum(0),
            (best_fixed * v).sum()[None],
            (curve * v2).sum(0).ravel(),
            (curve ** 2 * v2).sum(0).ravel(),
            (w * v2).sum(0).ravel(),
            (top_w * v1).sum(0),
            v.sum()[None],
        ])
        sums = jax.lax.psum(sums, "data")   # the one collective per chunk
        return acc + sums, regret[:, k0_pos]

    dp = smesh.spec("scenario")
    rp = smesh.spec()
    return jax.jit(jax.shard_map(
        fold, mesh=smesh.mesh,
        in_specs=(rp, dp, dp, dp, rp, rp, rp, rp, rp, rp),
        out_specs=(rp, dp)),
        donate_argnums=(0,))


def fold_acc_size(K: int, J: int, P: int) -> int:
    """Length of the packed fold vector (the ``_unpack_fold`` layout)."""
    return 5 * K + 2 * K * J + K * P + 2


def _unpack_fold(flat: np.ndarray, K: int, J: int, P: int):
    """Split the psum'd flat vector back into the named per-learner sums
    (grouped-learner order — callers reindex by the inverse permutation)."""
    o = 0

    def take(n):
        nonlocal o
        v = flat[o:o + n]
        o += n
        return v

    out = {
        "realized": take(K), "expected": take(K), "regret": take(K),
        "regret_sq": take(K), "best_fixed": float(take(1)[0]),
        "curve": take(K * J).reshape(K, J),
        "curve_sq": take(K * J).reshape(K, J),
        "weights": take(K * P).reshape(K, P),
        "top_weight": take(K), "n": int(round(float(take(1)[0]))),
    }
    assert o == len(flat)
    return out


def _weight_metrics(specs, weights_mean) -> None:
    """Per-chunk learner telemetry: Shannon entropy (nats) of the mean
    weight posterior and the heaviest expert's share, one labeled series
    per learner instance. No-op unless the metrics registry is collecting."""
    if not METRICS.enabled:
        return
    w = np.maximum(np.asarray(weights_mean, np.float64), 0.0)
    w = w / np.maximum(w.sum(axis=1, keepdims=True), 1e-300)
    ent = -(w * np.log(np.maximum(w, 1e-300))).sum(axis=1)
    top = w.max(axis=1)
    hist = METRICS.histogram("learn.weight_entropy")
    gauge = METRICS.gauge("learn.top_weight")
    for k, sp in enumerate(specs):
        label = f"{k}:{sp.kind}"
        hist.observe(float(ent[k]), learner=label)
        gauge.set(float(top[k]), learner=label)


def replay(
    C,
    arrivals,
    d: float,
    workload=None,
    learners=("hedge",),
    seed: int = 0,
    rng: np.random.Generator | None = None,
    backend: str = "auto",
) -> LearnResult:
    """Replay a batch of learners over a (S, J, P) counterfactual tensor.

    ``C`` is the engine's cost tensor (an ``EngineResult``, its
    ``unit_cost``, or a raw (J, P) / (S, J, P) array); ``arrivals`` the
    arrival-ordered job times, ``d`` the max relative deadline (feedback
    delay), ``workload`` the per-job Z_j used by the regret accounting
    (defaults to 1). ``learners`` is a flat list of kinds / ``LearnerSpec``s
    — a schedule grid is expressed as more specs; the result keeps their
    order. ``rng`` (single-scenario only) draws the uniform stream from a
    live generator — the hook ``run_tola_scenarios`` uses to stay
    bit-compatible with its legacy sampling stream; otherwise scenario s
    uses ``seed + s``.
    """
    if hasattr(C, "unit_cost"):
        if workload is None:
            workload = C.workload
        C = C.unit_cost
    # Device (jax) tensors stay on device: the compiled scan consumes them
    # directly without the numpy float64 staging copy; only the numpy
    # oracle and the result container force a host copy. (The engine still
    # emits host tensors, so this path serves callers that already hold the
    # cost tensor on device.)
    on_device = type(C).__module__.split(".")[0] in ("jax", "jaxlib")
    if not on_device:
        C = np.asarray(C, dtype=np.float64)
    if C.ndim == 2:
        C = C[None]
    if C.ndim != 3:
        raise ValueError(f"cost tensor must be (S, J, P); got {C.shape}")
    S, n, m = C.shape
    arrivals = np.asarray(arrivals, dtype=np.float64)
    if len(arrivals) != n:
        raise ValueError("arrivals length != n_jobs axis of C")
    Z = np.ones(n) if workload is None else np.asarray(workload, np.float64)
    specs = [as_spec(l) for l in learners]
    if not specs:
        raise ValueError("need at least one learner")
    backend = resolve_backend(backend)

    ev_kind, ev_j, n_done = build_events(arrivals, d)
    etas = np.stack([sp.eta.values(arrivals, d, m) for sp in specs])
    gammas = np.stack([sp.explore.values(arrivals, d, m) for sp in specs])
    if rng is not None:
        if S != 1:
            raise ValueError("rng streams are single-scenario only")
        u = rng.random(n)[None]
    else:
        u = np.stack([np.random.default_rng(seed + s).random(n)
                      for s in range(S)])

    K = len(specs)
    chosen = np.zeros((S, K, n), dtype=np.int64)
    p_sel = np.zeros((S, K, n))
    e_cost = np.zeros((S, K, n))
    weights = np.zeros((S, K, m))

    with span("replay", backend=backend, scenarios=S, learners=K):
        if backend == "numpy":
            if on_device:
                C = np.asarray(C, dtype=np.float64)
                on_device = False
            for s in range(S):
                for k, sp in enumerate(specs):
                    out = _replay_numpy_one(C[s], sp, u[s], ev_kind, ev_j,
                                            etas[k], gammas[k])
                    chosen[s, k], p_sel[s, k], e_cost[s, k], \
                        weights[s, k] = out
        else:
            pallas_ks: list[int] = []
            if backend == "pallas":
                # The fused kernel implements the full-information
                # exponentiated-weights trajectory — hedge instances only.
                pallas_ks = [k for k, sp in enumerate(specs)
                             if sp.kind == "hedge"]
                if pallas_ks:
                    from repro.kernels.weight_update import hedge_replay
                    out = hedge_replay(C, etas[pallas_ks], u, n_done)
                    for i, k in enumerate(pallas_ks):
                        chosen[:, k] = out["chosen"][:, i]
                        p_sel[:, k] = out["p_chosen"][:, i]
                        e_cost[:, k] = out["expected_cost"][:, i]
                        weights[:, k] = out["weights"][:, i]
            by_kind: dict[str, list[int]] = {}
            for k, sp in enumerate(specs):
                if k not in pallas_ks:
                    by_kind.setdefault(sp.kind, []).append(k)
            for kind, ks in by_kind.items():
                out = _replay_jax_kind(kind, C, u, etas[ks], gammas[ks],
                                       ev_kind, ev_j)
                ch, ps, ec, wf = (np.asarray(o, np.float64) for o in out)
                for i, k in enumerate(ks):
                    chosen[:, k] = ch[:, i].astype(np.int64)
                    p_sel[:, k] = ps[:, i]
                    e_cost[:, k] = ec[:, i]
                    weights[:, k] = wf[:, i]

    return LearnResult(
        specs=specs, chosen=chosen, p_chosen=p_sel, expected_unit=e_cost,
        weights=weights, unit_cost=np.asarray(C, dtype=np.float64),
        arrivals=arrivals, workload=Z,
        feedback_delay=float(d), backend=backend)


def replay_stream(
    jobs,
    policies,
    scenarios,
    r_total: int = 0,
    *,
    learners=("hedge",),
    seed: int = 0,
    scenario_chunk: int | None = None,
    backend: str = "auto",
    engine_backend: str = "auto",
    windows: str = "dealloc",
    selfowned: str = "prop12",
    early_start: bool = True,
    mesh=None,
    overlap: bool | None = None,
) -> StreamLearnResult:
    """Regret curves straight from a scenario stream — no (S, J, P) tensor.

    The engine evaluates ``scenario_chunk`` scenarios per pass
    (``evaluate_grid_chunks`` — one shared grid plan, device-synthesized
    price paths for the jax/pallas engine backends, no per-scenario Python
    market objects on the hot path), each chunk's counterfactual cost
    tensor is replayed by every learner in ``learners`` (scenario s keeps
    replay seed ``seed + s``, so the sampled traces are identical to a
    monolithic ``replay`` over the materialized tensor), and the per-chunk
    ``LearnResult`` is folded into a ``StreamLearnResult`` — running at
    S = 10^4-10^6 scenarios with chunk-sized peak memory.

    ``mesh`` (a ``GridMesh`` / shard count / ``None``) shards the
    scenario axis across a device mesh: the engine chunk is evaluated
    sharded (DESIGN.md §9 — over BOTH axes of a 2-D mesh) AND the replay
    fold runs as a ``shard_map`` program whose only cross-device
    communication is one ``psum`` of the packed per-learner sums per
    chunk, over ``"data"`` only (``_sharded_fold``). The fold's
    device arithmetic is float32, so its statistics agree with the host
    fold to ~1e-4 rather than bitwise. Requires jax replay and engine
    backends. ``overlap`` double-buffers chunk synthesis (see
    ``evaluate_grid``); it is rejected for adaptive sources, whose next
    chunk depends on this chunk's feedback.

    When ``scenarios`` is an adaptive ``ScenarioSpec`` / ``ScenarioStream``
    the chunk's realized regret of ``learners[0]`` is fed back through
    ``ScenarioStream.observe`` BEFORE the next chunk is synthesized: the
    adversary watches the learner at chunk boundaries and concentrates its
    spikes on the most harmful period (the ROADMAP adaptive-adversary
    round trip).
    """
    from repro.engine.api import evaluate_grid_chunks
    from repro.engine.mesh import as_scenario_mesh
    from repro.engine.scenarios import as_source

    if not jobs:
        raise ValueError("need jobs")
    arrivals = np.array([j.arrival for j in jobs])
    if np.any(np.diff(arrivals) < -1e-9):
        raise ValueError("jobs must be arrival-ordered")
    d = max(j.deadline - j.arrival for j in jobs)
    Z = np.array([j.total_work for j in jobs])
    specs = [as_spec(l) for l in learners]
    if not specs:
        raise ValueError("need at least one learner")
    backend = resolve_backend(backend)
    mesh = as_scenario_mesh(mesh)
    if mesh is not None and backend != "jax":
        raise ValueError(
            f"mesh= shards the jax replay fold; replay backend resolved to "
            f"{backend!r} (pass backend='jax' or leave it 'auto' with jax "
            f"installed)")

    source = as_source(scenarios)
    acc = StreamLearnResult(specs=specs, feedback_delay=float(d),
                            backend=backend)
    stream = evaluate_grid_chunks(
        jobs, policies, source, r_total,
        scenario_chunk=scenario_chunk, windows=windows,
        selfowned=selfowned, early_start=early_start, pool="dedicated",
        backend=engine_backend, mesh=mesh,
        overlap=overlap)
    if mesh is None:
        with span("replay_stream", backend=backend):
            for ci, ch in enumerate(stream):
                with span("fold", chunk=ci, s0=ch.s0, s1=ch.s1):
                    lr = replay(ch.unit_cost, arrivals, d, workload=Z,
                                learners=specs, seed=seed + ch.s0,
                                backend=backend)
                    feedback = acc.fold(lr)
                _weight_metrics(specs, lr.weights.mean(axis=0))
                # The chunk-boundary round trip: a no-op for every
                # non-adaptive source; the generator builds the NEXT chunk
                # only after this returns, so the adversary's state is
                # current when spikes land.
                source.observe(feedback)
        acc.obs = maybe_snapshot()
        return acc

    import jax.numpy as jnp

    # Everything chunk-invariant, once: the event stream, the (K, J)
    # schedule grids REORDERED so instances of one kind are contiguous
    # (``_sharded_fold`` runs one scan program per kind group), and the
    # inverse permutation that puts the folded sums back in specs order.
    J, m = len(jobs), len(policies)
    ev_kind, ev_j, _ = build_events(arrivals, d)
    sample_pos = np.nonzero(ev_kind == 0)[0].astype(np.int32)
    ring = _event_ring(ev_kind)
    by_kind: dict[str, list[int]] = {}
    for k, sp in enumerate(specs):
        by_kind.setdefault(sp.kind, []).append(k)
    perm = np.array([k for ks in by_kind.values() for k in ks])
    inv_perm = np.argsort(perm)
    kinds_sig = tuple((kind, len(ks)) for kind, ks in by_kind.items())
    etas = np.stack([sp.eta.values(arrivals, d, m) for sp in specs])[perm]
    gammas = np.stack([sp.explore.values(arrivals, d, m)
                       for sp in specs])[perm]
    fold_fn = _sharded_fold(mesh, kinds_sig, ring, int(inv_perm[0]))
    consts = (jnp.asarray(etas, jnp.float32), jnp.asarray(gammas,
              jnp.float32), jnp.asarray(ev_kind), jnp.asarray(ev_j),
              jnp.asarray(sample_pos), jnp.asarray(Z, jnp.float32))
    # The donated accumulator carry: the device keeps ONE running f32
    # vector whose buffer is recycled every chunk (donate_argnums above);
    # the host differences consecutive readings to recover the per-chunk
    # sums the telemetry and the adaptive feedback consume.
    dev_acc = jnp.zeros(fold_acc_size(len(specs), J, m), jnp.float32)
    prev_acc = np.zeros(dev_acc.shape[0], np.float64)

    with span("replay_stream", backend=backend, sharded=True):
        for ci, ch in enumerate(stream):
            Sc = ch.unit_cost.shape[0]
            u = np.stack([np.random.default_rng(seed + ch.s0 + s).random(J)
                          for s in range(Sc)])
            valid = np.zeros(mesh.pad(Sc), bool)
            valid[:Sc] = True
            with span("fold", chunk=ci, s0=ch.s0, s1=ch.s1):
                args = (dev_acc,
                        mesh.put_rows(np.asarray(ch.unit_cost, np.float32)),
                        mesh.put_rows(np.asarray(u, np.float32)),
                        mesh.put_rows(valid)) + consts
                record_jit("learn.fold:sharded", fold_fn, *args)
                dev_acc, regret_s = fold_fn(*args)
                cur_acc = np.asarray(dev_acc, np.float64)
                g = _unpack_fold(cur_acc - prev_acc, len(specs), J, m)
                prev_acc = cur_acc
                acc.fold_sums(
                    g["n"], g["realized"][inv_perm], g["expected"][inv_perm],
                    g["regret"][inv_perm], g["regret_sq"][inv_perm],
                    g["best_fixed"], g["curve"][inv_perm],
                    g["curve_sq"][inv_perm], g["weights"][inv_perm],
                    g["top_weight"][inv_perm])
            _weight_metrics(specs,
                            g["weights"][inv_perm] / max(g["n"], 1))
            source.observe(np.asarray(regret_s, np.float64)[:Sc])
    acc.obs = maybe_snapshot()
    return acc
