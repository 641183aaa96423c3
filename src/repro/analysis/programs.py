"""Layer-2: the compiled-program verifier (DESIGN.md §12).

Abstract-traces the registered jit factories — the six ``record_jit``
program families plus the pallas launchers — on canonical small shapes
(``jax.ShapeDtypeStruct`` args: tracing and AOT compilation only, no
device execution) and statically asserts the contracts that runtime
tests used to grep compiled HLO for:

- **placement (§9)**: zero collectives in the sharded synth/views/eval
  hot loop; exactly ONE packed all-reduce (the ``lax.psum``) in
  ``learn.fold:sharded`` — per-kind op counts from
  :func:`repro.obs.compiled.collective_counts` over the compiled text;
- **callback-free hot path**: no ``pure_callback``/``io_callback``/
  ``debug_callback`` primitives anywhere in the jaxpr (recursing into
  sub-jaxprs: pjit bodies, scan/cond branches, shard_map, pallas);
- **dtype lattice (§6)**: no f64/c128 aval anywhere in the jaxpr — the
  f64 oracle is host numpy, never a traced program;
- **donation validity (§11)**: each donated argnum's shape+dtype matches
  an output aval exactly, so the alias is warning-free;
- **weak types**: no weakly-typed OUTPUT aval — a weak output re-enters
  the next program with a different aval than a strong one and
  fragments downstream jit caches.

Program inventory (canonical shapes mirror the real call sites; on a 2-D
``GridMesh`` scenario rows scale with ``data_shards`` and group rows with
``model_shards``, so every axis divides its mesh axis exactly):

============================  ============================================
engine.eval.chain:sharded     ``backend_jax._sharded_fns(mesh)["chain"]``
engine.eval.task:sharded      ``backend_jax._sharded_fns(mesh)["task"]``
engine.eval.chain_ps:sharded  ``_sharded_fns(mesh)["chain_ps"]`` (refined)
engine.eval.task_ps:sharded   ``_sharded_fns(mesh)["task_ps"]`` (refined)
scenarios.synth:fresh:shd     ``scenarios._device_synth_fn(spec, mesh)``
scenarios.views:sharded       ``scenarios._device_views_fn(slot, mesh)``
plan.device.full              ``plan._device_plan_fns("prop12", "dealloc")``
learn.scan:hedge              ``replay._compiled_scan("hedge", ring)``
learn.fold:sharded            ``replay._sharded_fold(mesh, ...)`` (donated)
kernels.policy_cost.chain     ``policy_cost_chain`` (interpret pallas)
kernels.hedge_replay          ``weight_update._hedge_call`` (interpret)
kernels.flash_attention       ``ops._flash_jit`` (interpret pallas)
kernels.ssd_scan              ``ops._ssd_jit`` (interpret pallas)
============================  ============================================

The ``_ps`` (per-scenario availability, i.e. TOLA pool-refinement) eval
programs carry (S, R, L) self-owned stacks sharded over BOTH mesh axes
and, like the plain eval programs, must compile to ZERO collectives —
refinement rounds cost no cross-device traffic either.

The verifier is what ``tests/test_shard.py``'s collective assertions and
``obs.compiled``'s standing §9 check delegate to — one implementation of
the placement contract.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

__all__ = [
    "CheckResult", "ProgramSpec", "PROGRAM_KEYS", "program_inventory",
    "verify_program", "verify_all",
]

_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})

# §9 placement contract: exact per-kind collective op counts.
_ZERO = {"total": 0}
_ONE_PSUM = {"all-reduce": 1, "total": 1}

PROGRAM_KEYS = (
    "engine.eval.chain:sharded",
    "engine.eval.task:sharded",
    "engine.eval.chain_ps:sharded",
    "engine.eval.task_ps:sharded",
    "scenarios.synth:fresh:sharded",
    "scenarios.views:sharded",
    "plan.device.full",
    "learn.scan:hedge",
    "learn.fold:sharded",
    "kernels.policy_cost.chain",
    "kernels.hedge_replay",
    "kernels.flash_attention",
    "kernels.ssd_scan",
)


@dataclasses.dataclass(frozen=True)
class CheckResult:
    """One contract assertion on one program."""

    program: str
    check: str      # collectives | callbacks | dtype | donation | weak-type | build
    ok: bool
    detail: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ProgramSpec:
    key: str
    fn: object                  # jit-wrapped callable (has .lower)
    args: tuple                 # ShapeDtypeStructs + Python scalars
    collectives: dict           # expected exact counts (subset of kinds)
    donated: tuple = ()         # argnums whose buffers the program donates


# --------------------------------------------------------------------------
# Jaxpr walking (duck-typed: no jax.core imports)
# --------------------------------------------------------------------------

def _subjaxprs(params: dict):
    """Sub-jaxprs hiding in eqn params: pjit/scan/cond/shard_map/pallas."""
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(item, "eqns"):
                yield item


def _jaxpr_stats(closed) -> tuple[set, list]:
    """(primitive names, wide-dtype aval descriptions) over all sub-jaxprs."""
    prims: set[str] = set()
    wide: list[str] = []

    def _aval(var):
        aval = getattr(var, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None and str(dt) in ("float64", "complex128", "int64"):
            if str(dt) != "int64":      # int64 indices are canonicalized
                wide.append(f"{str(dt)}{getattr(aval, 'shape', ())}")

    stack = [getattr(closed, "jaxpr", closed)]
    seen: set[int] = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for var in (*j.invars, *j.outvars, *j.constvars):
            _aval(var)
        for eqn in j.eqns:
            prims.add(eqn.primitive.name)
            for var in (*eqn.invars, *eqn.outvars):
                _aval(var)
            stack.extend(_subjaxprs(eqn.params))
    return prims, wide


def _flatten_shapes(tree) -> list:
    import jax

    return jax.tree_util.tree_leaves(tree)


# --------------------------------------------------------------------------
# Per-program verification
# --------------------------------------------------------------------------

def verify_program(fn, args: Sequence, *, key: str = "?",
                   collectives: dict | None = None,
                   donated: Sequence[int] = ()) -> list[CheckResult]:
    """Run every static check on one program; never executes it."""
    import jax

    results: list[CheckResult] = []

    # ---- jaxpr-level checks: callbacks + dtype lattice -------------------
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    except Exception as exc:
        return [CheckResult(key, "build", False,
                            f"trace failed: {type(exc).__name__}: {exc}")]
    prims, wide = _jaxpr_stats(jaxpr)
    bad_cb = sorted(prims & _CALLBACK_PRIMS)
    results.append(CheckResult(
        key, "callbacks", not bad_cb,
        f"callback primitives in jaxpr: {bad_cb}" if bad_cb
        else "no callback primitives"))
    results.append(CheckResult(
        key, "dtype", not wide,
        f"wide dtypes in jaxpr: {sorted(set(wide))}" if wide
        else "dtype lattice clean (no f64/c128 avals)"))

    # ---- output avals: donation aliasing + weak-type leakage -------------
    try:
        out = jax.eval_shape(fn, *args)
    except Exception as exc:
        results.append(CheckResult(key, "weak-type", False,
                                   f"eval_shape failed: {exc}"))
        out = None
    if out is not None:
        leaves = _flatten_shapes(out)
        weak = [f"output[{i}] {l.shape} {l.dtype}"
                for i, l in enumerate(leaves)
                if getattr(l, "weak_type", False)]
        results.append(CheckResult(
            key, "weak-type", not weak,
            f"weakly-typed outputs: {weak}" if weak
            else "all outputs strongly typed"))
        for argnum in donated:
            arg = args[argnum]
            aliased = any(
                tuple(l.shape) == tuple(arg.shape) and l.dtype == arg.dtype
                for l in leaves)
            results.append(CheckResult(
                key, "donation", aliased,
                f"donated arg {argnum} shape={tuple(arg.shape)} "
                f"dtype={arg.dtype} "
                + ("aliases an output exactly" if aliased else
                   "matches NO output aval — donation would be dropped "
                   "with a warning")))

    # ---- compiled HLO: §9 collective placement ---------------------------
    if collectives is not None:
        from repro.obs.compiled import collective_counts
        try:
            txt = fn.lower(*args).compile().as_text()
        except Exception as exc:
            results.append(CheckResult(
                key, "collectives", False,
                f"lower/compile failed: {type(exc).__name__}: {exc}"))
            return results
        counts = collective_counts(txt)
        bad = {k: (counts.get(k, 0), v) for k, v in collectives.items()
               if counts.get(k, 0) != v}
        results.append(CheckResult(
            key, "collectives", not bad,
            (f"collective counts off contract: "
             + ", ".join(f"{k}={got} (want {want})"
                         for k, (got, want) in sorted(bad.items()))
             + f"; full counts {counts}") if bad
            else f"placement contract holds: {counts}"))
    return results


# --------------------------------------------------------------------------
# Canonical program inventory
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _build_eval_programs(mesh) -> list[ProgramSpec]:
    import jax.numpy as jnp

    from repro.engine import backend_jax as bj

    # Scenario rows ride "data", group rows ride "model": size each axis
    # by its own shard count so the canonical shapes divide exactly.
    d, m = mesh.data_shards, mesh.model_shards
    fns = bj._sharded_fns(mesh)
    A = _sds((d, 11), jnp.float32)
    R, L = 4 * m, 3
    chain_args = (A, A, _sds((R,), jnp.float32), _sds((R, L), jnp.float32),
                  _sds((R, L), jnp.float32), _sds((R, L), jnp.float32),
                  _sds((R, L), jnp.bool_), 1.0, 1.0)
    F = 12 * m
    task_args = (A, A, _sds((F,), jnp.float32), _sds((F,), jnp.float32),
                 _sds((F,), jnp.float32), _sds((F,), jnp.float32),
                 1.0, 1.0)
    chain_ps_args = (A, A, _sds((R,), jnp.float32),
                     _sds((R, L), jnp.float32),
                     _sds((d, R, L), jnp.float32),
                     _sds((d, R, L), jnp.float32),
                     _sds((d, R, L), jnp.bool_), 1.0, 1.0)
    task_ps_args = (A, A, _sds((F,), jnp.float32), _sds((F,), jnp.float32),
                    _sds((d, F), jnp.float32), _sds((d, F), jnp.float32),
                    1.0, 1.0)
    return [
        ProgramSpec("engine.eval.chain:sharded", fns["chain"], chain_args,
                    dict(_ZERO)),
        ProgramSpec("engine.eval.task:sharded", fns["task"], task_args,
                    dict(_ZERO)),
        ProgramSpec("engine.eval.chain_ps:sharded", fns["chain_ps"],
                    chain_ps_args, dict(_ZERO)),
        ProgramSpec("engine.eval.task_ps:sharded", fns["task_ps"],
                    task_ps_args, dict(_ZERO)),
    ]


def _build_scenario_programs(mesh) -> list[ProgramSpec]:
    import jax.numpy as jnp

    from repro.engine.scenarios import (ScenarioSpec, _device_synth_fn,
                                        _device_views_fn)

    # Synthesis/views shard over "data" only (replicated over "model").
    n = mesh.data_shards
    spec = ScenarioSpec("fresh", 8.0, n, seed=1)
    synth = _device_synth_fn(spec, mesh)
    z = _sds((n, spec.n_slots), jnp.float32)
    idx = _sds((n,), jnp.int32)
    views = _device_views_fn(1.0 / 12.0, mesh)
    h = _sds((n, spec.n_slots), jnp.uint32)
    price = _sds((n, spec.n_slots), jnp.float32)
    spike = _sds((n, spec.n_slots), jnp.bool_)
    thresh = _sds((n,), jnp.uint32)
    return [
        ProgramSpec("scenarios.synth:fresh:sharded", synth,
                    (idx, z, z, z), dict(_ZERO)),
        ProgramSpec("scenarios.views:sharded", views,
                    (h, price, spike, thresh, False), dict(_ZERO)),
    ]


def _build_plan_program() -> list[ProgramSpec]:
    import jax.numpy as jnp

    from repro.engine.plan import _device_plan_fns

    fns = _device_plan_fns("prop12", "dealloc")
    J, L, W, Ga, G = 3, 2, 2, 2, 2
    jl = _sds((J, L), jnp.float32)
    args = (jl, jl, _sds((J, L), jnp.bool_), _sds((J,), jnp.float32),
            _sds((J,), jnp.float32), jl, _sds((W,), jnp.float32),
            _sds((Ga,), jnp.int32), _sds((Ga,), jnp.float32), 1.0,
            _sds((G,), jnp.int32))
    return [ProgramSpec("plan.device.full", fns["full"], args, dict(_ZERO))]


def _canonical_events():
    """Tiny sample/update event stream: 3 jobs, ring 2."""
    import numpy as np

    ev_kind = np.array([0, 0, 1, 0, 1, 1], np.int32)
    ev_j = np.array([0, 1, 0, 2, 1, 2], np.int32)
    return ev_kind, ev_j, 3


def _build_learn_programs(mesh) -> list[ProgramSpec]:
    import jax.numpy as jnp

    from repro.learn.replay import (_compiled_scan, _event_ring,
                                    _sharded_fold, fold_acc_size)

    ev_kind, ev_j, J = _canonical_events()
    ring = _event_ring(ev_kind)
    P = 4
    scan = _compiled_scan("hedge", ring)
    scan_args = (_sds((2, J, P), jnp.float32), _sds((2, J), jnp.float32),
                 _sds((1, J), jnp.float32), _sds((1, J), jnp.float32),
                 _sds(ev_kind.shape, jnp.int32), _sds(ev_j.shape, jnp.int32))
    # The fold shards chunk rows over "data" and psums over "data" only;
    # a 2-D mesh's "model" axis sees replicated inputs and no collective.
    n = mesh.data_shards
    fold = _sharded_fold(mesh, (("hedge", 1),), ring, 0)
    fold_args = (_sds((fold_acc_size(1, J, P),), jnp.float32),
                 _sds((2 * n, J, P), jnp.float32),
                 _sds((2 * n, J), jnp.float32), _sds((2 * n,), jnp.bool_),
                 _sds((1, J), jnp.float32), _sds((1, J), jnp.float32),
                 _sds(ev_kind.shape, jnp.int32), _sds(ev_j.shape, jnp.int32),
                 _sds((J,), jnp.int32), _sds((J,), jnp.float32))
    return [
        ProgramSpec("learn.scan:hedge", scan, scan_args, dict(_ZERO)),
        ProgramSpec("learn.fold:sharded", fold, fold_args, dict(_ONE_PSUM),
                    donated=(0,)),
    ]


def _build_kernel_programs() -> list[ProgramSpec]:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import _flash_jit, _ssd_jit
    from repro.kernels.policy_cost import policy_cost_chain
    from repro.kernels.weight_update import _hedge_call

    out: list[ProgramSpec] = []
    # policy_cost_chain: single-bid entry, S=2 scenarios, R=4 rows, L=2.
    chain = jax.jit(functools.partial(
        policy_cost_chain, slot=1.0 / 12.0, p_od=1.0, block_rows=8,
        interpret=True))
    S, R, L, n1 = 2, 4, 2, 13
    chain_args = (_sds((S, n1), jnp.float32), _sds((S, n1), jnp.float32),
                  _sds((R,), jnp.float32), _sds((R, L), jnp.float32),
                  _sds((R, L), jnp.float32), _sds((R, L), jnp.float32),
                  _sds((R, L), jnp.float32))
    out.append(ProgramSpec("kernels.policy_cost.chain", chain, chain_args,
                           dict(_ZERO)))
    # hedge_replay's traceable core on its padded layout (S=2, K=1): one
    # 128-job block, one 512-row trajectory ring, 4 policies in 128 lanes.
    P, BJ, W = 4, 128, 512
    Jp, Pp = 128, 128
    hedge = jax.jit(functools.partial(
        _hedge_call, K=1, W=W, Pp=Pp, m=P, BJ=BJ, interpret=True))
    hedge_args = (_sds((2, Jp, Pp), jnp.float32),
                  _sds((1, 1, Jp), jnp.float32),
                  _sds((2, 1, Jp), jnp.float32), _sds((1, Jp), jnp.int32))
    out.append(ProgramSpec("kernels.hedge_replay", hedge, hedge_args,
                           dict(_ZERO)))
    # flash attention fwd: 2 heads, Sq=Sk=8, dh=8, one block.
    flash = jax.jit(functools.partial(
        _flash_jit, causal=True, window=0, prefix=0, block_q=8, block_k=8,
        interpret=True))
    q = _sds((2, 8, 8), jnp.float32)
    out.append(ProgramSpec("kernels.flash_attention", flash, (q, q, q),
                           dict(_ZERO)))
    # ssd scan: Bb=1, S=8, H=2, P=4, G=1, N=4, one chunk.
    ssd = jax.jit(functools.partial(_ssd_jit, chunk=8, interpret=True))
    ssd_args = (_sds((1, 8, 2, 4), jnp.float32), _sds((1, 8, 2), jnp.float32),
                _sds((2,), jnp.float32), _sds((1, 8, 1, 4), jnp.float32),
                _sds((1, 8, 1, 4), jnp.float32))
    out.append(ProgramSpec("kernels.ssd_scan", ssd, ssd_args, dict(_ZERO)))
    return out


def program_inventory(mesh=None, keys: Sequence[str] | None = None
                      ) -> tuple[list[ProgramSpec], list[CheckResult]]:
    """Build (programs, build_failures) for the canonical inventory.

    ``mesh=None`` creates the default :class:`GridMesh` over all visible
    devices — a 1-D (data-only) mesh; pass ``GridMesh.create(n, m)`` to
    verify the 2-D scenario x group placement. (1-device degenerate mesh
    in single-device CI; the static-analysis and shard-smoke CI jobs force
    8 host devices so the sharded programs verify with real cross-device
    axes, including 4x2/2x4 grids.)
    """
    from repro.engine import GridMesh

    if mesh is None:
        mesh = GridMesh.create()
    builders = (
        lambda: _build_eval_programs(mesh),
        lambda: _build_scenario_programs(mesh),
        _build_plan_program,
        lambda: _build_learn_programs(mesh),
        _build_kernel_programs,
    )
    programs: list[ProgramSpec] = []
    failures: list[CheckResult] = []
    for build in builders:
        try:
            programs.extend(build())
        except Exception as exc:
            failures.append(CheckResult(
                getattr(build, "__name__", "inventory"), "build", False,
                f"{type(exc).__name__}: {exc}"))
    if keys is not None:
        want = set(keys)
        unknown = want - {p.key for p in programs}
        for k in sorted(unknown):
            failures.append(CheckResult(k, "build", False,
                                        "unknown program key"))
        programs = [p for p in programs if p.key in want]
    return programs, failures


def verify_all(mesh=None, keys: Sequence[str] | None = None
               ) -> list[CheckResult]:
    """Verify every inventory program; returns all check results."""
    programs, results = program_inventory(mesh, keys)
    for p in programs:
        results.extend(verify_program(
            p.fn, p.args, key=p.key, collectives=p.collectives,
            donated=p.donated))
    return results
