"""Compiled-program introspection: flops/bytes/collective counts per key.

Engine call sites announce every cached jit program they fetch via
``record_jit("engine.eval.chain:sharded", fn, *args)``.  Outside a
:func:`capture` context that hook is a single ContextVar read.  Inside
one, the first announcement of each key lowers and compiles ``fn`` on the
announced example arguments and records:

* ``flops`` / ``bytes`` / per-kind collective **bytes** from
  ``repro.launch.hlo_analysis.analyze`` (the cost-model pass the roofline
  section already uses), and
* per-kind collective **op counts** from :func:`collective_counts` —
  the same regex family the shard tests assert with, turned into a
  standing metric (PR 6's placement contract: zero collectives in the
  eval/synth hot loop, exactly one all-reduce in the streamed fold).

Subsequent announcements of the same key only bump its ``captures``
counter — a per-key compile-cache hit count.  :func:`factory_caches`
additionally snapshots the ``lru_cache`` hit/miss stats of every
compiled-fn factory in the engine/learn stack, so a snapshot shows both
*what* was compiled and *how often* each cache was re-entered.

Keys in use (see DESIGN.md Section 10): ``plan.device.full``,
``scenarios.synth:<kind>[:sharded]``, ``scenarios.views[:sharded]``,
``engine.eval.chain[:sharded]``, ``engine.eval.task[:sharded]``,
``engine.eval.chain_ps[:sharded]``, ``engine.eval.task_ps[:sharded]``
(the per-scenario-availability refinement programs, sharded over both
axes of a 2-D ``GridMesh``), ``engine.eval.pallas_chain``,
``engine.eval.pallas_task``, ``learn.scan:<kind>``,
``learn.fold:sharded``.
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from contextvars import ContextVar

__all__ = [
    "CompiledRegistry",
    "CompileWatch",
    "capture",
    "capturing",
    "collective_counts",
    "current_registry",
    "factory_caches",
    "hlo_metrics",
    "record_jit",
]

_CAPTURE: ContextVar["CompiledRegistry | None"] = ContextVar(
    "repro_obs_compiled", default=None
)

# Op-count regexes over lowered (scheduled) HLO text.  ``-start`` variants
# (async collectives) count as the op itself; ``-done`` halves do not.
_COLLECTIVE_OPS = {
    "all-reduce": r"\ball-reduce(?:-start)?\(",
    "all-gather": r"\ball-gather(?:-start)?\(",
    "reduce-scatter": r"\breduce-scatter(?:-start)?\(",
    "all-to-all": r"\ball-to-all(?:-start)?\(",
    "collective-permute": r"\bcollective-permute(?:-start)?\(",
}


def collective_counts(hlo_text):
    """Per-kind collective op counts (plus ``"total"``) in HLO text."""
    txt = hlo_text.lower()
    out = {kind: len(re.findall(pat, txt)) for kind, pat in _COLLECTIVE_OPS.items()}
    out["total"] = sum(out.values())
    return out


def hlo_metrics(fn, *args, **kwargs):
    """Lower+compile a jitted ``fn`` on example args and analyze the HLO.

    Returns ``{"flops", "bytes", "collective_bytes", "collective_counts",
    "tpu_custom_calls", "warnings"}`` — ``tpu_custom_calls`` counts the
    Mosaic (Pallas TPU) kernels in the program, 0 for interpret mode.
    This is the programmatic face of the shard tests' "grep the compiled
    text" assertions.
    """
    from repro.launch.hlo_analysis import analyze

    txt = fn.lower(*args, **kwargs).compile().as_text()
    a = analyze(txt)
    return {
        "flops": a["flops"],
        "bytes": a["bytes"],
        "collective_bytes": a["collectives"],
        "collective_counts": collective_counts(txt),
        "tpu_custom_calls": txt.count('custom_call_target="tpu_custom_call"'),
        "warnings": a["warnings"],
    }


class CompiledRegistry:
    """key -> hlo metrics for every program announced under a capture."""

    def __init__(self):
        self.entries: dict[str, dict] = {}

    def record(self, key, fn, args=(), kwargs=None):
        entry = self.entries.get(key)
        if entry is not None:
            entry["captures"] += 1
            return entry
        try:
            entry = hlo_metrics(fn, *args, **(kwargs or {}))
        except Exception as exc:  # keep capture best-effort: never break the run
            entry = {"error": f"{type(exc).__name__}: {exc}"}
        entry["captures"] = 1
        self.entries[key] = entry
        return entry

    def __getitem__(self, key):
        return self.entries[key]

    def __contains__(self, key):
        return key in self.entries

    def snapshot(self):
        return {"programs": dict(self.entries), "factory_caches": factory_caches()}

    def table(self):
        """Human-readable program x {flops, bytes, collectives} table."""
        rows = [f"{'program':<34} {'gflops':>9} {'MB':>9} {'collectives':>12}"]
        for key in sorted(self.entries):
            e = self.entries[key]
            if "error" in e:
                rows.append(f"{key:<34} <{e['error']}>")
                continue
            cc = e["collective_counts"]
            kinds = ",".join(f"{k}x{n}" for k, n in cc.items()
                             if k != "total" and n) or "none"
            rows.append(
                f"{key:<34} {e['flops'] / 1e9:>9.3f} {e['bytes'] / 1e6:>9.2f} "
                f"{kinds:>12}"
            )
        return "\n".join(rows)


def record_jit(key, fn, *args, **kwargs):
    """Announce a compiled program fetch; no-op unless capturing."""
    reg = _CAPTURE.get()
    if reg is not None:
        reg.record(key, fn, args, kwargs)


@contextmanager
def capture(registry=None):
    """Enable compiled-program capture for the block; yields the registry."""
    reg = registry if registry is not None else CompiledRegistry()
    token = _CAPTURE.set(reg)
    try:
        yield reg
    finally:
        _CAPTURE.reset(token)


def current_registry():
    return _CAPTURE.get()


def capturing():
    return _CAPTURE.get() is not None


# lru_cache'd compiled-fn factories across the stack, plus the cross-call
# plan/view caches of ``repro.engine.cache``, snapshotted for the
# per-cache-key hit/miss/eviction counters.  Imported lazily: jax (and the
# engine) may be absent or expensive, and obs must stay import-light.
_FACTORIES = (
    ("scenarios.synth_fn", "repro.engine.scenarios", "_device_synth_fn"),
    ("scenarios.views_fn", "repro.engine.scenarios", "_device_views_fn"),
    ("plan.device_fns", "repro.engine.plan", "_device_plan_fns"),
    ("engine.sharded_fns", "repro.engine.backend_jax", "_sharded_fns"),
    ("learn.scan", "repro.learn.replay", "_compiled_scan"),
    ("learn.fold", "repro.learn.replay", "_sharded_fold"),
    ("engine.plan_cache", "repro.engine.cache", "PLAN_CACHE"),
    ("engine.view_cache", "repro.engine.cache", "VIEW_CACHE"),
)


def jit_factories():
    """The registered compiled-fn factory registry: (name, module, attr).

    The programmatic face of ``_FACTORIES`` — ``repro.analysis.programs``
    builds its canonical program inventory from the same factories this
    module snapshots cache stats for.
    """
    return _FACTORIES


def placement_violations(mesh=None, keys=None):
    """Failed §9-placement (and related) checks over the canonical programs.

    Delegates to the Layer-2 verifier in :mod:`repro.analysis.programs` —
    the single implementation of the placement contract — and returns only
    the failed :class:`CheckResult`s (empty list = contract holds).  Pass
    a 2-D ``GridMesh`` to assert the scenario x group placement (the
    refinement ``_ps`` programs included).
    """
    from repro.analysis.programs import verify_all

    return [c for c in verify_all(mesh=mesh, keys=keys) if not c.ok]


def factory_caches():
    """{name: {hits, misses, maxsize, currsize, evictions}} per cache.

    Every registered cache duck-types ``functools.lru_cache``'s
    ``cache_info()``.  Evictions are exact where the cache keeps a counter
    (the cross-call ``_LRU`` caches); for plain ``lru_cache`` factories
    they are the ``misses - currsize`` lower bound (every miss inserts, so
    anything not resident was evicted — exact as long as the cache was
    never cleared mid-run).
    """
    import importlib
    import sys

    out = {}
    for name, mod_name, attr in _FACTORIES:
        mod = sys.modules.get(mod_name)
        if mod is None:
            try:
                mod = importlib.import_module(mod_name)
            except Exception:
                continue
        fn = getattr(mod, attr, None)
        info = getattr(fn, "cache_info", None)
        if info is None:
            continue
        ci = info()
        out[name] = {
            "hits": ci.hits,
            "misses": ci.misses,
            "maxsize": ci.maxsize,
            "currsize": ci.currsize,
            "evictions": getattr(fn, "evictions",
                                 max(ci.misses - ci.currsize, 0)),
        }
    return out


class CompileWatch:
    """Count ACTUAL XLA backend compilations over a scope.

    ``jax.monitoring`` fires ``/jax/core/compile/backend_compile_duration``
    once per real backend compile and NOT on jit-cache hits, so this is
    the ground truth for "the warm path ran with zero compiles" — the
    cache-smoke CI gate (``bench_pipeline --only warm``).  Listeners
    cannot be deregistered individually on current jax, so one
    process-wide listener is installed on first use and watches are
    scoped by counting against a baseline.

        watch = CompileWatch()
        with watch:
            run_warm_path()
        assert watch.compiles == 0

    Degrades to counting nothing (and reporting ``supported=False``) when
    jax or its monitoring hooks are absent.
    """

    _installed = False
    _count = 0
    _EVENT = "/jax/core/compile/backend_compile_duration"

    @classmethod
    def _install(cls) -> bool:
        if cls._installed:
            return True
        try:
            import jax.monitoring as monitoring

            def _listener(name, secs, **kw):
                if name == cls._EVENT:
                    cls._count += 1

            monitoring.register_event_duration_secs_listener(_listener)
        except Exception:
            return False
        cls._installed = True
        return True

    def __init__(self):
        self.supported = self._install()
        self._base = 0
        self.compiles = 0

    def __enter__(self):
        self._base = type(self)._count
        return self

    def __exit__(self, *exc):
        self.compiles = type(self)._count - self._base
        return False
