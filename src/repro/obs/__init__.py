"""repro.obs — zero-dependency observability for the engine/learn stack.

Three cooperating layers, all stdlib-only so the engine can import them
unconditionally (DESIGN.md Section 10):

* :mod:`repro.obs.trace` — a context-var span tracer.  ``span("eval",
  chunk=k)`` always measures wall seconds (``sp.seconds`` after exit, the
  single timing source `EngineResult.timings` is derived from); full span
  records (nesting, attributes, timestamps) are captured only while a
  ``trace()`` context is active, and export to Chrome-trace/Perfetto JSON
  or a flat JSONL event log.  While a tracer is installed and jax is
  loaded, each span is mirrored as a ``jax.profiler.TraceAnnotation``, so
  a JAX profile shows the spans on the device trace's clock.
* :mod:`repro.obs.compiled` — compile-time introspection.  Engine call
  sites announce every cached jit program via ``record_jit(key, fn,
  *args)``; inside a ``capture()`` context the program is lowered,
  compiled, and analyzed (flops / bytes / collective op counts via
  ``launch.hlo_analysis``), turning the one-off HLO assertions from the
  shard tests into a standing metric.  Outside a capture context the hook
  is a single context-var read.
* :mod:`repro.obs.metrics` — a counter/gauge/histogram registry with
  labeled series (plan-cache hits, adaptive-adversary escalations,
  learner weight entropy), snapshotted into
  ``EngineResult.obs`` / ``StreamLearnResult.obs``.

``observe()`` composes all three for the common "turn everything on"
case; ``maybe_snapshot()`` is what the engine attaches to results.
"""
from __future__ import annotations

import contextlib
from types import SimpleNamespace

from . import compiled, metrics, trace
from .compiled import CompiledRegistry, capture, record_jit
from .metrics import METRICS, MetricsRegistry
from .trace import Span, Tracer, current_tracer, span, trace as tracing, tracing_enabled

__all__ = [
    "CompiledRegistry",
    "METRICS",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "capture",
    "compiled",
    "current_tracer",
    "maybe_snapshot",
    "metrics",
    "observe",
    "record_jit",
    "span",
    "trace",
    "tracing",
    "tracing_enabled",
]


@contextlib.contextmanager
def observe(*, spans=True, counters=True, programs=False, tracer=None):
    """Enable span tracing, metrics collection, and (optionally) compiled-
    program capture for the dynamic extent of the block.

    Yields a namespace with ``tracer`` (:class:`Tracer` or None),
    ``metrics`` (the global :data:`METRICS` registry), and ``compiled``
    (:class:`CompiledRegistry` or None).
    """
    with contextlib.ExitStack() as stack:
        tr = stack.enter_context(trace.trace(tracer)) if spans else None
        if counters:
            stack.enter_context(METRICS.collecting())
        reg = stack.enter_context(compiled.capture()) if programs else None
        yield SimpleNamespace(tracer=tr, metrics=METRICS, compiled=reg)


def maybe_snapshot():
    """Snapshot of whatever observability collection is currently active.

    Returns ``{"metrics": ..., "compiled": ...}`` with only the active
    layers present, or ``None`` when nothing is collecting — this is what
    ``evaluate_grid`` / ``replay_stream`` attach to their results.
    """
    out = {}
    if METRICS.enabled:
        out["metrics"] = METRICS.snapshot()
    reg = compiled.current_registry()
    if reg is not None:
        out["compiled"] = reg.snapshot()
    return out or None
