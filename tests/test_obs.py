"""repro.obs: span tracing, trace export, metrics, compiled introspection,
and the EngineResult.timings contracts (ISSUE 7).

The timing-derivation tests assert BIT-FOR-BIT equality between
``EngineResult.timings`` and the span-derived totals on the numpy path:
the engine folds ``span.seconds`` floats directly, so the dict is a view
of the span tree, not a parallel measurement.
"""

import dataclasses
import json
import pickle
import time

import numpy as np
import pytest

from repro import obs
from repro.core import generate_chain_jobs, selfowned_policies
from repro.engine import EngineResult, ScenarioSpec, evaluate_grid
from repro.engine.api import evaluate_grid_chunks
from repro.obs import METRICS, span
from repro.obs.metrics import MetricsRegistry


def _setup(n=8, seed=0):
    jobs = generate_chain_jobs(n, 2, seed=seed)
    horizon = max(j.deadline for j in jobs) + 1.0
    return jobs, horizon


GRID = selfowned_policies()[:6]


# --------------------------------------------------------------------------
# Span tracer core
# --------------------------------------------------------------------------

def test_span_measures_without_tracer():
    assert obs.current_tracer() is None
    with span("work", tag="x") as sp:
        time.sleep(0.001)
    assert sp.seconds > 0.0
    assert sp.attrs == {"tag": "x"}
    assert obs.current_tracer() is None


def test_span_nesting_and_parents():
    with obs.tracing() as tr:
        with span("outer") as outer:
            with span("inner_a"):
                pass
            with span("inner_b"):
                with span("leaf"):
                    pass
    by_name = {r.name: r for r in tr.spans}
    assert by_name["inner_a"].parent == outer.id
    assert by_name["inner_b"].parent == outer.id
    assert by_name["leaf"].parent == by_name["inner_b"].id
    assert by_name["outer"].parent is None
    # children finish (and record) before their parent
    assert tr.spans[-1].name == "outer"
    kids = tr.children(outer.id)
    assert {r.name for r in kids} == {"inner_a", "inner_b"}
    assert [r.name for r in tr.roots()] == ["outer"]
    # parent duration covers its children
    assert by_name["outer"].seconds >= (
        by_name["inner_a"].seconds + by_name["inner_b"].seconds)


def test_span_set_attrs_and_totals():
    with obs.tracing() as tr:
        with span("phase") as sp:
            sp.set(backend="numpy", n=3)
        with span("phase"):
            pass
    assert tr.named("phase")[0].attrs == {"backend": "numpy", "n": 3}
    tot = tr.totals()
    assert tot["phase"] == (tr.spans[0].seconds + tr.spans[1].seconds)


def test_nested_tracers_restore():
    with obs.tracing() as outer_tr:
        with span("a"):
            pass
        with obs.tracing() as inner_tr:
            with span("b"):
                pass
        assert obs.current_tracer() is outer_tr
        with span("c"):
            pass
    assert [r.name for r in outer_tr.spans] == ["a", "c"]
    assert [r.name for r in inner_tr.spans] == ["b"]


def test_spans_not_recorded_when_disabled():
    with span("ghost"):
        pass
    with obs.tracing() as tr:
        pass
    assert len(tr) == 0


# --------------------------------------------------------------------------
# Trace export: Chrome/Perfetto JSON + JSONL
# --------------------------------------------------------------------------

def _traced_numpy_run(S=6, chunk=3):
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, S, seed=1)
    with obs.tracing() as tr:
        res = evaluate_grid(jobs, GRID, spec, backend="numpy",
                            scenario_chunk=chunk)
    return tr, res


def test_chrome_trace_schema(tmp_path):
    tr, _ = _traced_numpy_run()
    path = tmp_path / "trace.json"
    tr.save(path)
    doc = json.load(open(path))
    assert "traceEvents" in doc and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        # The Perfetto-required complete-event fields.
        assert ev["ph"] == "X"
        assert isinstance(ev["name"], str)
        for field in ("ts", "dur"):
            assert isinstance(ev[field], (int, float))
        for field in ("pid", "tid"):
            assert isinstance(ev[field], int)
        assert isinstance(ev["args"], dict)
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert {"evaluate_grid", "plan", "synth", "eval", "chunk"} <= names


def test_jsonl_export_line_parseable(tmp_path):
    tr, _ = _traced_numpy_run()
    path = tmp_path / "trace.jsonl"
    tr.save_jsonl(path)
    lines = open(path).read().splitlines()
    assert len(lines) == len(tr)
    for line in lines:
        rec = json.loads(line)
        assert {"id", "parent", "name", "ts", "dur", "pid", "tid",
                "attrs"} <= set(rec)


def test_attr_coercion_json_safe():
    with obs.tracing() as tr:
        with span("np_attrs", f=np.float64(1.5), i=np.int32(2),
                  arr=(np.int64(1), np.int64(2)), obj=object()):
            pass
    doc = tr.to_chrome()
    args = doc["traceEvents"][0]["args"]
    json.dumps(doc)  # round-trips
    assert args["f"] == 1.5 and args["i"] == 2
    assert args["arr"] == [1, 2]
    assert isinstance(args["obj"], str)


# --------------------------------------------------------------------------
# EngineResult.timings as a span-derived view (bit-for-bit, numpy path)
# --------------------------------------------------------------------------

def _lsum(values) -> float:
    """Left-to-right float sum, the order the engine and the tracer use
    (Python >= 3.12's ``sum`` compensates, so it can differ in the last
    ulp)."""
    total = 0.0
    for v in values:
        total += v
    return total


def test_timings_match_span_totals_bitforbit():
    tr, res = _traced_numpy_run(S=6, chunk=2)
    tot = tr.totals()
    assert res.timings["plan"] == tot["plan"]
    assert res.timings["pool"] == tot["pool"]
    assert res.timings["synth"] == tot["synth"]
    assert res.timings["eval"] == tot["eval"]
    # per-chunk split: each entry is exactly its span's seconds, and the
    # split sums exactly to the phase totals (same accumulation order).
    synth_spans = tr.named("synth")
    eval_spans = tr.named("eval")
    chunks = res.timings["chunks"]
    assert len(chunks) == len(synth_spans) == len(eval_spans) == 3
    for entry, ss, es in zip(chunks, synth_spans, eval_spans):
        assert entry["synth"] == ss.seconds
        assert entry["eval"] == es.seconds
    assert _lsum(c["synth"] for c in chunks) == res.timings["synth"]
    assert _lsum(c["eval"] for c in chunks) == res.timings["eval"]
    # every chunk span parents exactly one synth + one eval span
    for c in tr.named("chunk"):
        kids = tr.children(c.id)
        assert sorted(r.name for r in kids) == ["eval", "synth"]


def test_grid_chunks_spans_and_timings():
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 6, seed=3)
    with obs.tracing() as tr:
        chunks = list(evaluate_grid_chunks(jobs, GRID, spec,
                                           scenario_chunk=3,
                                           backend="numpy"))
    assert len(chunks) == 2
    synth_spans = tr.named("synth")
    for ch, ss in zip(chunks, synth_spans):
        assert ch.timings["synth"] == ss.seconds
    assert len(tr.named("chunk")) == 2


# --------------------------------------------------------------------------
# Disabled-mode overhead: span machinery must cost < 2% of a small grid
# --------------------------------------------------------------------------

def test_disabled_overhead_under_two_percent():
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 8, seed=2)
    args = (jobs, GRID, spec)
    kw = dict(backend="numpy", scenario_chunk=2)
    evaluate_grid(*args, **kw)  # warm caches
    t0 = time.perf_counter()
    evaluate_grid(*args, **kw)
    wall = time.perf_counter() - t0
    # How many spans does this run open? (count via a traced pass)
    with obs.tracing() as tr:
        evaluate_grid(*args, **kw)
    n_spans = len(tr)
    # Per-span disabled cost, measured directly.
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        with span("x", a=1, b=2):
            pass
    per_span = (time.perf_counter() - t0) / reps
    assert n_spans * per_span < 0.02 * wall, (
        f"{n_spans} spans x {per_span * 1e6:.2f}us = "
        f"{n_spans * per_span * 1e3:.3f}ms vs 2% of {wall * 1e3:.1f}ms")


# --------------------------------------------------------------------------
# timings["synth"] contract under overlap (satellite): residual wait <=
# full synthesis on the same workload, and chunk splits sum to totals.
# --------------------------------------------------------------------------

def test_overlap_synth_contract():
    pytest.importorskip("jax")
    jobs, horizon = _setup(n=16)
    spec = ScenarioSpec("fresh", horizon, 32, seed=5)
    kw = dict(backend="jax", scenario_chunk=8)
    base = evaluate_grid(jobs, GRID, spec, overlap=False, **kw)
    ov = evaluate_grid(jobs, GRID, spec, overlap=True, **kw)
    assert base.timings["overlap"] is False
    assert ov.timings["overlap"] is True
    # Residual wait after async dispatch must not exceed the full blocking
    # synthesis of the identical workload (1ms absolute slack absorbs
    # timer jitter when both sides are near zero).
    assert ov.timings["synth"] <= base.timings["synth"] + 1e-3, (
        f"overlap synth {ov.timings['synth']:.4f}s > non-overlap "
        f"{base.timings['synth']:.4f}s")
    for res in (base, ov):
        chunks = res.timings["chunks"]
        assert len(chunks) == 4
        assert _lsum(c["synth"] for c in chunks) == res.timings["synth"]
        assert _lsum(c["eval"] for c in chunks) == res.timings["eval"]
    np.testing.assert_allclose(ov.unit_cost, base.unit_cost, rtol=0, atol=0)


# --------------------------------------------------------------------------
# EngineResult.timings defaults + round-trips (satellite)
# --------------------------------------------------------------------------

def _min_result():
    z = np.zeros((1, 2, 3))
    return EngineResult(unit_cost=z, spot_cost=z, ondemand_cost=z,
                        spot_work=z, ondemand_work=z,
                        workload=np.ones(2), selfowned_work=z[0],
                        selfowned_reserved=z[0])


def test_timings_default_empty_dict():
    res = _min_result()
    assert res.timings == {} and isinstance(res.timings, dict)
    assert res.obs is None
    # instances do not share the default dict
    res.timings["plan"] = 1.0
    assert _min_result().timings == {}


def test_engine_result_replace_and_pickle_roundtrip():
    res = _min_result()
    res.timings.update({"plan": 0.5, "chunks": []})
    rep = dataclasses.replace(res, backend="jax")
    assert rep.timings == {"plan": 0.5, "chunks": []}
    assert rep.backend == "jax"
    back = pickle.loads(pickle.dumps(rep))
    assert back.timings == rep.timings
    assert back.obs is None
    jobs, horizon = _setup()
    real = evaluate_grid(jobs, GRID,
                         ScenarioSpec("fresh", horizon, 2, seed=0),
                         backend="numpy")
    back = pickle.loads(pickle.dumps(real))
    assert back.timings == real.timings
    np.testing.assert_array_equal(back.unit_cost, real.unit_cost)


# --------------------------------------------------------------------------
# Metrics registry
# --------------------------------------------------------------------------

def test_metrics_disabled_records_nothing():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.gauge("g").set(2.0)
    reg.histogram("h").observe(1.0)
    assert reg.snapshot() == {}


def test_metrics_counter_gauge_histogram_labels():
    reg = MetricsRegistry()
    with reg.collecting():
        reg.counter("c").inc(stage="a")
        reg.counter("c").inc(2.0, stage="a")
        reg.counter("c").inc(stage="b")
        reg.gauge("g").set(1.5, backend="jax")
        for v in (0.01, 0.02, 5.0):
            reg.histogram("h").observe(v, phase="eval")
    assert not reg.enabled
    snap = reg.snapshot()
    c = {tuple(s["labels"].items()): s["value"] for s in snap["c"]["series"]}
    assert c[(("stage", "a"),)] == 3.0 and c[(("stage", "b"),)] == 1.0
    assert snap["g"]["series"][0]["value"] == 1.5
    h = snap["h"]["series"][0]
    assert h["count"] == 3 and h["min"] == 0.01 and h["max"] == 5.0
    assert h["sum"] == pytest.approx(5.03)
    assert sum(b["count"] for b in h["buckets"]) == 3
    json.dumps(snap)


def test_metrics_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_engine_metrics_snapshot_on_result():
    from repro.engine.plan import build_grid_plan

    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 4, seed=1)
    n_groups = len(build_grid_plan(jobs, GRID).groups)
    with METRICS.collecting(reset=True):
        for _ in range(2):
            res = evaluate_grid(jobs, GRID, spec, backend="numpy",
                                scenario_chunk=2)
    assert res.obs is not None
    m = res.obs["metrics"]
    series = m["engine.plan_cache"]["series"]
    by_event = {s["labels"]["event"]: s["value"] for s in series}
    # one lookup per eval group per call; the second call hits every group
    assert by_event.get("hit", 0.0) + by_event.get("miss", 0.0) \
        == 2 * n_groups
    assert by_event["hit"] >= n_groups
    assert "engine.chunk_seconds" not in m
    assert "engine.scenarios_per_sec" not in m
    # no active collection -> no snapshot
    res2 = evaluate_grid(jobs, GRID, spec, backend="numpy")
    assert res2.obs is None


def test_adaptive_escalation_counter():
    from repro.learn import replay_stream

    jobs, horizon = _setup()
    spec = ScenarioSpec("adaptive", horizon, 12, seed=7, n_periods=2,
                        n_phases=2)
    with METRICS.collecting(reset=True):
        out = replay_stream(jobs, GRID, spec, scenario_chunk=4,
                            backend="numpy", engine_backend="numpy")
    m = out.obs["metrics"]
    stages = {s["labels"]["stage"]: s["value"]
              for s in m["scenarios.adaptive_chunks"]["series"]}
    assert sum(stages.values()) == 3          # one increment per chunk
    assert "periods" in stages
    if "scenarios.adaptive_escalations" in m:
        esc = m["scenarios.adaptive_escalations"]["series"]
        assert all(s["value"] >= 1 for s in esc)
    ent = m["learn.weight_entropy"]["series"]
    assert ent and all(s["count"] == 3 for s in ent)   # one obs per chunk
    assert "learn.top_weight" in m


@pytest.mark.parametrize("kernel, kw", [
    ("chain", {}),
    ("task", dict(windows="even", selfowned="naive", early_start=False,
                  pool="shared")),
])
def test_pallas_lookup_tiles_gauge(kernel, kw):
    """Each pallas launch records the lane tiles one slot lookup reads —
    the tile heads, plus one tile — only while metrics are collected."""
    pytest.importorskip("jax")
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 2, seed=6)
    METRICS.reset()
    evaluate_grid(jobs, GRID, spec, backend="pallas", **kw)
    assert "engine.eval.lookup_tiles" not in METRICS.snapshot()
    with METRICS.collecting(reset=True):
        res = evaluate_grid(jobs, GRID, spec, backend="pallas", **kw)
    tiles = -(-(spec.n_slots + 1) // 128)
    heads = -(-tiles // 128)
    series = res.obs["metrics"]["engine.eval.lookup_tiles"]["series"]
    assert series == [{"labels": {"kernel": kernel}, "value": heads + 1}]


# --------------------------------------------------------------------------
# Compiled-program introspection
# --------------------------------------------------------------------------

def test_collective_counts_regex():
    txt = """
      x = all-reduce(a), y = all-reduce-start(b), z = all-reduce-done(c)
      g = all-gather(d), p = collective-permute(e)
    """
    counts = obs.compiled.collective_counts(txt)
    assert counts["all-reduce"] == 2          # -start counts, -done doesn't
    assert counts["all-gather"] == 1
    assert counts["collective-permute"] == 1
    assert counts["total"] == 4


def test_hlo_metrics_and_capture_counters():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((8, 8), jnp.float32)
    m = obs.compiled.hlo_metrics(fn, x, x)
    assert m["flops"] > 0
    assert m["collective_counts"]["total"] == 0
    assert obs.compiled.current_registry() is None
    with obs.capture() as reg:
        obs.record_jit("k", fn, x, x)
        obs.record_jit("k", fn, x, x)
    assert reg["k"]["captures"] == 2
    assert reg["k"]["flops"] == m["flops"]
    snap = reg.snapshot()
    assert "k" in snap["programs"] and "factory_caches" in snap
    assert "k" in reg.table()
    json.dumps(snap)
    assert obs.compiled.current_registry() is None


def test_record_jit_noop_without_capture():
    # must not lower/compile anything — works with a non-jit callable
    obs.record_jit("nope", None)


def test_capture_never_raises_on_bad_program():
    with obs.capture() as reg:
        obs.record_jit("bad", object())
    assert "error" in reg["bad"]


# --------------------------------------------------------------------------
# Acceptance: streamed run under full observation — span tree covers
# plan/synth/eval/fold per chunk, compiled metrics carry the Section 9
# collective counts (one psum in the fold, zero in the eval hot loop).
# --------------------------------------------------------------------------

def test_streamed_observation_end_to_end(tmp_path):
    pytest.importorskip("jax")
    from repro.engine import GridMesh
    from repro.learn import replay_stream

    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 4, seed=9)
    mesh = GridMesh.create(1)
    with obs.observe(programs=True) as session:
        out = replay_stream(jobs, GRID[:4], spec, scenario_chunk=2,
                            backend="jax", engine_backend="jax", mesh=mesh)
    tr, reg = session.tracer, session.compiled
    names = {r.name for r in tr.spans}
    assert {"plan", "synth", "eval", "fold", "chunk",
            "replay_stream"} <= names
    assert len(tr.named("fold")) == 2 and len(tr.named("chunk")) == 2
    # fold spans are children of the replay_stream root
    root = tr.named("replay_stream")[0]
    assert all(r.parent == root.id for r in tr.named("fold"))
    # Perfetto-loadable trace on disk
    doc = json.load(open(tr.save(tmp_path / "stream.json")))
    assert {ev["name"] for ev in doc["traceEvents"]} == names
    # Section 9 placement contract as standing compiled metrics
    fold = reg["learn.fold:sharded"]["collective_counts"]
    assert fold["all-reduce"] == 1 and fold["total"] == 1
    chain = reg["engine.eval.chain:sharded"]["collective_counts"]
    assert chain["total"] == 0
    synth = reg["scenarios.synth:fresh:sharded"]["collective_counts"]
    assert synth["total"] == 0
    # the snapshot rode along on the stream result
    assert out.obs is not None and "compiled" in out.obs
    assert out.obs["compiled"]["programs"]["learn.fold:sharded"][
        "collective_counts"]["all-reduce"] == 1
    caches = out.obs["compiled"]["factory_caches"]
    assert caches["learn.fold"]["misses"] >= 1


# --------------------------------------------------------------------------
# Spans on the profiler's clock: mirrored as jax.profiler annotations in
# the profile's host plane while a tracer is installed, absent otherwise.
# --------------------------------------------------------------------------

def _profiled(fn, log_dir):
    """Run ``fn`` under the JAX profiler; the host plane's events as
    {name: [(start_ns, duration_ns), ...]}."""
    import glob
    import os

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns))
    return events


def _small_jax_grid():
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 2, seed=4)
    run = lambda: evaluate_grid(jobs, GRID, spec, backend="jax")
    run()  # compile outside the profile
    return run


def test_spans_mirrored_in_profiler_host_plane(tmp_path):
    pytest.importorskip("jax")
    run = _small_jax_grid()
    tracers = []

    def traced():
        with obs.tracing() as tr:
            run()
        tracers.append(tr)

    events = _profiled(traced, tmp_path)
    (tr,) = tracers
    assert {"prepare_stream", "plan.fingerprint", "eval.wait"} <= {
        r.name for r in tr.spans}
    for name in {r.name for r in tr.spans}:
        recs = sorted(tr.named(name), key=lambda r: r.ts)
        evs = sorted(events.get(name, []))
        assert len(evs) == len(recs), name
        for rec, (_, dur_ns) in zip(recs, evs):
            assert abs(dur_ns * 1e-9 - rec.seconds) <= max(
                0.05 * rec.seconds, 50e-6), (name, dur_ns, rec.seconds)


def test_no_profiler_events_without_tracer(tmp_path):
    pytest.importorskip("jax")
    run = _small_jax_grid()
    with obs.tracing() as tr:
        run()
    names = {r.name for r in tr.spans}

    def untraced_then_marker():
        run()
        with obs.tracing():
            with span("profiler_was_on"):
                pass

    events = _profiled(untraced_then_marker, tmp_path)
    assert "profiler_was_on" in events
    assert not names & set(events)


# --------------------------------------------------------------------------
# The span tree: children of prepare_stream and eval, the TOLA round, and
# one request id per call.
# --------------------------------------------------------------------------

def _kids(tr, rec):
    return sorted(r.name for r in tr.children(rec.id))


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_evaluate_grid_span_tree(backend):
    pytest.importorskip("jax")
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 2, seed=6)
    with obs.tracing() as tr:
        evaluate_grid(jobs, GRID, spec, backend=backend)
    (root,) = tr.roots()
    assert root.name == "evaluate_grid"
    (prep,) = tr.named("prepare_stream")
    assert prep.parent == root.id
    assert {"plan.arrays", "plan.fingerprint", "plan.lookup"} <= set(
        _kids(tr, prep))
    (lookup,) = tr.named("plan.lookup")
    assert lookup.attrs["hits"] + lookup.attrs["misses"] >= 1
    (ev,) = tr.named("eval")
    J, P = len(jobs), len(GRID)
    assert ev.attrs["cells"] == 2 * J * P
    assert ev.attrs["rows"] % J == 0 and ev.attrs["rows"] >= J
    phases = ["eval.fetch", "eval.scatter", "eval.stack", "eval.wait"]
    if backend == "pallas":
        # one launch for every bid: the phases sit right under eval
        assert set(phases) <= set(_kids(tr, ev))
        for name in phases:
            assert len(tr.named(name)) == 1
    else:
        # one program per bid: the phases sit under each eval.bid
        bids = tr.named("eval.bid")
        assert bids and all(b.parent == ev.id for b in bids)
        for b in bids:
            assert set(phases) <= set(_kids(tr, b))
        for name in phases:
            assert len(tr.named(name)) == len(bids)


def _tola_inputs():
    from repro.core import SpotMarket

    jobs, horizon = _setup(n=10)
    markets = [SpotMarket(horizon, seed=s) for s in (0, 1)]
    return jobs, markets


def test_tola_span_tree():
    from repro.core import run_tola_scenarios

    jobs, markets = _tola_inputs()
    with obs.tracing() as tr:
        run_tola_scenarios(jobs, GRID, markets, r_total=4, pool_iters=1,
                           backend="numpy")
    (root,) = tr.roots()
    assert root.name == "tola"
    rounds = tr.named("tola.round")
    assert sorted((r.attrs["round"], r.attrs["scenario"]) for r in rounds) \
        == [(0, 0), (0, 1), (1, 0), (1, 1)]
    for r in rounds:
        assert r.parent == root.id
        assert _kids(tr, r) == ["replay", "tola.availability", "tola.plans",
                                "tola.pool", "tola.realize"]
    # Each round's engine call sits in its own span under the root.
    (score,) = tr.named("tola.score")
    (rescore,) = tr.named("tola.rescore")
    assert score.parent == rescore.parent == root.id
    assert rescore.attrs["round"] == 1
    assert [r.parent for r in tr.named("evaluate_grid")] == [score.id,
                                                             rescore.id]


def test_single_market_tola_has_one_root():
    from repro.core import run_tola_scenarios

    jobs, markets = _tola_inputs()
    with obs.tracing() as tr:
        run_tola_scenarios(jobs, GRID, markets[:1], r_total=4, pool_iters=1,
                           backend="numpy")
    (root,) = tr.roots()
    assert root.name == "tola"
    assert len(tr.named("tola.round")) == 2
    assert {r.root for r in tr.spans} == {root.id}


def test_every_span_carries_its_request_id(tmp_path):
    from repro.core import run_tola_scenarios

    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 2, seed=7)
    tjobs, markets = _tola_inputs()
    with obs.tracing() as tr:
        evaluate_grid(jobs, GRID, spec, backend="numpy")
        run_tola_scenarios(tjobs, GRID, markets, r_total=4, pool_iters=1,
                           backend="numpy")
    roots = tr.roots()
    assert [r.name for r in roots] == ["evaluate_grid", "tola"]
    by_id = {r.id: r for r in tr.spans}
    for r in tr.spans:
        # the root's own id, reached by walking up the parents
        top = r
        while top.parent is not None:
            top = by_id[top.parent]
        assert r.root == top.id
    assert {r.root for r in tr.spans} == {roots[0].id, roots[1].id}
    # both exporters carry it
    chrome = tr.to_chrome()["traceEvents"]
    assert [ev["args"]["root_id"] for ev in chrome] == [
        r.root for r in tr.spans]
    lines = tr.to_jsonl().splitlines()
    assert [json.loads(line)["root"] for line in lines] == [
        r.root for r in tr.spans]


def test_root_restarts_in_a_nested_tracer():
    with obs.tracing() as outer:
        with span("outer_call"):
            with obs.tracing() as inner:
                with span("inner_call"):
                    with span("leaf"):
                        pass
    (top,) = inner.named("inner_call")
    assert top.parent is None and top.root == top.id
    assert inner.named("leaf")[0].root == top.id
    assert outer.spans[0].root == outer.spans[0].id


def test_obs_spans_work_without_jax():
    """``repro.obs`` neither imports jax nor needs it: with jax made
    unimportable, a traced span records and nothing pulls jax in."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"
        "from repro import obs\n"
        "with obs.tracing() as tr:\n"
        "    with obs.span('a'):\n"
        "        with obs.span('b'):\n"
        "            pass\n"
        "assert [r.name for r in tr.spans] == ['b', 'a']\n"
        "assert 'jax.profiler' not in sys.modules\n"
    )
    import os

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    subprocess.run([sys.executable, "-c", code], check=True, env=env)
