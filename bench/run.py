"""Benchmark of the policy engine on one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: the
deployment) and a traffic mix (``bench/traffic/<mix>.json``: how units of
work are offered); ``bench/traffic.py`` turns the two into units of work on
the program, by the unit kind the mix names (``bench/units/<kind>.py``). A
run:

1. checks that JAX's first device is a TPU of a kind listed in
   ``bench/peaks.json`` and that the cell's chips are there (else it exits
   with code 3 and prints no result); after set-up, that the engine
   resolved a device backend (pallas or jax), and not the host oracle
   (else the same);
2. sets up: the program's persistent compile cache (``<checkout>/.jax_cache``
   unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), the job stream, and
   the mix's warm-up units, which load or compile every program the window
   runs — all of it counted in ``setup_s``;
3. runs units back to back (a closed loop) until ``--seconds`` have passed,
   counting compiles inside the window;
4. with ``--trace 1``, records the window with the JAX profiler and the
   program's ``repro.obs`` spans and reduces them with the per-layer readers
   in ``bench/metrics/<metric>.py``;
5. re-computes a sample of the window's answers with the plain reference
   (``bench/reference.py``) and compares them, each number against its limit
   from the configuration;
6. prints one JSON line last on standard output, with the resolved engine
   backend under ``backend``.

Standard error carries the resolved engine backend, the window's compile
count, the units of work done, how late the loop ran, and, as its last
lines, each compared number with its limit.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, os.path.join(ROOT, "src"))
                if p not in sys.path]

import devtrace  # noqa: E402

# libtpu logs to /tmp/tpu_logs unless told otherwise: keep them under TMPDIR.
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


DEVICE_BACKENDS = ("pallas", "jax")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for, or
    the engine did not resolve a device backend."""


def _json(path):
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration, mix
    and the metrics it reports."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["cfg"] = _json(os.path.join(root, conf["file"]))
    cell["mix"] = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def reports(m):
        return name in m.get("workloads", [name])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if reports(m)]
    e2e = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if reports(m) and m["moves"] in e2e]
    return cell


def _reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_chip and (d0.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} device(s) of platform {d0.platform!r}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "device": d0}


def peaks_of(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if kind not in table["devices"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


class Run:
    """What a traced window leaves for the per-layer readers: the device
    trace, the program's spans on its clock, the units completed, the
    cell's shapes and configuration, the chip's peaks."""

    def __init__(self, trace, spans, units, shapes, cfg, peaks,
                 span_totals=None):
        self.trace, self.spans, self.units = trace, spans, units
        self.shapes, self.cfg, self.peaks = shapes, cfg, peaks
        self.span_totals = span_totals or {}

    def span_s(self, names) -> float:
        """Host seconds of the program's spans of these names, summed."""
        return sum(self.span_totals.get(n, 0.0) for n in names)


def _spans_on_trace_clock(tracer, unit_starts, trace):
    """The program's ``repro.obs`` spans as (name, start, end) on the
    trace's clock, aligned through the unit annotations, and the unit
    annotations themselves: host time in a unit outside every program span
    is named by them."""
    import numpy as np

    if not trace.units or not unit_starts:
        return []
    n = min(len(trace.units), len(unit_starts))
    off = float(np.median([trace.units[i][0] - unit_starts[i]
                           for i in range(n)]))
    t0 = tracer._t0 * 1e-9 + off
    return [(devtrace.UNIT, s, e) for s, e in trace.units] + [
        (r.name, t0 + r.ts, t0 + r.ts + r.seconds) for r in tracer.spans]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t0: float = _T0,
             trace_dir: str | None = None, log=print):
    """One run of one cell; returns (result dict, checks dict)."""
    import jax
    import numpy as np

    dev = device_info(cell["chips"], require_chip)
    peaks = peaks_of(dev["kind"]) if require_chip else None

    from repro.engine import setup_persistent_cache
    from repro.obs import trace as obs_trace
    from repro.obs.compiled import CompileWatch

    import traffic

    setup_persistent_cache()
    work_gen = traffic.make(cell["cfg"], cell["mix"], seed)
    warm = CompileWatch()
    with warm:
        work_gen.warm()
    setup_s = time.perf_counter() - t0
    log(f"[setup] setup_s={setup_s} backend={work_gen.backend} "
        f"warmup_units={cell['mix']['warmup_units']} "
        f"warmup_compiles={warm.compiles} shapes={work_gen.shapes}")
    if require_chip and work_gen.backend not in DEVICE_BACKENDS:
        raise NoChip(f"the engine resolved backend {work_gen.backend!r}, not "
                     f"a device backend {DEVICE_BACKENDS}")

    tracer = obs_trace.Tracer() if trace else None
    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    watch = CompileWatch()
    attempted = failed = work = 0
    unit_starts: list[float] = []
    late = []
    with watch:
        ctx = obs_trace.trace(tracer) if trace else contextlib.nullcontext()
        with ctx, jax.profiler.TraceAnnotation(devtrace.WINDOW):
            start = time.perf_counter()
            prev_end = start
            while True:
                t = time.perf_counter()
                late.append(t - prev_end)
                unit_starts.append(t)
                attempted += 1
                try:
                    with jax.profiler.TraceAnnotation(devtrace.UNIT):
                        work += work_gen.unit(attempted - 1)
                except Exception as e:  # a unit that raises is a failed unit
                    failed += 1
                    log(f"[unit] {attempted - 1} failed: {e!r}")
                prev_end = time.perf_counter()
                if prev_end - start >= seconds:
                    break
            window_s = prev_end - start
    if trace:
        jax.profiler.stop_trace()
    log(f"[window] backend={work_gen.backend} window_s={window_s} "
        f"units={attempted} failed={failed} work={work} "
        f"compiles={watch.compiles} late_max_ms={1e3 * max(late)} "
        f"late_total_ms={1e3 * sum(late)}")
    if require_chip and work_gen.backend not in DEVICE_BACKENDS:
        raise NoChip(f"the window ran backend {work_gen.backend!r}")

    stats = dev["device"].memory_stats() or {}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    metrics, breakdown = {}, None
    if trace:
        tr = devtrace.load(tmp)
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        spans = _spans_on_trace_clock(tracer, unit_starts, tr)
        totals: dict = {}
        for r in tracer.spans:
            totals[r.name] = totals.get(r.name, 0.0) + r.seconds
        run = Run(tr, spans, attempted - failed, work_gen.shapes, cell["cfg"],
                  peaks, totals)
        for m in cell["per_layer"]:
            v = _reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(),
                     "idle_gaps": tr.idle_gaps(spans)}
    else:
        e2e = dict(work_gen.rates(work, attempted - failed, window_s),
                   setup_s=setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    t = time.perf_counter()
    got = work_gen.check(seed)
    limits = cell["cfg"]["limits"]
    checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    correct = failed == 0 and attempted > 0 and all(
        bool(np.isfinite(c["value"])) and c["value"] <= c["limit"]
        for c in checks.values())
    log(f"[check] reference_s={time.perf_counter() - t} {work_gen.checked}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device,
              "backend": work_gen.backend}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, checks


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--trace-dir", default=None,
                   help="keep the profiler's trace here (default: a "
                        "temporary directory, removed after reading)")
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    err = lambda *a: print(*a, file=sys.stderr, flush=True)
    try:
        result, checks = run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), trace_dir=args.trace_dir,
                                  log=err)
    except NoChip as e:
        err(f"bench: {e}")
        return 3
    for k, c in checks.items():
        err(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
