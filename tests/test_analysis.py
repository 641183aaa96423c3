"""Tests for repro.analysis: Layer-1 source rules, suppression machinery,
the Layer-2 compiled-program verifier, and the CLI contract.

Layer-1 fixtures are inline source blobs analyzed under *virtual* paths
(``analyze_source(src, "src/repro/core/simulate.py")``), so each rule is
exercised against the module classification it guards without touching
real files.  The deliberate-break tests at the bottom are the acceptance
demo: a smuggled ``psum`` or an inline epsilon fails the pass with the
rule code / program key and location — no device program ever executes.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.analysis import (Baseline, analyze_source, load_baseline,
                            run_source_analysis)
from repro.analysis.engine import BaselineEntry
from repro.analysis.report import render_json, summary_table

REPO = pathlib.Path(__file__).resolve().parents[1]

LIB = "src/repro/engine/foo.py"          # generic library module
DEVICE = "src/repro/kernels/foo.py"      # device-path module
GUARDED = "src/repro/core/simulate.py"   # knife-edge module


def _codes(src, path):
    return [f.code for f in analyze_source(src, path)]


# --------------------------------------------------------------------------
# Per-rule good / bad fixtures
# --------------------------------------------------------------------------

def test_rpr001_timing_fires_outside_trace():
    bad = "import time\nt0 = time.perf_counter()\n"
    assert _codes(bad, LIB) == ["RPR001"]
    assert _codes("from time import perf_counter\n", LIB) == ["RPR001"]


def test_rpr001_silent_in_trace_module_and_on_spans():
    src = "import time\nt0 = time.perf_counter_ns()\n"
    assert _codes(src, "src/repro/obs/trace.py") == []
    good = ("from repro.obs import span\n"
            "with span('phase') as sp:\n    pass\n")
    assert _codes(good, LIB) == []


def test_rpr002_unbounded_cache_fires():
    bad = ("import functools\n"
           "@functools.lru_cache(maxsize=None)\n"
           "def f():\n    return 1\n")
    assert _codes(bad, LIB) == ["RPR002"]
    bare = ("import functools\n"
            "@functools.lru_cache\ndef f():\n    return 1\n"
            "@functools.cache\ndef g():\n    return 2\n")
    assert _codes(bare, LIB) == ["RPR002", "RPR002"]


def test_rpr002_bounded_cache_silent():
    good = ("import functools\n"
            "@functools.lru_cache(maxsize=64)\n"
            "def f():\n    return 1\n")
    assert _codes(good, LIB) == []


def test_rpr003_float64_on_device_path_fires():
    assert _codes("import jax.numpy as jnp\nD = jnp.float64\n",
                  DEVICE) == ["RPR003"]
    jit_leak = ("import jax\n"
                "def step(x):\n    return x.astype('float64')\n"
                "fn = jax.jit(step)\n")
    assert _codes(jit_leak, DEVICE) == ["RPR003"]
    assert _codes("import jax\njax.config.update('jax_enable_x64', True)\n",
                  DEVICE) == ["RPR003"]


def test_rpr003_host_numpy_f64_oracle_allowed():
    # np.float64 outside any jit-reachable function is the documented
    # host-side oracle boundary — not a device-path leak.
    good = ("import numpy as np\n"
            "def oracle(x):\n    return np.asarray(x, dtype=np.float64)\n")
    assert _codes(good, DEVICE) == []
    # ...and float64 off the device path is out of scope entirely.
    assert _codes("import jax.numpy as jnp\nD = jnp.float64\n",
                  "src/repro/core/cost.py") == []


def test_rpr004_inline_epsilon_fires_with_location():
    src = "def clip(x):\n    if x > 1e-9:\n        return 0.0\n    return x\n"
    findings = analyze_source(src, GUARDED)
    assert [(f.code, f.location) for f in findings] == [
        ("RPR004", f"{GUARDED}:2")]


def test_rpr004_named_guard_silences():
    good = ("FLEX_REL = 1e-6\n"
            "def clip(x, y):\n"
            "    if x > FLEX_REL * 1e-5:\n        return 0.0\n    return x\n")
    assert _codes(good, GUARDED) == []
    # large-magnitude literals are not knife-edge tolerances
    assert _codes("def f(x):\n    return x > 0.5\n", GUARDED) == []
    # same comparison outside the guarded modules is out of scope
    assert _codes("def f(x):\n    return x > 1e-9\n", LIB) == []


def test_rpr005_host_sync_in_jit_reachable_fires():
    bad = ("import jax\n"
           "def _inner(x):\n    return float(x[0])\n"
           "def step(x):\n    return _inner(x) + 1.0\n"
           "fn = jax.jit(step)\n")
    assert _codes(bad, LIB) == ["RPR005"]
    item = ("import jax\n"
            "@jax.jit\ndef step(x):\n    return x.sum().item()\n")
    assert _codes(item, LIB) == ["RPR005"]


def test_rpr005_host_sync_outside_jit_graph_silent():
    good = ("import jax\n"
            "def step(x):\n    return x + 1.0\n"
            "fn = jax.jit(step)\n"
            "def report(x):\n    return float(x[0])\n")
    assert _codes(good, LIB) == []


def test_rpr006_donation_outside_whitelist_fires():
    src = "import jax\nfn = jax.jit(f, donate_argnums=(0,))\n"
    assert _codes(src, LIB) == ["RPR006"]
    # learn/replay.py is the §11 whitelist: same source, no finding.
    assert _codes(src, "src/repro/learn/replay.py") == []


def test_rpr007_callbacks_on_device_path_fire():
    assert _codes("import jax\ny = jax.pure_callback(f, s, x)\n",
                  DEVICE) == ["RPR007"]
    assert _codes("import jax\njax.debug.print('x={}', x)\n",
                  DEVICE) == ["RPR007"]
    assert _codes("from jax.experimental import io_callback\n",
                  DEVICE) == ["RPR007"]
    # off the device path the same source is out of scope
    assert _codes("import jax\ny = jax.pure_callback(f, s, x)\n",
                  "src/repro/core/foo.py") == []


def test_rpr000_syntax_error():
    findings = analyze_source("def broken(:\n", LIB)
    assert [f.code for f in findings] == ["RPR000"]


# --------------------------------------------------------------------------
# Suppression: inline noqa + content-keyed baseline
# --------------------------------------------------------------------------

def test_noqa_suppresses_matching_code():
    src = "def f(x):\n    return x > 1e-9  # repro: noqa RPR004\n"
    assert _codes(src, GUARDED) == []
    bare = "def f(x):\n    return x > 1e-9  # repro: noqa\n"
    assert _codes(bare, GUARDED) == []


def test_noqa_other_code_does_not_suppress():
    src = "def f(x):\n    return x > 1e-9  # repro: noqa RPR001\n"
    assert _codes(src, GUARDED) == ["RPR004"]


def test_baseline_roundtrip_is_content_keyed(tmp_path):
    mod = tmp_path / "src" / "repro" / "core"
    mod.mkdir(parents=True)
    target = mod / "simulate.py"
    target.write_text("def g(x):\n    return x > 1e-9\n")

    active, baselined = run_source_analysis(["src"], tmp_path, Baseline())
    assert [f.code for f in active] == ["RPR004"] and baselined == []

    bl_path = tmp_path / "analysis-baseline.json"
    bl_path.write_text(json.dumps({"version": 1, "entries": [{
        "rule": "RPR004", "path": "src/repro/core/simulate.py",
        "line_text": "return x > 1e-9", "justification": "fixture"}]}))
    active, baselined = run_source_analysis(
        ["src"], tmp_path, load_baseline(bl_path))
    assert active == [] and [f.code for f in baselined] == ["RPR004"]

    # shifting the finding to a different line number must not invalidate
    # the entry — the baseline keys on (rule, path, stripped line text).
    target.write_text("# padding\n\n\ndef g(x):\n    return x > 1e-9\n")
    active, baselined = run_source_analysis(
        ["src"], tmp_path, load_baseline(bl_path))
    assert active == [] and len(baselined) == 1
    assert baselined[0].line == 5


def test_missing_baseline_is_empty():
    assert len(load_baseline("/no/such/baseline.json")) == 0
    assert len(load_baseline(None)) == 0


def test_one_baseline_entry_covers_identical_lines(tmp_path):
    mod = tmp_path / "src" / "repro" / "core"
    mod.mkdir(parents=True)
    (mod / "simulate.py").write_text(
        "def g(x):\n    return x > 1e-9\ndef h(x):\n    return x > 1e-9\n")
    bl = Baseline([BaselineEntry("RPR004", "src/repro/core/simulate.py",
                                 "return x > 1e-9", "fixture")])
    active, baselined = run_source_analysis(["src"], tmp_path, bl)
    assert active == [] and len(baselined) == 2


# --------------------------------------------------------------------------
# Report output: JSON stability + summary table
# --------------------------------------------------------------------------

def test_json_output_is_stable():
    src = ("import time\nt0 = time.time()\n"
           "def f(x):\n    return x > 1e-9\n")
    findings = analyze_source(src, GUARDED)
    assert len(findings) == 2
    one, two = render_json(findings, []), render_json(findings, [])
    assert one == two
    payload = json.loads(one)
    assert payload["version"] == 1
    assert payload["counts"] == {"active": 2, "baselined": 0}
    assert [f["code"] for f in payload["findings"]] == ["RPR001", "RPR004"]
    assert all("line_text" in f and "path" in f for f in payload["findings"])


def test_summary_table_counts_per_rule():
    findings = analyze_source(
        "import time\nt0 = time.time()\nt1 = time.time()\n", LIB)
    table = summary_table(findings, [])
    line = next(l for l in table.splitlines() if l.startswith("RPR001"))
    assert line.split()[-2:] == ["2", "0"]
    assert table.splitlines()[-1].split() == ["total", "2", "0"]


# --------------------------------------------------------------------------
# The repo itself lints clean (the acceptance gate CI enforces)
# --------------------------------------------------------------------------

def test_repo_source_is_clean_under_baseline():
    baseline = load_baseline(REPO / "analysis-baseline.json")
    active, _ = run_source_analysis(["src", "benchmarks"], REPO, baseline)
    assert active == [], "\n".join(
        f"{f.location}: {f.code} {f.message}" for f in active)


# --------------------------------------------------------------------------
# CLI: exit codes 0 / 1 / 2
# --------------------------------------------------------------------------

def _cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        cwd=cwd, env=env, capture_output=True, text=True)


def test_cli_exit_codes(tmp_path):
    mod = tmp_path / "src" / "repro" / "core"
    mod.mkdir(parents=True)
    target = mod / "simulate.py"

    target.write_text("def g(x):\n    return x\n")
    assert _cli(["--root", str(tmp_path)], tmp_path).returncode == 0

    target.write_text("def g(x):\n    return x > 1e-9\n")
    proc = _cli(["--root", str(tmp_path)], tmp_path)
    assert proc.returncode == 1
    assert "RPR004" in proc.stdout
    assert "src/repro/core/simulate.py:2" in proc.stdout

    bad_baseline = tmp_path / "corrupt.json"
    bad_baseline.write_text("{not json")
    proc = _cli(["--root", str(tmp_path), "--baseline", str(bad_baseline)],
                tmp_path)
    assert proc.returncode == 2


def test_cli_json_format(tmp_path):
    mod = tmp_path / "src" / "repro" / "core"
    mod.mkdir(parents=True)
    (mod / "simulate.py").write_text("def g(x):\n    return x > 1e-9\n")
    proc = _cli(["--root", str(tmp_path), "--format", "json"], tmp_path)
    payload = json.loads(proc.stdout)
    assert payload["counts"]["active"] == 1
    assert payload["findings"][0]["code"] == "RPR004"


# --------------------------------------------------------------------------
# Layer 2: the compiled-program verifier (abstract tracing only)
# --------------------------------------------------------------------------

def test_verifier_full_inventory_passes():
    from repro.analysis.programs import PROGRAM_KEYS, verify_all

    checks = verify_all()
    failed = [c for c in checks if not c.ok]
    assert not failed, "\n".join(
        f"{c.program}/{c.check}: {c.detail}" for c in failed)
    assert {c.program for c in checks} == set(PROGRAM_KEYS)
    # the fold is the only donating program and the only one with a psum
    fold = {c.check: c for c in checks
            if c.program == "learn.fold:sharded"}
    assert fold["donation"].ok and fold["collectives"].ok
    assert "'all-reduce': 1" in fold["collectives"].detail


def test_verifier_unknown_key_is_a_failure():
    from repro.analysis.programs import verify_all

    checks = verify_all(keys=["no.such.program"])
    assert [(c.program, c.check, c.ok) for c in checks] == [
        ("no.such.program", "build", False)]


def test_broken_placement_contract_fails_with_program_key():
    # The acceptance demo: smuggle a psum into a zero-collective program
    # and the verifier must fail its collectives check by name — without
    # ever executing the program.
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.analysis.programs import verify_program

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("s",))
    broken = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "s"), mesh=mesh,
        in_specs=P("s"), out_specs=P()))
    arg = jax.ShapeDtypeStruct((len(devs), 4), jnp.float32)
    checks = verify_program(broken, (arg,), key="demo.sneaky-psum",
                            collectives={"total": 0})
    (coll,) = [c for c in checks if c.check == "collectives"]
    assert not coll.ok
    assert coll.program == "demo.sneaky-psum"
    assert "off contract" in coll.detail and "total=1" in coll.detail


def test_callback_in_program_fails_check():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.programs import verify_program

    def leaky(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    checks = verify_program(jax.jit(leaky),
                            (jax.ShapeDtypeStruct((4,), jnp.float32),),
                            key="demo.callback")
    (cb,) = [c for c in checks if c.check == "callbacks"]
    assert not cb.ok and "pure_callback" in cb.detail


def test_f64_program_fails_dtype_check():
    import jax
    import jax.numpy as jnp

    from repro.analysis.programs import verify_program

    if not jax.config.jax_enable_x64:
        pytest.skip("x64 disabled: f64 avals cannot be constructed")
    checks = verify_program(
        jax.jit(lambda x: x + 1.0),
        (jax.ShapeDtypeStruct((4,), jnp.float64),), key="demo.f64")
    (dt,) = [c for c in checks if c.check == "dtype"]
    assert not dt.ok


def test_invalid_donation_fails_check():
    import jax
    import jax.numpy as jnp

    from repro.analysis.programs import verify_program

    # donated (8,) input vs (4,) output: the alias can never be taken.
    checks = verify_program(
        jax.jit(lambda x: x[:4], donate_argnums=(0,)),
        (jax.ShapeDtypeStruct((8,), jnp.float32),),
        key="demo.bad-donation", donated=(0,))
    (don,) = [c for c in checks if c.check == "donation"]
    assert not don.ok and "matches NO output" in don.detail
