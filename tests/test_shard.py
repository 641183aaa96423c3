"""Sharded scenario x group axes (DESIGN.md §9): GridMesh, shard_map'ed
jobs -> cost -> regret, the two-axis padding contract, and the
one-psum-per-chunk rule.

Fast tests run in-process on whatever devices are visible (a 1-device mesh
is the degenerate case and must be BITWISE identical to the unsharded jax
path — same program, same f32 arithmetic). Multi-device behavior (real
2-D sharding, padding of S % data_shards != 0 and G % model_shards != 0,
sharded refinement rounds) runs in-process when 8 devices are visible (the
shard-smoke CI job forces 8 host devices) and in a slow subprocess test
that forces them itself, because the XLA device-count flag must be set
before jax initializes.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import generate_chain_jobs, selfowned_policies
from repro.engine import (
    GridMesh,
    ScenarioSpec,
    as_scenario_mesh,
    evaluate_grid,
    make_scenarios,
)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _setup(n=20, jt=2, seed=0):
    jobs = generate_chain_jobs(n, jt, seed=seed)
    horizon = max(j.deadline for j in jobs) + 1.0
    return jobs, horizon


GRID = selfowned_policies()[:12]


# --------------------------------------------------------------------------
# Mesh construction and argument normalization
# --------------------------------------------------------------------------

def test_mesh_create_defaults_and_padding():
    mesh = GridMesh.create()
    assert mesh.n_shards == len(jax.devices())
    n = mesh.n_shards
    assert mesh.pad(0) == 0
    assert mesh.pad(1) == n
    assert mesh.pad(n) == n
    assert mesh.pad(n + 1) == 2 * n
    a = np.arange(10.0).reshape(5, 2)
    padded = mesh.pad_rows(a)
    assert padded.shape[0] == mesh.pad(5)
    # padding repeats the LAST row — real scenario data, masked downstream
    assert np.array_equal(padded[5:], np.repeat(a[-1:], len(padded) - 5, 0))


def test_mesh_2d_axes_and_group_padding():
    # The 1-D mesh is a GridMesh whose second logical axis
    # group -> "model" is absent, with its own whole-group padding contract.
    mesh = GridMesh.create(1)          # 1-D: model axis absent, 1-wide
    assert mesh.data_shards == 1
    assert mesh.model_shards == 1
    from jax.sharding import PartitionSpec as P
    assert mesh.spec("scenario", "group", "bid") == P("data", None, None)
    assert mesh.pad_groups(5) == 5
    from repro.engine.mesh import edge_repeat, pad_to
    assert pad_to(13, 4) == 16 and pad_to(8, 4) == 8 and pad_to(0, 3) == 0
    a = np.arange(6.0).reshape(3, 2)
    p = edge_repeat(a, 5)
    assert p.shape == (5, 2)
    assert np.array_equal(p[3:], np.repeat(a[-1:], 2, axis=0))
    with pytest.raises(ValueError):
        edge_repeat(a, 2)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
@pytest.mark.parametrize("shape", [(4, 2), (2, 4)])
def test_mesh_2d_create(shape):
    n, m = shape
    mesh = GridMesh.create(n, m)
    assert mesh.n_shards == n * m
    assert (mesh.data_shards, mesh.model_shards) == (n, m)
    assert tuple(mesh.mesh.axis_names) == ("data", "model")
    # scenario rows pad to data_shards, groups to model_shards
    assert mesh.pad(n + 1) == 2 * n
    assert mesh.pad_groups(m + 1) == 2 * m
    # logical-axis routing: scenario -> data, group -> model
    from jax.sharding import PartitionSpec as P
    assert mesh.spec("scenario") == P("data")
    assert mesh.spec("group") == P("model")
    assert mesh.spec("scenario", "group") == P("data", "model")
    # a raw 2-D jax Mesh normalizes too
    from repro.launch.mesh import make_mesh
    got = as_scenario_mesh(make_mesh(shape, ("data", "model")))
    assert (got.data_shards, got.model_shards) == shape


def test_mesh_create_raises_past_visible_devices_1d():
    avail = len(jax.devices())
    with pytest.raises(ValueError) as err:
        GridMesh.create(avail + 7)
    msg = str(err.value)
    # the message names both the requested and the visible device counts
    assert f"{avail + 7}-device" in msg and f"only {avail} device" in msg
    assert "xla_force_host_platform_device_count" in msg


def test_mesh_create_raises_past_visible_devices_2d():
    avail = len(jax.devices())
    with pytest.raises(ValueError) as err:
        GridMesh.create(avail, 2)
    msg = str(err.value)
    assert f"{avail}x2 ({2 * avail}-device)" in msg
    assert f"only {avail} device" in msg


def test_as_scenario_mesh_normalization():
    assert as_scenario_mesh(None) is None
    mesh = GridMesh.create(1)
    assert as_scenario_mesh(mesh) is mesh
    assert as_scenario_mesh(1).n_shards == 1
    with pytest.raises(ValueError):
        as_scenario_mesh(True)
    with pytest.raises(ValueError):
        as_scenario_mesh(0)
    with pytest.raises(ValueError):
        as_scenario_mesh("data")
    # a raw jax Mesh is accepted iff it has a "data" axis
    from repro.launch.mesh import make_mesh
    assert as_scenario_mesh(make_mesh((1,), ("data",))).n_shards == 1
    with pytest.raises(ValueError, match="data"):
        as_scenario_mesh(make_mesh((1,), ("model",)))


def test_mesh_is_hashable_cache_key():
    m1 = GridMesh.create(1)
    m2 = GridMesh.create(1)
    assert hash(m1) == hash(m2)
    assert m1 == m2


# --------------------------------------------------------------------------
# Guard rails at the API boundary
# --------------------------------------------------------------------------

def test_mesh_rejects_non_jax_backends():
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 4, seed=3)
    mesh = GridMesh.create(1)
    with pytest.raises(ValueError, match="mesh"):
        evaluate_grid(jobs, GRID, spec, 300, backend="numpy", mesh=mesh)
    with pytest.raises(ValueError, match="mesh"):
        evaluate_grid(jobs, GRID, spec, 300, backend="pallas", mesh=mesh)


def _per_scenario_avails(S, J):
    """Deterministic per-scenario availability queries (one per scenario,
    distinct results) shaped like TOLA's realized-residual queries."""
    def make(s):
        return lambda starts, ends: np.full_like(
            np.asarray(starts, np.float64), float(s % 3))
    return [make(s) for s in range(S)]


def test_mesh_shards_per_scenario_availability():
    # Refined (per-scenario availability) plans evaluate SHARDED since the
    # 2-D GridMesh landed: the (S, R, L) self-owned stacks ride the "data"
    # axis next to the views. 1-device mesh: bitwise == unsharded jax;
    # both within 1e-5 of the f64 numpy oracle.
    jobs, horizon = _setup()
    markets = make_scenarios(horizon, 3, seed=1)
    avail = _per_scenario_avails(len(markets), len(jobs))
    oracle = evaluate_grid(jobs, GRID, markets, 300, backend="numpy",
                           availability=avail).unit_cost
    ref = evaluate_grid(jobs, GRID, markets, 300, backend="jax",
                        availability=avail).unit_cost
    got = evaluate_grid(jobs, GRID, markets, 300, backend="jax",
                        availability=avail,
                        mesh=GridMesh.create(1)).unit_cost
    assert np.array_equal(ref, got)
    assert np.abs(got - oracle).max() < 1e-5


def test_overlap_rejects_reactive_stream():
    jobs, horizon = _setup()
    spec = ScenarioSpec("adaptive", horizon, 8, seed=3)
    with pytest.raises(ValueError, match="reactive|adaptive"):
        evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                      scenario_chunk=4, overlap=True)


def test_replay_stream_mesh_rejects_numpy_replay():
    from repro.learn import replay_stream

    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 4, seed=3)
    with pytest.raises(ValueError, match="mesh"):
        replay_stream(jobs, GRID, spec, 300, backend="numpy",
                      mesh=GridMesh.create(1))


# --------------------------------------------------------------------------
# 1-device mesh: the degenerate case is bitwise the unsharded jax program
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["fresh", "adversarial", "adaptive"])
def test_one_device_mesh_bitwise_spec(kind):
    jobs, horizon = _setup()
    spec = ScenarioSpec(kind, horizon, 5, seed=7)
    ref = evaluate_grid(jobs, GRID, spec, 300, backend="jax")
    got = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                        mesh=GridMesh.create(1))
    assert np.array_equal(ref.unit_cost, got.unit_cost)
    assert np.array_equal(ref.spot_cost, got.spot_cost)


def test_one_device_mesh_bitwise_market_list():
    jobs, horizon = _setup()
    markets = make_scenarios(horizon, 3, seed=1)
    ref = evaluate_grid(jobs, GRID, markets, 300, backend="jax")
    got = evaluate_grid(jobs, GRID, markets, 300, backend="jax",
                        mesh=GridMesh.create(1))
    assert np.array_equal(ref.unit_cost, got.unit_cost)


def test_one_device_mesh_bitwise_task_path():
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 4, seed=7)
    ref = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                        early_start=False)
    got = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                        early_start=False, mesh=GridMesh.create(1))
    assert np.array_equal(ref.unit_cost, got.unit_cost)


def test_mesh_chunked_uneven_mean_matches_oracle():
    # S=7 with chunk=3 exercises BOTH uneven weighting (a final short
    # chunk under reduce="mean") and mesh padding of every chunk.
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 7, seed=7)
    oracle = evaluate_grid(jobs, GRID, spec, 300, backend="numpy",
                           reduce="mean").unit_cost
    sharded = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                            scenario_chunk=3, reduce="mean",
                            mesh=GridMesh.create(1)).unit_cost
    assert np.abs(sharded - oracle).max() < 1e-5
    # and without reduce: concatenated chunks, padding sliced off
    full = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                         scenario_chunk=3,
                         mesh=GridMesh.create(1)).unit_cost
    assert full.shape[0] == 7
    mono = evaluate_grid(jobs, GRID, spec, 300, backend="jax").unit_cost
    assert np.array_equal(full, mono)


def test_overlap_bitwise_and_flagged():
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 6, seed=7)
    ref = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                        scenario_chunk=2, overlap=False)
    ov = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                       scenario_chunk=2, overlap=True)
    assert np.array_equal(ref.unit_cost, ov.unit_cost)
    assert ov.timings["overlap"] is True
    assert ref.timings["overlap"] is False
    # overlap is the DEFAULT for non-reactive jax streams
    dflt = evaluate_grid(jobs, GRID, spec, 300, backend="jax",
                         scenario_chunk=2)
    assert dflt.timings["overlap"] is True


def test_replay_stream_sharded_fold_matches_host_fold():
    from repro.learn import replay_stream

    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 7, seed=5)
    # multi-kind learner set exercises the grouped scan + inverse perm
    learners = ["hedge", "exp3", "egreedy"]
    ref = replay_stream(jobs, GRID, spec, 300, learners=learners, seed=11,
                        scenario_chunk=3, backend="jax",
                        engine_backend="jax")
    sh = replay_stream(jobs, GRID, spec, 300, learners=learners, seed=11,
                       scenario_chunk=3, backend="jax",
                       engine_backend="jax", mesh=GridMesh.create(1))
    assert sh.n_scenarios == ref.n_scenarios == 7
    assert sh.n_chunks == ref.n_chunks == 3
    # device f32 fold vs host f64-on-f32-traces fold: ~1e-4 budget
    assert np.abs(ref.regret_per_job() - sh.regret_per_job()).max() < 1e-4
    assert np.abs(ref.realized_unit() - sh.realized_unit()).max() < 1e-4
    assert abs(ref.best_fixed() - sh.best_fixed()) < 1e-4
    m0, lo0, hi0 = ref.confidence_bands()
    m1, lo1, hi1 = sh.confidence_bands()
    assert np.abs(m0 - m1).max() < 1e-4
    assert np.abs(hi0 - hi1).max() < 1e-4
    assert np.abs(ref.weights() - sh.weights()).max() < 1e-4
    for a, b in zip(ref.summary(), sh.summary()):
        assert a["learner"] == b["learner"]
        assert abs(a["top_weight"] - b["top_weight"]) < 1e-4
        assert abs(a["expected_regret"] - b["expected_regret"]) < 1e-4


def test_replay_stream_sharded_adaptive_round_trip():
    from repro.learn import replay_stream

    jobs, horizon = _setup()
    spec = ScenarioSpec("adaptive", horizon, 8, seed=5)
    ref = replay_stream(jobs, GRID, spec, 300, learners=["hedge"], seed=3,
                        scenario_chunk=4, backend="jax",
                        engine_backend="jax")
    sh = replay_stream(jobs, GRID, spec, 300, learners=["hedge"], seed=3,
                       scenario_chunk=4, backend="jax",
                       engine_backend="jax", mesh=GridMesh.create(1))
    # the adversary consumed the SAME feedback signal chunk by chunk
    assert np.abs(ref.regret_per_job() - sh.regret_per_job()).max() < 1e-4


def test_run_tola_scenarios_accepts_mesh():
    from repro.core import run_tola_scenarios

    jobs, horizon = _setup(n=12)
    markets = make_scenarios(horizon, 2, seed=1)
    ref = run_tola_scenarios(jobs, GRID, markets, r_total=300, seed=0,
                             pool_iters=2, backend="jax")
    # the mesh rides EVERY round now — round 0 and the per-scenario
    # refinement rounds alike (DESIGN.md §9); 1-device mesh is bitwise
    got = run_tola_scenarios(jobs, GRID, markets, r_total=300, seed=0,
                             pool_iters=2, backend="jax",
                             mesh=GridMesh.create(1))
    for a, b in zip(ref, got):
        assert np.array_equal(a.cost_matrix, b.cost_matrix)
        assert np.array_equal(a.chosen, b.chosen)


def test_run_tola_scenarios_keeps_mesh_every_round(monkeypatch):
    # Every evaluate_grid call of a TOLA run, round 0 and each pool
    # refinement round, receives the caller's mesh unchanged.
    import repro.engine as engine
    from repro.core import run_tola_scenarios

    seen = []
    real = engine.evaluate_grid

    def spy(*args, **kwargs):
        seen.append((kwargs.get("availability") is not None,
                     kwargs.get("mesh")))
        return real(*args, **kwargs)

    monkeypatch.setattr(engine, "evaluate_grid", spy)
    jobs, horizon = _setup(n=12)
    markets = make_scenarios(horizon, 2, seed=1)
    mesh = GridMesh.create(1)
    run_tola_scenarios(jobs, GRID, markets, r_total=300, seed=0,
                       pool_iters=2, backend="jax", mesh=mesh)
    assert [refined for refined, _ in seen] == [False, True, True]
    assert all(m is mesh for _, m in seen)


def test_sweep_policies_accepts_mesh():
    from repro.core import sweep_policies

    jobs, horizon = _setup(n=12)
    spec = ScenarioSpec("fresh", horizon, 4, seed=2)
    _, a_ref, _, _ = sweep_policies(jobs, GRID, spec, 300, backend="jax")
    _, a_mesh, _, _ = sweep_policies(jobs, GRID, spec, 300, backend="jax",
                                     mesh=GridMesh.create(1))
    assert a_ref == a_mesh


# --------------------------------------------------------------------------
# Collective counts in the compiled programs: the §9 placement contract,
# verified through the single implementation in repro.analysis.programs
# (the same Layer-2 pass CI runs) — not ad-hoc HLO greps.
# --------------------------------------------------------------------------

def _verify(keys):
    from repro.analysis.programs import verify_all

    checks = verify_all(mesh=GridMesh.create(), keys=keys)
    assert checks, f"no checks produced for {keys}"
    failed = [c for c in checks if not c.ok]
    assert not failed, "\n".join(f"{c.program}/{c.check}: {c.detail}"
                                 for c in failed)
    return checks


def test_cost_program_has_zero_collectives():
    # The scenario axis never reduces inside the cost tensor, so the
    # compiled sharded chain/task programs must contain NO collectives —
    # sharding the hot loop costs zero cross-device traffic.
    checks = _verify(["engine.eval.chain:sharded", "engine.eval.task:sharded"])
    colls = [c for c in checks if c.check == "collectives"]
    assert len(colls) == 2
    for c in colls:
        assert "'total': 0" in c.detail


def test_refinement_program_has_zero_collectives():
    # The per-scenario (pool refinement) programs obey the same contract:
    # the (S, R, L) self-owned stacks shard alongside the views and no
    # axis reduces cross-device — refinement rounds cost zero collectives.
    checks = _verify(["engine.eval.chain_ps:sharded",
                      "engine.eval.task_ps:sharded"])
    colls = [c for c in checks if c.check == "collectives"]
    assert len(colls) == 2
    for c in colls:
        assert "'total': 0" in c.detail


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
@pytest.mark.parametrize("shape", [(4, 2), (2, 4)])
def test_2d_placement_contract(shape):
    # The §9 standing metric on a REAL 2-D mesh: every canonical program
    # (refinement included) placed per contract, zero violations.
    from repro.obs.compiled import placement_violations

    assert placement_violations(mesh=GridMesh.create(*shape)) == []


def test_synth_program_has_zero_collectives():
    checks = _verify(["scenarios.synth:fresh:sharded"])
    (coll,) = [c for c in checks if c.check == "collectives"]
    assert "'total': 0" in coll.detail


def test_fold_program_has_exactly_one_allreduce():
    # replay_stream's sharded fold: every per-learner sum rides ONE packed
    # psum — exactly one all-reduce per chunk, and no other collective.
    checks = _verify(["learn.fold:sharded"])
    (coll,) = [c for c in checks if c.check == "collectives"]
    assert "'all-reduce': 1" in coll.detail
    assert "'total': 1" in coll.detail


def test_placement_violations_empty_on_contract():
    # obs.compiled.placement_violations is the standing-metric face of the
    # same verifier: the §9 contract holding means an empty violation list.
    from repro.obs.compiled import placement_violations

    assert placement_violations(
        mesh=GridMesh.create(),
        keys=["engine.eval.chain:sharded", "learn.fold:sharded"]) == []


# --------------------------------------------------------------------------
# Real 2-D sharding in-process (the shard-smoke CI job forces 8 devices)
# --------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
@pytest.mark.parametrize("shape", [(4, 2), (2, 4)])
def test_2d_mesh_eval_parity(shape):
    # S=13 % data_shards != 0 AND (with 7 policies) G % model_shards != 0:
    # both padding contracts at once. Bitwise vs unsharded jax (no
    # cross-lane arithmetic anywhere in the cost tensor), <=1e-5 vs the
    # f64 oracle.
    jobs, horizon = _setup(n=13, seed=3)
    grid = selfowned_policies()[:7]
    markets = make_scenarios(horizon, 13, seed=1)
    mesh = GridMesh.create(*shape)
    for early in (True, False):
        ref = evaluate_grid(jobs, grid, markets, 300, backend="jax",
                            early_start=early).unit_cost
        orc = evaluate_grid(jobs, grid, markets, 300, backend="numpy",
                            early_start=early).unit_cost
        got = evaluate_grid(jobs, grid, markets, 300, backend="jax",
                            early_start=early, mesh=mesh).unit_cost
        assert np.array_equal(ref, got)
        assert np.abs(got - orc).max() < 1e-5


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
@pytest.mark.parametrize("shape", [(4, 2), (2, 4)])
def test_2d_mesh_refinement_rounds(shape):
    # run_tola_scenarios keeps mesh= through the refinement rounds: the
    # per-scenario availability pass shards over both axes and matches
    # the unsharded run bitwise.
    from repro.core import run_tola_scenarios

    jobs, horizon = _setup(n=13, seed=3)
    markets = make_scenarios(horizon, 5, seed=1)
    ref = run_tola_scenarios(jobs, GRID, markets, r_total=6, seed=0,
                             pool_iters=2, backend="jax")
    got = run_tola_scenarios(jobs, GRID, markets, r_total=6, seed=0,
                             pool_iters=2, backend="jax",
                             mesh=GridMesh.create(*shape))
    for a, b in zip(ref, got):
        assert np.array_equal(a.cost_matrix, b.cost_matrix)
        assert np.array_equal(a.chosen, b.chosen)


# --------------------------------------------------------------------------
# Real multi-device sharding: 8 forced host devices in a subprocess
# --------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import numpy as np
import jax
from repro.core import generate_chain_jobs, selfowned_policies
from repro.core import run_tola_scenarios
from repro.engine import GridMesh, ScenarioSpec, evaluate_grid
from repro.engine import make_scenarios
from repro.learn import replay_stream

assert len(jax.devices()) == 8
jobs = generate_chain_jobs(20, 2, seed=0)
horizon = max(j.deadline for j in jobs) + 1.0
grid = selfowned_policies()[:12]
mesh = GridMesh.create(8)
out = {"n_shards": mesh.n_shards}

# S=13 % 8 != 0 forces padding; parity vs the f64 oracle AND bitwise vs
# the unsharded jax program (no cross-scenario arithmetic in the tensor)
diffs, bitwise = {}, {}
for kind in ("fresh", "adversarial", "regime"):
    spec = ScenarioSpec(kind, horizon, 13, seed=7)
    oracle = evaluate_grid(jobs, grid, spec, 300, backend="numpy").unit_cost
    sh = evaluate_grid(jobs, grid, spec, 300, backend="jax",
                       mesh=mesh).unit_cost
    un = evaluate_grid(jobs, grid, spec, 300, backend="jax").unit_cost
    diffs[kind] = float(np.abs(sh - oracle).max())
    bitwise[kind] = bool(np.array_equal(sh, un))
out["oracle_diffs"] = diffs
out["bitwise_vs_unsharded"] = bitwise

# sharded replay fold on 8 devices vs the host fold
spec = ScenarioSpec("fresh", horizon, 13, seed=5)
ref = replay_stream(jobs, grid, spec, 300, learners=["hedge", "exp3"],
                    seed=11, scenario_chunk=5, backend="jax",
                    engine_backend="jax")
sh = replay_stream(jobs, grid, spec, 300, learners=["hedge", "exp3"],
                   seed=11, scenario_chunk=5, backend="jax",
                   engine_backend="jax", mesh=mesh)
out["fold_n"] = [ref.n_scenarios, sh.n_scenarios]
out["fold_regret_diff"] = float(
    np.abs(ref.regret_per_job() - sh.regret_per_job()).max())
out["fold_curve_diff"] = float(
    np.abs(ref.confidence_bands()[0] - sh.confidence_bands()[0]).max())

# 2-D meshes (4x2, 2x4): S=13 % 4 != 0 AND 7 policies force group padding;
# refinement rounds (per-scenario availability) stay sharded throughout
grid7 = selfowned_policies()[:7]
markets = make_scenarios(horizon, 13, seed=1)
orc = evaluate_grid(jobs, grid7, markets, 300, backend="numpy").unit_cost
un = evaluate_grid(jobs, grid7, markets, 300, backend="jax").unit_cost
m5 = make_scenarios(horizon, 5, seed=2)
ref_tola = run_tola_scenarios(jobs, grid, m5, r_total=6, seed=0,
                              pool_iters=2, backend="jax")
grid2d = {}
for shape in ((4, 2), (2, 4)):
    gmesh = GridMesh.create(*shape)
    sh2 = evaluate_grid(jobs, grid7, markets, 300, backend="jax",
                        mesh=gmesh).unit_cost
    got_tola = run_tola_scenarios(jobs, grid, m5, r_total=6, seed=0,
                                  pool_iters=2, backend="jax", mesh=gmesh)
    grid2d["%dx%d" % shape] = {
        "shards": [gmesh.data_shards, gmesh.model_shards],
        "oracle_diff": float(np.abs(sh2 - orc).max()),
        "bitwise_vs_unsharded": bool(np.array_equal(sh2, un)),
        "refine_bitwise": bool(all(
            np.array_equal(a.cost_matrix, b.cost_matrix)
            and np.array_equal(a.chosen, b.chosen)
            for a, b in zip(ref_tola, got_tola))),
    }
out["grid2d"] = grid2d
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_8_devices_subprocess():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
             "HOME": os.environ.get("HOME", "")},
        cwd=Path(__file__).resolve().parents[1], timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_shards"] == 8
    for kind, diff in res["oracle_diffs"].items():
        assert diff < 1e-5, (kind, diff)
    assert all(res["bitwise_vs_unsharded"].values())
    assert res["fold_n"] == [13, 13]
    assert res["fold_regret_diff"] < 1e-4
    assert res["fold_curve_diff"] < 1e-4
    assert set(res["grid2d"]) == {"4x2", "2x4"}
    for shape, r in res["grid2d"].items():
        assert r["shards"] == [int(x) for x in shape.split("x")], shape
        assert r["oracle_diff"] < 1e-5, (shape, r)
        assert r["bitwise_vs_unsharded"], shape
        assert r["refine_bitwise"], shape
