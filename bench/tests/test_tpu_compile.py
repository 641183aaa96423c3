"""Compile the programs the cells' windows run, at the cells' sizes, for a
described TPU v5e (no chip needed).

* The sweep cell (``configs/paper61-exp1-type4.json``): the device plan, the
  market synthesis and views, and the Pallas chain kernel, at 2,500 jobs
  (L = 49 after the chain transform), 25 policies in 25 eval groups over 5
  bids (12,500 rows per bid), 4 scenarios of 12,628 slots.
* The TOLA cell (``configs/paper61-exp2-type1-r600.json``): the refinement
  round's device plan, split around the availability queries, and the
  Pallas chain kernel on per-scenario plans, at 1,250 jobs (L = 49), 175
  policies in 65 eval groups over 5 bids (16,250 rows per bid), 2 markets
  of 6,596 slots.
"""

import functools
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

J, L, S, N = 2_500, 49, 4, 12_628
BIDS, XS, GROUPS = 5, 5, 25
ROWS = (GROUPS // BIDS) * J
# The TOLA cell.
TJ, TS, TN, TGROUPS = 1_250, 2, 6_596, 65
TROWS = (TGROUPS // BIDS) * TJ


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return fn.lower(*args).compile()


def test_eval_kernel_compiles(one_chip):
    from repro.kernels.policy_cost import policy_cost_chain

    f32 = jnp.float32
    fn = jax.jit(functools.partial(policy_cost_chain, slot=1 / 12, p_od=1.0,
                                   interpret=False))
    c = _compile(fn, one_chip,
                 ((BIDS, S, N + 1), f32), ((BIDS, S, N + 1), f32),
                 ((BIDS, ROWS), f32), ((BIDS, ROWS, L), f32),
                 ((BIDS, ROWS, L), f32), ((BIDS, ROWS, L), f32),
                 ((BIDS, ROWS, L), jnp.bool_))
    assert "tpu_custom_call" in c.as_text()


def test_synth_and_views_compile(one_chip):
    from repro.engine import ScenarioSpec
    from repro.engine.scenarios import _device_synth_fn, _device_views_fn

    spec = ScenarioSpec("fresh", (N - 1) / 12, 2 ** 31 - 1, seed=1000)
    assert spec.n_slots == N
    i32 = jnp.int32
    _compile(_device_synth_fn(spec), one_chip, *[((S,), i32)] * 4)
    _compile(_device_views_fn(1 / 12), one_chip, ((S, N), i32),
             ((S, N), jnp.float32), ((S, N), jnp.bool_), ((S,), i32),
             ((), jnp.bool_))


def test_device_plan_compiles(one_chip):
    from repro.engine.plan import _device_plan_fns

    f32, i32 = jnp.float32, jnp.int32
    full = _device_plan_fns("prop12", "dealloc")["full"]
    _compile(full, one_chip, ((J, L), f32), ((J, L), f32),
             ((J, L), jnp.bool_), ((J,), f32), ((J,), f32), ((J, L), f32),
             ((XS,), f32), ((XS,), i32), ((XS,), f32), ((), f32),
             ((GROUPS,), i32))


def _tola_structure():
    from repro.core.baselines import selfowned_policies
    from repro.engine.plan import _grid_structure

    return _grid_structure(selfowned_policies(), 600, "dealloc")


def test_refinement_plan_compiles(one_chip):
    from repro.engine.plan import _device_plan_fns

    s = _tola_structure()
    W, Ga, G = len(s.key_param), len(s.a_plan), len(s.g_akey)
    assert G == TGROUPS
    f32, i32 = jnp.float32, jnp.int32
    fns = _device_plan_fns("prop12", "dealloc")
    _compile(fns["plans"], one_chip, ((TJ, L), f32), ((TJ, L), f32),
             ((TJ, L), jnp.bool_), ((TJ,), f32), ((TJ,), f32), ((W,), f32))
    _compile(fns["groups"], one_chip, ((TJ, L), f32), ((TJ, L), f32),
             ((TJ, L), jnp.bool_), ((W, TJ, L), f32), ((Ga,), i32),
             ((Ga,), f32), ((Ga, TS, TJ, L), f32), ((G,), i32))


def test_per_scenario_eval_kernel_compiles(one_chip):
    from repro.kernels.policy_cost import policy_cost_chain

    f32 = jnp.float32
    fn = jax.jit(functools.partial(policy_cost_chain, slot=1 / 12, p_od=1.0,
                                   interpret=False))
    c = _compile(fn, one_chip,
                 ((BIDS, TS, TN + 1), f32), ((BIDS, TS, TN + 1), f32),
                 ((BIDS, TROWS), f32), ((BIDS, TROWS, L), f32),
                 ((BIDS, TS, TROWS, L), f32), ((BIDS, TS, TROWS, L), f32),
                 ((BIDS, TS, TROWS, L), jnp.bool_))
    assert "tpu_custom_call" in c.as_text()
