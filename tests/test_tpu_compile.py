"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler installed with jax compiles for a
topology that is described, not attached, and refuses what the chip would
refuse (block shapes off the (8, 128) tiling, too much fast memory). Sizes
are those of ``chip_smoke.py``'s jax phase: the paper's §6.1 stream of
10,000 type-1 jobs (L <= 49) over 32,287 price slots, and the 175-policy
self-owned grid (5 bids x 13 eval groups, 130,000 rows per bid).

The topology is described inside a module-scope fixture, so only the
worker that runs this file loads the TPU library, and every worker
collects the same tests.
"""

import functools
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

J, P, L = 10_000, 175, 49
N1 = 32_287 + 1              # slot boundaries of the §6.1 horizon
BIDS, ROWS = 5, 13 * J       # bids x (groups per bid x jobs)
S = 4                        # scenarios of the jax phase


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

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
    return jax.jit(fn).lower(*args).compile()


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    return used < 16e9, used


def test_policy_cost_chain_compiles(one_chip):
    from repro.kernels.policy_cost import policy_cost_chain

    f32 = jnp.float32
    fn = functools.partial(policy_cost_chain, slot=1 / 12, p_od=1.0,
                           interpret=False)
    c = _compile(fn, one_chip,
                 ((BIDS, 1, N1), f32), ((BIDS, 1, N1), f32),
                 ((BIDS, ROWS), f32), ((BIDS, ROWS, L), f32),
                 ((BIDS, ROWS, L), f32), ((BIDS, ROWS, L), f32),
                 ((BIDS, ROWS, L), f32))
    assert "tpu_custom_call" in c.as_text()
    assert _fits_hbm(c)[0], _fits_hbm(c)


def test_policy_cost_compiles(one_chip):
    from repro.kernels.policy_cost import policy_cost

    T = ROWS * L                 # the planned-start path's flattened tasks
    fn = functools.partial(policy_cost, slot=1 / 12, p_od=1.0,
                           interpret=False)
    c = _compile(fn, one_chip, ((N1,), jnp.float32), ((N1,), jnp.float32),
                 *[((T,), jnp.float32)] * 4)
    assert "tpu_custom_call" in c.as_text()


def test_hedge_kernel_compiles(one_chip):
    from repro.kernels.weight_update import _hedge_call

    BJ, Pp = 128, 256
    Jp = -(-J // BJ) * BJ
    W = -(-(Jp + 1) // 512) * 512     # the whole trajectory: the worst ring
    fn = functools.partial(_hedge_call, K=1, W=W, Pp=Pp, m=P, BJ=BJ,
                           interpret=False)
    c = _compile(fn, one_chip, ((S, Jp, Pp), jnp.float32),
                 ((1, 1, Jp), jnp.float32), ((S, 1, Jp), jnp.float32),
                 ((1, Jp), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_jax_chain_body_compiles(one_chip):
    from repro.engine.backend_jax import _chain_body

    f32 = jnp.float32
    fn = functools.partial(_chain_body, p_od=1.0, slot=1 / 12)
    c = _compile(fn, one_chip, ((S, N1), f32), ((S, N1), f32),
                 ((ROWS,), f32), ((ROWS, L), f32), ((ROWS, L), f32),
                 ((ROWS, L), f32), ((ROWS, L), jnp.bool_))
    assert "tpu_custom_call" not in c.as_text()
    assert _fits_hbm(c)[0], _fits_hbm(c)
