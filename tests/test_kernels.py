"""Pallas kernels vs jnp oracles (interpret mode), shape/dtype sweeps."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SpotMarket
from repro.core.simulate import simulate_tasks
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.policy_cost import policy_cost
from repro.kernels.ref import attention_ref, policy_cost_ref, ssd_ref
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "BH,BK,Sq,Sk,dh,causal,window,prefix",
    [
        (4, 2, 256, 256, 64, True, 0, 0),      # GQA causal
        (2, 2, 384, 384, 128, True, 0, 0),     # MHA, dh=128
        (4, 1, 128, 512, 64, False, 0, 0),     # cross attention (enc-dec)
        (2, 2, 512, 512, 64, True, 128, 16),   # sliding window + meta prefix
        (2, 1, 200, 300, 64, True, 0, 0),      # ragged (padding path)
        (1, 1, 640, 640, 64, True, 256, 0),    # window without prefix
    ],
)
def test_flash_attention_vs_ref(BH, BK, Sq, Sk, dh, causal, window, prefix,
                                dtype):
    q = jnp.asarray(RNG.normal(size=(BH, Sq, dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(BK, Sk, dh)), dtype)
    v = jnp.asarray(RNG.normal(size=(BK, Sk, dh)), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              prefix=prefix, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, prefix=prefix)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize(
    "Bb,S,H,P,G,N,chunk",
    [
        (2, 256, 4, 64, 1, 64, 64),
        (1, 200, 2, 32, 1, 16, 64),    # ragged
        (2, 128, 4, 64, 2, 32, 32),    # grouped B/C
        (1, 512, 8, 64, 1, 128, 128),  # mamba2-like dims
    ],
)
def test_ssd_scan_vs_sequential_ref(Bb, S, H, P, G, N, chunk):
    x = jnp.asarray(RNG.normal(size=(Bb, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(Bb, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(Bb, S, G, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bb, S, G, N)), jnp.float32)
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, str_ = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), atol=1e-4,
                               rtol=1e-4)


def test_ssd_jnp_chunked_matches_sequential():
    """The model's chunked jnp implementation (layers.ssd) against the
    sequential recurrence — independent check of the training path."""
    from repro.models.layers import ssd as ssd_jnp
    Bb, S, H, P, G, N = 2, 160, 4, 32, 1, 16
    x = jnp.asarray(RNG.normal(size=(Bb, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(Bb, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(Bb, S, G, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bb, S, G, N)), jnp.float32)
    y, st = ssd_jnp(x, dt, A, B, C, chunk=64)
    yr, str_ = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), atol=1e-4,
                               rtol=1e-4)


class TestPolicyCostKernel:
    def setup_method(self):
        self.m = SpotMarket(120.0, seed=3)
        self.v = self.m.view(0.24)

    def _tasks(self, T):
        start = RNG.uniform(0, 90, T)
        size = RNG.uniform(0.05, 20, T)
        end = start + size
        d = RNG.choice([1.0, 8.0, 64.0], T)
        z = RNG.uniform(0.0, 1.0, T) * d * size
        return start, end, z, d

    @pytest.mark.parametrize("T", [7, 64, 300])
    def test_against_exact_numpy_simulator(self, T):
        start, end, z, d = self._tasks(T)
        ref = simulate_tasks(self.v, start, end, z, d)
        out = policy_cost(
            jnp.asarray(self.v.A_cum, jnp.float32),
            jnp.asarray(self.v.C_cum, jnp.float32),
            jnp.asarray(start), jnp.asarray(end), jnp.asarray(z),
            jnp.asarray(d), interpret=True)
        np.testing.assert_allclose(out["spot_cost"], ref.spot_cost,
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(out["ondemand_cost"], ref.ondemand_cost,
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(out["spot_work"], ref.spot_work,
                                   atol=2e-3, rtol=2e-3)

    def test_jnp_ref_matches_numpy(self):
        start, end, z, d = self._tasks(128)
        ref = simulate_tasks(self.v, start, end, z, d)
        out = policy_cost_ref(
            jnp.asarray(self.v.A_cum, jnp.float32),
            jnp.asarray(self.v.C_cum, jnp.float32),
            jnp.asarray(start), jnp.asarray(end), jnp.asarray(z),
            jnp.asarray(d))
        np.testing.assert_allclose(out["spot_cost"], ref.spot_cost,
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(out["ondemand_cost"], ref.ondemand_cost,
                                   atol=2e-3, rtol=2e-3)


# --------------------------------------------------------------------------
# The chain kernel's two-level slot lookup
# --------------------------------------------------------------------------

def _full_count(cum, targets):
    """The full sweep's count #{k : cum[k] < target}, over every slot."""
    return (np.asarray(cum)[None, :] < np.asarray(targets)[:, None]).sum(
        axis=1)


def _lookup(cum, targets, idx):
    """The kernel's two-level counts of the three rows of ``cum`` (3, n1)
    below ``targets``, and its gathers (A[k], C[k], A[k+1], C[k+1]) at
    ``idx``, through one Pallas call (interpret mode)."""
    import jax
    from jax.experimental import pallas as pl

    from repro.kernels import policy_cost as pc

    BT = 128
    nq = -(-len(targets) // BT) * BT
    tg = np.zeros(nq, np.float32)
    tg[:len(targets)] = targets
    ks = np.zeros(nq, np.int32)
    ks[:len(idx)] = idx
    tiles, heads = pc._two_level(jnp.asarray(cum, jnp.float32))

    def kernel(t_ref, h_ref, q_ref, k_ref, o_ref):
        q = pc.to_col(q_ref[...], BT)
        k = pc.to_col(k_ref[...], BT)
        vals = [pc._count(t_ref, h_ref, q, r, BT)[0].astype(jnp.float32)
                for r in range(3)]
        vals += pc._gather2(t_ref, h_ref, k, BT)
        for i, v in enumerate(vals):
            o_ref[pl.ds(i, 1), :] = pc.to_row(v, BT)

    out = pl.pallas_call(
        kernel, grid=(nq // BT,),
        in_specs=[pl.BlockSpec(tiles.shape, lambda i: (0, 0)),
                  pl.BlockSpec(heads.shape, lambda i: (0, 0)),
                  pl.BlockSpec((1, BT), lambda i: (0, i)),
                  pl.BlockSpec((1, BT), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8, BT), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, nq), jnp.float32),
        interpret=True)(tiles, heads, jnp.asarray(tg)[None],
                        jnp.asarray(ks)[None])
    out = np.asarray(out)
    return (out[:3, :len(targets)].astype(np.int64),
            out[3:7, :len(idx)])


# n1 = n_slots + 1 slot boundaries: one tile, a full tile, one past it, a
# ragged tile, and 130 tiles (the heads span two lane tiles).
@pytest.mark.parametrize("n1", [5, 128, 129, 300, 128 * 129 + 2])
def test_two_level_lookup_matches_full_sweep(n1):
    """Count == searchsorted(side="left") and gather == indexing, bit for
    bit, on nondecreasing f32 arrays with flat runs and ties."""
    rng = np.random.default_rng(n1)
    step = np.float32(1 / 12)
    A = np.concatenate([[0], np.cumsum(rng.choice([0, step], n1 - 1))])
    C = np.cumsum(rng.uniform(0, 3, n1))          # arbitrary mantissas
    H = np.cumsum(rng.choice([0, step], n1))
    cum = np.stack([A, C, H]).astype(np.float32)
    vals = rng.choice(np.unique(cum), min(300, cum.size))
    targets = np.concatenate([
        vals,                                     # ties
        np.nextafter(vals, np.float32(np.inf)),
        np.nextafter(vals, np.float32(-np.inf)),
        [-1.0, 0.0, cum.max() + 1, 1e30],         # below, above horizon
    ]).astype(np.float32)
    # The device flushes subnormals to zero, and no query target is one.
    targets = targets[(targets == 0)
                      | (np.abs(targets) >= np.finfo(np.float32).tiny)]
    n = n1 - 1                                    # n_slots; k < n
    idx = np.clip(np.concatenate([[0, 126, 127, 128, 129, 255, 256, n - 1],
                                  rng.integers(0, n, 100)]), 0, n - 1)
    counts, gathers = _lookup(cum, targets, idx)
    for r in range(3):
        np.testing.assert_array_equal(
            counts[r], np.searchsorted(cum[r], targets, side="left"))
        np.testing.assert_array_equal(counts[r], _full_count(cum[r], targets))
    want = [A[idx], C[idx], A[idx + 1], C[idx + 1]]
    for got, w in zip(gathers, want):
        np.testing.assert_array_equal(got, np.asarray(w, np.float32))


def _exp1_kernel_inputs(monkeypatch, n_jobs=24):
    """The chain kernel's operands and outputs for one pallas
    ``evaluate_grid`` call on an Experiment 1 stream (type-4 chains, the 25
    spot/on-demand policies) against two fresh device-synthesized
    markets."""
    import repro.engine.backend_pallas as bp
    from repro.core import generate_chain_jobs, spot_od_policies
    from repro.engine import ScenarioSpec, evaluate_grid

    jobs = generate_chain_jobs(n_jobs, job_type=4, seed=11)
    spec = ScenarioSpec("fresh", max(j.deadline for j in jobs) + 1.0, 2,
                        seed=1000)
    chain, task = bp._kernels()
    seen = {}

    def spy(*args, **kw):
        seen["args"], seen["out"] = args, chain(*args, **kw)
        return seen["out"]

    monkeypatch.setattr(bp, "_kernels", lambda: (spy, task))
    evaluate_grid(jobs, spot_od_policies(), spec, 0, backend="pallas")
    args = [np.asarray(a) for a in seen["args"]]
    return args, {k: np.asarray(v) for k, v in seen["out"].items()}


def test_chain_kernel_exp1_markets(monkeypatch):
    """The chain kernel on Experiment 1 shapes against chain_costs_ref, and
    the rows whose H counts (queried at the planned windows) the two-level
    lookup answers differently from the full sweep: H = t - A is flat on
    available slots but may wiggle by ulps in f32, the one place the two
    can disagree; none does here."""
    from repro.core.simulate import FLEX_ABS, FLEX_REL
    from repro.kernels import policy_cost as pc
    from repro.kernels.ref import chain_costs_ref

    (A, C, arrival, ends, z_t, d_eff, pins), got = _exp1_kernel_inputs(
        monkeypatch)
    B, S, n1 = A.shape
    slot = np.float32(1 / 12)
    differ = 0
    for b in range(B):
        starts = np.concatenate([arrival[b][:, None], ends[b][:, :-1]], 1)
        for s in range(S):
            ref = chain_costs_ref(A[b, s], C[b, s], arrival[b], ends[b],
                                  z_t[b], d_eff[b], pins[b])
            # Per unit of work, as the engine's 1e-5 contract reads them.
            wl = np.maximum(z_t[b].sum(axis=1), 1e-12)
            for pair in (("spot_cost", "ondemand_cost"),
                         ("spot_work", "ondemand_work")):
                np.testing.assert_allclose(
                    sum(got[k][b, s] for k in pair) / wl,
                    sum(np.asarray(ref[k]) for k in pair) / wl,
                    atol=1e-5, rtol=1e-5, err_msg=f"{pair} bid {b} s {s}")
            np.testing.assert_allclose(
                got["spot_work"][b, s] / wl, np.asarray(ref["spot_work"]) / wl,
                atol=1e-5, rtol=1e-5, err_msg=f"spot share bid {b} s {s}")
            # H as the kernel holds it, and its targets at the planned
            # windows, formed as passes 1 and 2 of _task_costs form them.
            tiles = np.asarray(pc._stack_cum(A[b, s], C[b, s], 1 / 12)[0])
            H = tiles[:, 2 * 128:].reshape(-1)[:n1]
            k0 = np.clip((starts / slot).astype(np.int32), 0, n1 - 2)
            A0 = A[b, s][k0] + (A[b, s][k0 + 1] - A[b, s][k0]) / slot * (
                starts - k0 * slot)
            need = z_t[b] / np.where(d_eff[b] > 0, d_eff[b], 1)
            tgt = (starts - A0 + (ends[b] - starts) - need).astype(
                np.float32)
            two, _ = _lookup(np.stack([A[b, s], C[b, s], H]), tgt.ravel(),
                             np.zeros(1, np.int32))
            # Only the counts of active tasks with slack reach a result.
            span = ends[b] - starts
            no_flex = span - need <= np.maximum(
                1e-15, np.maximum(FLEX_REL * span, FLEX_ABS * ends[b]))
            used = (z_t[b] > 1e-15) & ~no_flex
            diff = two[2].reshape(tgt.shape) != _full_count(
                H, tgt.ravel()).reshape(tgt.shape)
            differ += int((diff & used).any(axis=1).sum())
    assert differ == 0
