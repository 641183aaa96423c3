"""Pallas TPU kernel for the online-learning hot loop: the fused Hedge
weight-update replay (paper Alg. 4 over a precomputed cost tensor).

The recurrence is tiny per step (an (m,)-vector exponentiated-weights
update) but strictly sequential over jobs, and the learn subsystem replays
it for every (scenario x learner x schedule-grid) instance. The TPU
formulation exploits that the FULL-INFORMATION update does not depend on
the sampled trace, so the replay factors into two passes over
VMEM-resident data. The grid is (instance, job block): job blocks run in
order and each one does both passes for its ``BJ`` jobs.

1. *Trajectory pass* — ``fori_loop`` over the block's update events in
   order: ``logw <- logw - eta_j * C[j]`` followed by the log-space
   renormalization ``logw <- logw - max(logw)`` (the exp-rescale that pins
   the top weight at exp(0) = 1 so long horizons cannot flush the weights
   to zero), each state written to a VMEM ring of ``W`` trajectory rows
   (row ``t`` lives at ``t % W``).
2. *Sample pass* — the delayed-feedback offset ``n_done[j]`` (how many
   updates had been applied when job j sampled) selects each job's
   trajectory row via a one-hot MATMUL (MXU work instead of serial
   gathers); normalize to probabilities, inverse-CDF sample against the
   precomputed uniform stream (cumsum as a triangular-ones matmul, then a
   comparison count), and read off the chosen index, its probability and
   the expected cost.

Fast memory: ``C`` streams through VMEM one (BJ, Pp) job block at a time,
and the ring holds only the rows a block can still sample from — ``W``
covers the block plus the longest feedback delay in jobs
(``max_j j - n_done[j]``), so VMEM use does not grow with J.

Oracle: ``kernels/ref.py::hedge_replay_ref`` (vectorized numpy, same
two-pass factorization) and the sequential event loop in
``repro.learn.replay`` (float64, structurally different) — see
tests/test_learn.py. tests/test_tpu_compile.py compiles the kernel for a
described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret
from repro.kernels.policy_cost import to_col, to_row

__all__ = ["hedge_replay"]

_NEG = -3.0e38  # "minus infinity" that stays finite in float32
_LANES = 128
_TR = 512       # trajectory rows per one-hot matmul of the sample pass
_HI = jax.lax.Precision.HIGHEST


def _hedge_kernel(C_ref, eta_ref, u_ref, nd_ref,
                  ch_ref, ps_ref, ec_ref, wf_ref, traj, *,
                  W: int, Pp: int, m: int, BJ: int):
    c = pl.program_id(1)
    base = c * BJ

    @pl.when(c == 0)
    def _():
        # Zero the ring so unwritten rows contribute exact zeros to the
        # one-hot matmuls (uninitialized VMEM could hold NaNs).
        traj[...] = jnp.zeros((W, Pp), jnp.float32)
        lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, Pp), 1)
        traj[pl.ds(0, 1), :] = jnp.where(
            lane1 < m, jnp.float32(-np.log(m)), jnp.float32(_NEG))

    lane_bj = jax.lax.broadcasted_iota(jnp.int32, (1, BJ), 1)
    eta_blk = eta_ref[...]                                # (1, BJ)

    def stepA(t, logw):
        c_row = C_ref[pl.ds(t, 1), :]                     # (1, Pp)
        eta = jnp.sum(jnp.where(lane_bj == t, eta_blk, 0.0), axis=1,
                      keepdims=True)                      # (1, 1)
        logw = logw - eta * c_row
        logw = logw - jnp.max(logw, axis=1, keepdims=True)  # exp-rescale
        traj[pl.ds((base + t + 1) % W, 1), :] = logw
        return logw

    logw_f = jax.lax.fori_loop(0, BJ, stepA, traj[pl.ds(base % W, 1), :])
    wf_ref[...] = logw_f

    # Sample pass: gather each job's trajectory row through one-hot
    # matmuls over the ring, _TR rows at a time.
    slot_col = to_col(nd_ref[...], BJ) % W                # (BJ, 1) int32
    rows = jax.lax.broadcasted_iota(jnp.int32, (BJ, _TR), 1)

    def gather(r, acc):
        oh = (rows + r * _TR == slot_col).astype(jnp.float32)
        blk = traj[pl.ds(pl.multiple_of(r * _TR, _TR), _TR), :]
        return acc + jnp.dot(oh, blk, precision=_HI,
                             preferred_element_type=jnp.float32)

    logw_s = jax.lax.fori_loop(0, W // _TR, gather,
                               jnp.zeros((BJ, Pp), jnp.float32))
    logw_s = logw_s - jnp.max(logw_s, axis=1, keepdims=True)
    p = jnp.exp(logw_s)
    p = p / jnp.sum(p, axis=1, keepdims=True)
    # tri[i, k] = 1 iff i <= k: p @ tri is an inclusive cumsum along lanes.
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Pp, Pp), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (Pp, Pp), 1)
           ).astype(jnp.float32)
    cdf = jnp.dot(p, tri, precision=_HI, preferred_element_type=jnp.float32)
    uu = to_col(u_ref[...], BJ)                          # (BJ, 1)
    total = cdf[:, Pp - 1:Pp]
    cnt = jnp.sum((cdf <= uu * total).astype(jnp.int32), axis=1,
                  keepdims=True)
    chosen = jnp.minimum(cnt, m - 1)                      # (BJ, 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (BJ, Pp), 1)
    oh_c = (lanes == chosen).astype(jnp.float32)
    ch_ref[...] = to_row(chosen, BJ)
    ps_ref[...] = to_row(jnp.sum(p * oh_c, axis=1, keepdims=True), BJ)
    ec_ref[...] = to_row(jnp.sum(p * C_ref[...], axis=1, keepdims=True),
                          BJ)


def _hedge_call(C_p, eta_p, u_p, nd_p, *, K: int, W: int, Pp: int, m: int,
                BJ: int, interpret: bool):
    """The traceable pallas launch on the padded layout.

    ``C_p`` (S, Jp, Pp), ``eta_p`` (K, 1, Jp), ``u_p`` (S, 1, Jp) and
    ``nd_p`` (1, Jp); returns chosen/p_chosen/expected_cost (S*K, 1, Jp)
    and final log-weights (S*K, 1, Pp). Split from :func:`hedge_replay`
    (which owns the host-side numpy padding) so the device program can be
    traced and compiled on ShapeDtypeStructs without executing it.
    """
    S, Jp = C_p.shape[0], C_p.shape[1]
    kernel = functools.partial(_hedge_kernel, W=W, Pp=Pp, m=m, BJ=BJ)
    B = S * K
    jrow = lambda imap: pl.BlockSpec((None, 1, BJ), imap)
    return pl.pallas_call(
        kernel,
        grid=(B, Jp // BJ),
        in_specs=[
            pl.BlockSpec((None, BJ, Pp), lambda b, c: (b // K, c, 0)),
            jrow(lambda b, c: (b % K, 0, c)),
            jrow(lambda b, c: (b // K, 0, c)),
            pl.BlockSpec((1, BJ), lambda b, c: (0, c)),
        ],
        out_specs=[
            jrow(lambda b, c: (b, 0, c)),
            jrow(lambda b, c: (b, 0, c)),
            jrow(lambda b, c: (b, 0, c)),
            pl.BlockSpec((None, 1, Pp), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Jp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Jp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Jp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Pp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((W, Pp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(C_p, eta_p, u_p, nd_p)


def ring_rows(n_done, BJ: int) -> int:
    """Trajectory rows the ring needs: one job block, the longest feedback
    delay in jobs, and the block's starting row, rounded to the matmul
    tile."""
    n_done = np.asarray(n_done, np.int64)
    J = len(n_done)
    lag = int((np.arange(J) - n_done).max(initial=0))
    Jp = -(-J // BJ) * BJ
    return -(-min(Jp + 1, BJ + lag + 1) // _TR) * _TR


def hedge_replay(C, etas, u, n_done, *, block_jobs: int = 128,
                 interpret: bool | None = None):
    """Fused Hedge replay over a (S, J, P) cost tensor.

    ``C``: per-scenario counterfactual unit costs; ``etas``: (K, J)
    per-update learning rates (one row per schedule-grid instance); ``u``:
    (S, J) per-scenario uniform sampling streams; ``n_done``: (J,) updates
    applied before each job's sample (``repro.learn.replay.build_events``).
    One kernel launch covers the whole S x K instance grid. Returns dict of
    ``chosen``/``p_chosen``/``expected_cost`` (S, K, J) and final sampling
    ``weights`` (S, K, P). ``block_jobs`` is rounded up to 128 lanes.
    """
    if interpret is None:
        interpret = default_interpret()
    C = np.asarray(C, dtype=np.float32)
    S, J, P = C.shape
    etas = np.atleast_2d(np.asarray(etas, dtype=np.float32))
    K = etas.shape[0]
    BJ = -(-block_jobs // _LANES) * _LANES
    Jp = -(-J // BJ) * BJ
    Pp = -(-P // _LANES) * _LANES

    C_p = np.zeros((S, Jp, Pp), dtype=np.float32)
    C_p[:, :J, :P] = C
    eta_p = np.zeros((K, 1, Jp), dtype=np.float32)
    eta_p[:, 0, :J] = etas
    u_p = np.full((S, 1, Jp), 2.0, dtype=np.float32)
    u_p[:, 0, :J] = np.asarray(u, dtype=np.float32)
    nd_p = np.zeros((1, Jp), dtype=np.int32)
    nd_p[0, :J] = np.asarray(n_done, dtype=np.int32)

    ch, ps, ec, wf = _hedge_call(C_p, eta_p, u_p, nd_p, K=K,
                                 W=ring_rows(n_done, BJ), Pp=Pp, m=P, BJ=BJ,
                                 interpret=interpret)

    logw = np.asarray(wf, dtype=np.float64).reshape(S, K, Pp)[..., :P]
    w = np.exp(logw - logw.max(axis=-1, keepdims=True))
    w /= w.sum(axis=-1, keepdims=True)
    return {
        "chosen": np.asarray(ch, np.int64).reshape(S, K, Jp)[..., :J],
        "p_chosen": np.asarray(ps, np.float64).reshape(S, K, Jp)[..., :J],
        "expected_cost": np.asarray(ec, np.float64).reshape(S, K, Jp)[..., :J],
        "weights": w,
    }
