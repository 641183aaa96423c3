"""Pallas TPU kernels for the perf-critical compute layers:

  flash_attention — blockwise online-softmax attention (GQA, causal,
                    sliding window + meta-token prefix)
  ssd_scan        — Mamba-2 SSD chunked scan (grid-carried chunk state)
  policy_cost     — the paper's TOLA scoring hot loop (batched closed-form
                    task-cost evaluation over the market's cumulative
                    arrays); policy_cost_chain extends it to whole
                    (scenario x policy x job) grids — one launch per bid,
                    chain recurrence in-kernel (repro.engine's fast path)
  weight_update   — the online-learning hot loop (repro.learn's pallas
                    path): fused Hedge replay — in-VMEM weight-trajectory
                    pass + one-hot-matmul sample gather, one launch per
                    (scenario x learner x schedule-grid) sweep

Each kernel has a pure-jnp oracle in ref.py (structurally different
algorithm) and a jit'd wrapper in ops.py; validated in interpret mode on CPU.
"""

from repro.kernels.ops import flash_attention, policy_cost_batch, ssd

__all__ = ["flash_attention", "ssd", "policy_cost_batch", "default_interpret"]


def default_interpret() -> bool:
    """Pallas interpret mode for the engine's and the learner's kernel
    launches: on exactly when JAX runs on the CPU."""
    import jax

    return jax.default_backend() == "cpu"
