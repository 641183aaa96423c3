"""Batched online-learning subsystem (DESIGN.md §7).

Separates *learner* from *replay engine*: ``learners.py`` defines the
state/update interface (Hedge = the paper's Alg. 4, EXP3, UCB1,
epsilon-greedy, follow-the-leader, each with pluggable eta/exploration
schedules); ``replay.py`` runs the sequential sample -> observe -> reweight
recurrence over the evaluation engine's (scenarios x jobs x policies) cost
tensor — sequential float64 numpy as the exact oracle, one ``jax.lax.scan``
per learner kind vmapped across scenarios x schedule-grid instances, or the
fused Pallas weight-update kernel; ``regret.py`` turns the sampled traces
into realized/expected regret curves with per-scenario confidence bands.

    from repro.engine import evaluate_grid
    from repro.learn import replay
    res = evaluate_grid(jobs, policies, markets, r)
    lr = replay(res, arrivals, d, learners=["hedge", "exp3"], backend="jax")
    lr.regret_curve()     # (S, K, J) running regret per learner

``repro.core.tola.run_tola_scenarios`` delegates its Alg. 4 loop to the
numpy oracle here, bit-compatibly with the pre-subsystem implementation.
"""

from repro.learn.learners import (
    FULL_INFO_KINDS,
    LEARNER_KINDS,
    LearnerSpec,
    Schedule,
    as_spec,
)
from repro.learn.regret import LearnResult, StreamLearnResult, prop_b1_bound
from repro.learn.replay import (
    available_backends,
    build_events,
    replay,
    replay_stream,
    resolve_backend,
)

__all__ = [
    "LEARNER_KINDS", "FULL_INFO_KINDS", "LearnerSpec", "Schedule", "as_spec",
    "LearnResult", "StreamLearnResult", "prop_b1_bound",
    "replay", "replay_stream", "build_events", "available_backends",
    "resolve_backend",
]
