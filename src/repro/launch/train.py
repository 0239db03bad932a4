"""Production training launcher.

Wires configs + mesh + sharded GRPO train step into a runnable driver:

  PYTHONPATH=src python -m repro.launch.train --arch search-r1-100m \
      --iters 50                 # local CPU RL training (real rollouts)

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b \
      --dry-run [--multi-pod]    # production-mesh lower/compile path

On real TPU pods the same entry point runs with the production mesh; on this
CPU container the production path is exercised via --dry-run (512 forced host
devices live only in launch/dryrun.py).
"""
from __future__ import annotations

import argparse

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="search-r1-100m")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    args = ap.parse_args()

    if args.dry_run:
        # delegate to the dry-run module (it must own process start-up because
        # of XLA_FLAGS device forcing); it pins itself to the CPU, so this
        # parent, which has not touched JAX, leaves any chip alone
        import os
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", args.shape]
        if args.multi_pod:
            cmd.append("--multi-pod")
        raise SystemExit(subprocess.call(cmd, env=dict(os.environ)))

    from repro.configs import get_config
    from repro.core import (GRPOConfig, RewardComposer, RolloutConfig,
                            RuleReward, RLTrainer, TrainerConfig)
    from repro.data.tokenizer import default_tokenizer
    from repro.launch.cache import enable_compile_cache
    from repro.models import Model
    from repro.optim.adamw import AdamWConfig
    from repro.tools.search_env import SearchEnv

    enable_compile_cache()
    cfg = get_config(args.arch)
    model = Model(cfg)
    tok = default_tokenizer(cfg.vocab_size)
    env = SearchEnv(n_entities=120, seed=0)
    params = model.init(jax.random.PRNGKey(0))
    trainer = RLTrainer(
        model, params, env, tok, RewardComposer([(RuleReward(env), 1.0)]),
        TrainerConfig(n_tasks_per_iter=4, group_size=4, max_seq_len=384,
                      checkpoint_every=args.checkpoint_every,
                      log_path="results/train/launch_log.jsonl"),
        RolloutConfig(max_turns=3, max_new_tokens=48, temperature=0.8,
                      group_size=4),
        GRPOConfig(kl_coef=0.0), AdamWConfig(lr=3e-4))
    for i in range(args.iters):
        out = trainer.train_iteration(jax.random.PRNGKey(i))
        print(f"iter {out['step']}: reward={out['reward_mean']:.3f} "
              f"loss={out['loss']:.4f} tok/s={out['throughput_tok_s']:.0f}")


if __name__ == "__main__":
    main()
