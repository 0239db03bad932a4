"""End-to-end driver (paper §3 reproduced in kind): train a search agent with
GRPO on the synthetic Search-R1 env.

Stages:
  1. behaviour-cloning warmup on scripted expert trajectories (plays the role
     of the pretrained/instruction-tuned Qwen3 base, which lets the paper
     skip SFT);
  2. GRPO with asynchronous multi-turn tool rollouts;
  3. held-out evaluation (exact match) before/after RL.

    PYTHONPATH=src python examples/train_search_agent.py \
        [--arch search-r1-100m] [--iters 60] [--sft-steps 150]

Defaults use a ~5M model so the demo finishes on 1 CPU core; pass
``--arch search-r1-100m`` for the 100M-parameter configuration.
"""
import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config, register, ModelConfig
from repro.core import (GRPOConfig, RewardComposer, RolloutConfig, RuleReward,
                        RLTrainer, TrainerConfig)
from repro.core.mdp import to_training_batch
from repro.core.sft import make_expert_trajectories, make_sft_train_step
from repro.data.tokenizer import default_tokenizer
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.tools.search_env import SearchEnv

DEMO = register(ModelConfig(
    arch_id="search-agent-demo", family="dense", n_layers=4, d_model=256,
    n_heads=8, n_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=4096,
    qk_norm=True, rope_theta=1e4, dtype="float32", tie_embeddings=True,
    remat=False))


def sft_stage(model, params, env, tok, steps, batch_size, lr, seed=0):
    step_fn = jax.jit(make_sft_train_step(model, AdamWConfig(lr=lr)))
    opt = adamw_init(params)
    trajs = make_expert_trajectories(env, tok, n=steps * batch_size, seed=seed)
    L = 256
    for i in range(steps):
        chunk = trajs[i * batch_size:(i + 1) * batch_size]
        b = to_training_batch(chunk, L, tok.pad_id)
        toks = np.full((batch_size, L), tok.pad_id, np.int32)
        mask = np.zeros((batch_size, L), np.float32)
        toks[:, :b["tokens"].shape[1]] = b["tokens"]
        mask[:, :b["loss_mask"].shape[1]] = b["loss_mask"]
        params, opt, m = step_fn(params, opt,
                                 {"tokens": toks, "loss_mask": mask})
        if (i + 1) % 25 == 0:
            print(f"  sft step {i+1}/{steps} loss={float(m['loss']):.3f}")
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="search-agent-demo")
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--sft-steps", type=int, default=150)
    ap.add_argument("--sft-batch", type=int, default=8)
    ap.add_argument("--tasks-per-iter", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--eval-tasks", type=int, default=32)
    ap.add_argument("--out", default="results/train/search_agent.json")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    model = Model(cfg)
    tok = default_tokenizer(cfg.vocab_size)
    env = SearchEnv(n_entities=120, seed=0)
    params = model.init(jax.random.PRNGKey(0))
    print(f"model {cfg.arch_id}: {model.n_params()/1e6:.1f}M params")

    print("[1/3] behaviour-cloning warmup ...")
    t0 = time.time()
    params = sft_stage(model, params, env, tok, args.sft_steps,
                       args.sft_batch, lr=3e-3)
    print(f"  sft done in {time.time()-t0:.0f}s")

    trainer = RLTrainer(
        model, params, env, tok, RewardComposer([(RuleReward(env), 1.0)]),
        TrainerConfig(n_tasks_per_iter=args.tasks_per_iter,
                      group_size=args.group_size, max_seq_len=384,
                      log_path="results/train/search_agent_log.jsonl"),
        RolloutConfig(max_turns=3, max_new_tokens=48, temperature=0.8,
                      group_size=args.group_size),
        GRPOConfig(kl_coef=0.0), AdamWConfig(lr=3e-4))

    print("[2/3] evaluating SFT policy (pre-RL) ...")
    pre = trainer.evaluate(n_tasks=args.eval_tasks)
    print(f"  pre-RL: {pre}")

    print(f"[3/3] GRPO for {args.iters} iterations ...")
    curve = []
    for i in range(args.iters):
        out = trainer.train_iteration(jax.random.PRNGKey(1000 + i))
        curve.append({k: out[k] for k in
                      ("step", "reward_mean", "exact_match", "finished_frac",
                       "tool_calls_mean", "rollout_s", "train_s")})
        if (i + 1) % 10 == 0:
            print(f"  iter {i+1}: reward={out['reward_mean']:.3f} "
                  f"em={out['exact_match']:.2f} "
                  f"tools={out['tool_calls_mean']:.1f}")
    post = trainer.evaluate(n_tasks=args.eval_tasks)
    print(f"post-RL: {post}")

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"pre_rl": pre, "post_rl": post, "curve": curve,
                   "arch": args.arch}, f, indent=1)
    print(f"wrote {args.out}")
    print(f"test score: {pre['test_score']:.3f} -> {post['test_score']:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
