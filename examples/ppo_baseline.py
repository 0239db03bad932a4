"""PPO baseline (the veRL-native algorithm RLFactory builds on): train the
tool agent with PPO + value head instead of GRPO, on the same env — the
paper's Search-R1 comparisons are GRPO-based; this demonstrates the framework
supports both.

    PYTHONPATH=src python examples/ppo_baseline.py [--iters 20]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import RolloutConfig, RolloutWorker, RuleReward
from repro.core.mdp import to_training_batch
from repro.core.ppo import (PPOConfig, init_ppo_params, make_ppo_train_step,
                            value_head_apply)
from repro.data.tokenizer import default_tokenizer
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serving.engine import GenerationEngine
from repro.tools.search_env import SearchEnv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=12)
    args = ap.parse_args()

    cfg = get_config("tiny")
    model = Model(cfg)
    tok = default_tokenizer(cfg.vocab_size)
    env = SearchEnv(n_entities=60, seed=0)
    params = init_ppo_params(model, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_ppo_train_step(model, AdamWConfig(lr=5e-4),
                                       PPOConfig()))
    rule = RuleReward(env)
    L = 384

    for it in range(args.iters):
        engine = GenerationEngine(model, params["lm"], pad_id=tok.pad_id,
                                  stop_ids=(tok.eos_id,), max_len=L,
                                  temperature=1.0)
        worker = RolloutWorker(engine, env, tok,
                               RolloutConfig(max_turns=2, max_new_tokens=32,
                                             group_size=2))
        tasks = env.sample_tasks(4, seed=it)
        trajs = worker.rollout(tasks, jax.random.PRNGKey(100 + it))
        gts = [t.meta["ground_truth"] for t in trajs]
        rewards = rule(trajs, gts)

        b = to_training_batch(trajs, L, tok.pad_id,
                              old_logprobs=[np.array(t.meta["logprobs"],
                                                     np.float32)
                                            for t in trajs])
        toks = np.full((len(trajs), L), tok.pad_id, np.int32)
        mask = np.zeros((len(trajs), L), np.float32)
        olp = np.zeros((len(trajs), L), np.float32)
        n = b["tokens"].shape[1]
        toks[:, :n], mask[:, :n], olp[:, :n] = (b["tokens"], b["loss_mask"],
                                                b["old_logprobs"])
        # old values from the current critic (one forward)
        _, _, _, hidden = T.lm_apply(params["lm"], cfg, jnp.asarray(toks),
                                     return_hidden=True)
        old_values = np.asarray(value_head_apply(params["value"], hidden))
        batch = {"tokens": toks, "loss_mask": mask, "old_logprobs": olp,
                 "old_values": old_values, "rewards": rewards}
        params, opt, m = step(params, opt, batch)
        print(f"iter {it}: reward={rewards.mean():.3f} "
              f"pg={float(m['pg_loss']):.4f} v={float(m['v_loss']):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
