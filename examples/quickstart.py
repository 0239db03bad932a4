"""Quickstart: build a tool env, roll out a multi-turn trajectory batch, and
take one GRPO step.  (~1 min on CPU.)

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core import (GRPOConfig, RewardComposer, RolloutConfig,
                        RolloutWorker, RuleReward, grpo_advantages,
                        make_grpo_train_step)
from repro.core.mdp import to_training_batch
from repro.data.tokenizer import default_tokenizer
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serving.engine import GenerationEngine
from repro.tools.search_env import SearchEnv


def main():
    # 1. model + tokenizer
    cfg = get_config("tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = default_tokenizer(cfg.vocab_size)
    print(f"model: {cfg.arch_id}, {model.n_params()/1e6:.1f}M params")

    # 2. tool environment (MCP-style registry + Qwen3 tool manager)
    env = SearchEnv(n_entities=50, seed=0)
    print(f"tools: {env.registry.names()}")

    # 3. rollout: Generate -> Parse -> Invoke -> Update
    engine = GenerationEngine(model, params, pad_id=tok.pad_id,
                              stop_ids=(tok.eos_id,), max_len=512)
    worker = RolloutWorker(engine, env, tok,
                           RolloutConfig(max_turns=3, max_new_tokens=32,
                                         group_size=4))
    tasks = env.sample_tasks(2, seed=1)
    trajs = worker.rollout(tasks, jax.random.PRNGKey(1))
    print(f"rolled out {len(trajs)} trajectories "
          f"(lengths {[len(t) for t in trajs]})")

    # 4. rewards (rule-based, Eq. 1) + GRPO advantages
    rewards = RewardComposer([(RuleReward(env), 1.0)])(
        trajs, [t.meta["ground_truth"] for t in trajs])
    adv = grpo_advantages(rewards, [t.group_id for t in trajs])
    print(f"rewards: {np.round(rewards, 3)}")

    # 5. one GRPO update on loss-masked trajectories
    batch_np = to_training_batch(
        trajs, 512, tok.pad_id,
        old_logprobs=[np.array(t.meta["logprobs"], np.float32) for t in trajs])
    batch = {
        "tokens": batch_np["tokens"],
        "loss_mask": batch_np["loss_mask"],
        "old_logprobs": batch_np["old_logprobs"],
        "advantages": adv,
        "ref_logprobs": np.zeros_like(batch_np["old_logprobs"]),
    }
    step = jax.jit(make_grpo_train_step(model, AdamWConfig(lr=1e-4),
                                        GRPOConfig(kl_coef=0.0)))
    params, _, metrics = step(params, adamw_init(params), batch)
    print(f"GRPO step: loss={float(metrics['loss']):.4f} "
          f"grad_norm={float(metrics['grad_norm']):.3f}")
    print("done.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
