"""Reward-diversity demo (paper §2.4.1): score the same rollout batch with
all three reward paradigms — rule-based (Eq. 1), model-judge (Eq. 2, a judge
LM running on the serving engine, the QwQ-32B role), and tool-verify (Eq. 3)
— then with their weighted composition.

    PYTHONPATH=src python examples/judge_and_verify_rewards.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core import (ModelJudgeReward, RewardComposer, RolloutConfig,
                        RolloutWorker, RuleReward, ToolVerifyReward)
from repro.data.tokenizer import default_tokenizer
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.serving.engine import GenerationEngine
from repro.tools.search_env import SearchEnv


def main():
    cfg = get_config("tiny")
    model = Model(cfg)
    tok = default_tokenizer(cfg.vocab_size)
    env = SearchEnv(n_entities=40, seed=0)
    params = model.init(jax.random.PRNGKey(0))

    engine = GenerationEngine(model, params, pad_id=tok.pad_id,
                              stop_ids=(tok.eos_id,), max_len=512)
    worker = RolloutWorker(engine, env, tok,
                           RolloutConfig(max_turns=2, max_new_tokens=24,
                                         group_size=2))
    tasks = env.sample_tasks(3, seed=2)
    trajs = worker.rollout(tasks, jax.random.PRNGKey(1))
    gts = [t.meta["ground_truth"] for t in trajs]

    # a separate judge model (here: same tiny arch, different init) served
    # through its own engine — the dedicated reward-rollout worker group
    judge_params = model.init(jax.random.PRNGKey(42))
    judge_engine = GenerationEngine(model, judge_params, pad_id=tok.pad_id,
                                    stop_ids=(tok.eos_id,), max_len=768)

    rule = RuleReward(env)
    judge = ModelJudgeReward(judge_engine, tok, max_judge_tokens=8)
    verify = ToolVerifyReward(env, tok)

    r_rule = rule(trajs, gts)
    r_judge = judge(trajs, gts)
    r_verify = verify(trajs, gts)
    composer = RewardComposer([(rule, 0.6), (judge, 0.2), (verify, 0.2)])
    r_total = composer(trajs, gts)

    print(f"{'trajectory':>10} {'rule':>8} {'judge':>8} {'verify':>8} {'composed':>9}")
    for i in range(len(trajs)):
        print(f"{i:>10} {r_rule[i]:>8.3f} {r_judge[i]:>8.3f} "
              f"{r_verify[i]:>8.3f} {r_total[i]:>9.3f}")
    print("\nreward breakdowns are stored on each trajectory:")
    print(f"  traj0: {trajs[0].reward_breakdown}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
