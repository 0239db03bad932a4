"""Serving example: batched multi-turn tool-agent inference (no training).

Loads (or initializes) a policy, serves a batch of questions through the
Generate-Parse-Invoke-Update loop with greedy decoding, and prints the
answers with per-stage timing — the inference-side counterpart of the
trainer (vLLM-worker analogue).

    PYTHONPATH=src python examples/serve_tools_agent.py [--ckpt path]
"""
import argparse
import time

import jax

from repro.configs import get_config
from repro.core import RolloutConfig, RolloutWorker
from repro.data.tokenizer import default_tokenizer
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.serving.engine import GenerationEngine
from repro.tools.search_env import SearchEnv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    model = Model(cfg)
    tok = default_tokenizer(cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt:
        from repro.checkpoint.checkpointer import load_checkpoint
        params, _, step, _ = load_checkpoint(args.ckpt, params)
        print(f"restored checkpoint at step {step}")

    env = SearchEnv(n_entities=80, seed=0, latency_s=0.05, latency_jitter=0.02)
    engine = GenerationEngine(model, params, pad_id=tok.pad_id,
                              stop_ids=(tok.eos_id,), max_len=512,
                              temperature=0.0)
    worker = RolloutWorker(engine, env, tok,
                           RolloutConfig(max_turns=3, max_new_tokens=48,
                                         temperature=0.0, group_size=1))

    tasks = env.sample_tasks(args.batch, split="test", seed=7)
    t0 = time.time()
    trajs = worker.rollout(tasks, jax.random.PRNGKey(0), group_size=1)
    dt = time.time() - t0

    n_tokens = sum(len(t.model_tokens()) for t in trajs)
    print(f"\nserved {len(trajs)} requests in {dt:.1f}s "
          f"({n_tokens/dt:.1f} model-tok/s, "
          f"async tool overlap {worker.executor.overlap_factor:.1f}x)\n")
    for t in trajs:
        _, answer = env.manager.parse_response(tok.decode(t.model_tokens()))
        print(f"Q: {t.meta['question']}")
        print(f"A: {answer!r}  (truth: {t.meta['ground_truth']!r}, "
              f"tool calls: {t.n_tool_calls})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
