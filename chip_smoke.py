"""Chip smoke test: the rollout-and-learn loop on one TPU at Qwen3-4B widths.

    python chip_smoke.py [--seed N]

Runs in one process, on the system's own entry points, and fails (non-zero
exit) on any error:

1. Rollout at full width and full depth.  ``qwen3-4b`` (36 layers, bf16
   weights from ``--seed``) behind a paged ``GenerationEngine`` (8 slots,
   max_len 2048, a pool of 8 x 2048 tokens), driven by the continuous
   scheduler through ``RolloutWorker`` over ``SearchEnv`` tasks: 2 tasks x
   GRPO group 4, two turns each, so prefix sharing, copy-on-write, tool
   calls and park/resume all run.  The engine is then checked against a
   plain float32 forward pass with no cache: greedy decode from two
   prompts, last-position logits after the prefill and after the decode.
2. Trainer steps at published widths with the depth cut to fit one chip:
   ``RLTrainer.train_iteration`` on ``qwen3-4b`` with 2 layers (f32
   params, AdamW), its rollouts on the paged engine.  The rule reward plus
   the policy's mean sampled logprob, which gives random weights nonzero
   GRPO advantages.

The script fails unless JAX runs on a TPU and the compiled decode program
holds the Pallas paged-attention kernel.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
It has no multi-chip option: no path of the system spans chips yet.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (GRPOConfig, RewardComposer, RLTrainer,  # noqa: E402
                        RolloutConfig, RolloutWorker, RuleReward,
                        TrainerConfig)
from repro.core.rewards import RewardFn  # noqa: E402
from repro.data.tokenizer import default_tokenizer  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.serving.engine import GenerationEngine  # noqa: E402
from repro.tools.manager import Qwen3ToolManager  # noqa: E402
from repro.tools.registry import ToolCall  # noqa: E402
from repro.tools.search_env import SearchEnv  # noqa: E402

ARCH = "qwen3-4b"
SLOTS = 8                    # decode-batch rows: 2 tasks x GRPO group 4
TASKS, GROUP = 2, 4
MAX_LEN = 2048               # phase 1 context; the pool holds SLOTS rows of it
PAGE = 16
MAX_NEW = 64                 # tokens per model turn
MAX_TURNS = 2
DECODE_CHECK = 16            # greedy tokens decoded for the reference check
# phase 2 cuts, sized by compiling the learner step for a described v5e:
# 2 layers (0.59B params) with an (8, 512) token batch need 11.5 GB of the
# chip's 16 GB; the check below repeats that sum on the chip itself
TRAIN_LAYERS = 2
TRAIN_SEQ = 512
TRAIN_ITERS = 3
HEADROOM = 2e9               # bytes the learner step must leave free


class ScriptedSearchManager(Qwen3ToolManager):
    """Random weights never write a tool call.  A turn that parses to
    neither a call nor an answer is read as one ``search`` call on the
    task's entity, so every trajectory parks on a tool and resumes with the
    observation prefilled: the paths a trained policy would take."""

    def parse_response(self, text):
        calls, answer = super().parse_response(text)
        if calls or answer is not None:
            return calls, answer
        return [ToolCall("search", {"query": "capital"}, 0)], None


class SampledLogprobReward(RewardFn):
    """Mean sampled logprob of a trajectory's model tokens.  Under random
    weights every trajectory earns the same rule reward, which gives GRPO
    zero advantages and the learner a zero gradient; this reward differs
    from one sample to the next, so each trainer step carries a real one."""
    name = "sampled_logprob"
    streaming_safe = True

    def __call__(self, trajs, ground_truths):
        out = np.zeros((len(trajs),), np.float32)
        for i, tr in enumerate(trajs):
            lp = np.asarray(tr.meta["logprobs"], np.float32)
            lp = lp[lp < 0.0]        # prompt and observation entries are 0
            out[i] = lp.mean() if lp.size else 0.0
        return out


def search_env(seed: int) -> SearchEnv:
    env = SearchEnv(n_entities=200, seed=seed, max_tool_calls=MAX_TURNS)
    env.manager = ScriptedSearchManager(env.registry, compact=True)
    return env


def pick_tasks(env: SearchEnv, tok, seed: int):
    """The first task draw from ``seed`` on whose prompts all end mid-page:
    a GRPO group then shares a partial tail block, which its first decoded
    token must copy on write."""
    for s in range(seed, seed + 1000):
        tasks = env.sample_tasks(TASKS, seed=s)
        if all(len(tok.encode(env.manager.get_prompt(q), add_bos=True)) % PAGE
               for q, _ in tasks):
            return tasks
    raise RuntimeError("no task draw with prompts that end mid-page")


def log(msg: str) -> None:
    print(msg, flush=True)


def device_memory() -> dict:
    return jax.devices()[0].memory_stats() or {}


def bf16_params(model: Model, seed: int):
    """Random weights from ``seed``, cast leaf by leaf inside one program so
    the f32 draws of a 4B model never sit in device memory at once."""
    init = jax.jit(lambda k: jax.tree.map(lambda p: p.astype(jnp.bfloat16),
                                          model.init(k)))
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def paged_engine(model, params, tok, max_len: int) -> GenerationEngine:
    return GenerationEngine(model, params, pad_id=tok.pad_id,
                            stop_ids=(tok.eos_id,), max_len=max_len,
                            temperature=1.0, cache_mode="paged",
                            page_size=PAGE,
                            num_blocks=SLOTS * max_len // PAGE)


def rollout_config() -> RolloutConfig:
    return RolloutConfig(max_turns=MAX_TURNS, max_new_tokens=MAX_NEW,
                         temperature=1.0, group_size=GROUP, n_slots=SLOTS)


def decode_hlo(engine: GenerationEngine, session, n: int) -> str:
    """StableHLO of the fused decode loop for this session, lowered with the
    arguments ``GenerationEngine.generate`` passes (lowering runs nothing)."""
    B = session.batch
    stop = jnp.asarray(np.asarray(engine.stop_ids, np.int32))
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    return engine._loop_jit.lower(
        engine.params, session.cache, session.last_logits,
        jnp.asarray(session.lengths, jnp.int32), jnp.asarray(session.stopped),
        None, keys, jnp.int32(n), jnp.float32(0.0), stop, None,
        jnp.zeros((B,), jnp.int32), jnp.full((B,), n, jnp.int32),
        T=n, per_row=True).as_text()


def reference_check(model, params, engine, tok, prompts) -> dict:
    """Engine (paged cache, bf16, the kernel in decode) against a plain
    float32 forward pass with no cache, on the last-position logits after
    the prompt prefill and after DECODE_CHECK greedy tokens.

    The f32 pass runs the model with float32 activations on the same
    weights: each bf16 weight is cast to f32 where the layer uses it, which
    is exactly the f32 cast of the params, one layer at a time.  The
    tolerance is twice what bf16 alone costs: the plain bf16 forward pass's
    own maximum difference from the f32 pass on the same positions."""
    session = engine.start([list(p) for p in prompts])
    hlo = decode_hlo(engine, session, DECODE_CHECK)
    prefill_logits = np.asarray(session.last_logits, np.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), len(prompts))
    res = engine.generate(session, DECODE_CHECK, temperature=0.0,
                          row_keys=keys)
    decode_logits = np.asarray(session.last_logits, np.float32)
    seqs = [list(p) + t for p, t in zip(prompts, res.token_lists())]
    del session

    L = max(len(s) for s in seqs)
    toks = np.full((len(seqs), L), tok.pad_id, np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s        # right pads: causal, never attended
    rows = np.arange(len(seqs))
    at_prompt = np.array([len(p) - 1 for p in prompts])
    at_end = np.array([len(s) - 1 for s in seqs])

    def last_logits(m: Model):
        fwd = jax.jit(lambda p, t: m.apply(p, {"tokens": t})[0])
        out = np.asarray(fwd(params, jnp.asarray(toks)), np.float32)
        return out[rows, at_prompt], out[rows, at_end]

    f32_pre, f32_dec = last_logits(Model(dataclasses.replace(model.cfg,
                                                             dtype="float32")))
    bf_pre, bf_dec = last_logits(model)
    engine_err = max(np.abs(prefill_logits - f32_pre).max(),
                     np.abs(decode_logits - f32_dec).max())
    bf16_err = max(np.abs(bf_pre - f32_pre).max(),
                   np.abs(bf_dec - f32_dec).max())
    return {"engine_err": float(engine_err), "bf16_err": float(bf16_err),
            "tol": float(2 * bf16_err),
            "finite": bool(np.isfinite(prefill_logits).all()
                           and np.isfinite(decode_logits).all()),
            "kernel_in_hlo": "tpu_custom_call" in hlo,
            "decoded": int(res.counts.sum())}


def phase_rollout(seed: int) -> None:
    log(f"== phase 1: rollout, {ARCH} at full width and depth")
    cfg = get_config(ARCH)
    model = Model(cfg)
    tok = default_tokenizer(cfg.vocab_size)
    t0 = time.monotonic()
    params = bf16_params(model, seed)
    log(f"params: {model.n_params():,} ({cfg.n_layers} layers, bf16), "
        f"init {time.monotonic() - t0:.1f} s")
    engine = paged_engine(model, params, tok, MAX_LEN)
    log(f"engine: paged, page {PAGE}, {SLOTS} slots x {MAX_LEN} tokens, "
        f"engine._use_paged_kernel={engine._use_paged_kernel}")
    if not engine._use_paged_kernel:
        raise RuntimeError("the paged engine did not select the kernel")
    env = search_env(seed)
    worker = RolloutWorker(engine, env, tok, rollout_config())
    tasks = pick_tasks(env, tok, seed)

    times = []
    for label in ("cold", "warm"):
        t0 = time.monotonic()
        trajs = worker.rollout(tasks, jax.random.PRNGKey(seed))
        times.append(time.monotonic() - t0)
        st = worker.last_stats
        log(f"rollout ({label}): {times[-1]:.2f} s, "
            f"{len(trajs)} trajectories, "
            f"{sum(len(t.model_tokens()) for t in trajs)} model tokens, "
            f"{sum(t.n_tool_calls for t in trajs)} tool calls, "
            f"prefix_hit_rate={st.get('prefix_hit_rate', 0):.3f}, "
            f"cow_count={st.get('cow_count', 0):.0f}, "
            f"rounds={st.get('rounds', 0):.0f}")
    log(f"phase 1 compile s (cold - warm rollout): {times[0] - times[1]:.2f}; "
        f"run s (warm rollout): {times[1]:.2f}")
    if len(trajs) != TASKS * GROUP:
        raise RuntimeError(f"{len(trajs)} trajectories, "
                           f"expected {TASKS * GROUP}")
    if not all(t.n_tool_calls >= 1 for t in trajs):
        raise RuntimeError("a trajectory made no tool call")
    if not (st.get("prefix_hit_rate", 0) > 0 and st.get("cow_count", 0) > 0):
        raise RuntimeError(f"prefix sharing did not run: {st}")

    prompts = [tok.encode(env.manager.get_prompt(q), add_bos=True)
               for q, _ in tasks]
    chk = reference_check(model, params, engine, tok, prompts)
    log(f"decode program holds the kernel (tpu_custom_call): "
        f"{chk['kernel_in_hlo']}")
    log(f"reference check: {chk['decoded']} greedy tokens; max |engine - "
        f"f32| = {chk['engine_err']:.6g}; max |plain bf16 - f32| = "
        f"{chk['bf16_err']:.6g}; tolerance = {chk['tol']:.6g}")
    mem = device_memory()
    log(f"phase 1 peak_bytes_in_use: {mem.get('peak_bytes_in_use', 0):,} "
        f"of bytes_limit {mem.get('bytes_limit', 0):,}")
    if not chk["kernel_in_hlo"]:
        raise RuntimeError("the decode program does not hold the kernel")
    if not chk["finite"] or not chk["engine_err"] <= chk["tol"]:
        raise RuntimeError("engine logits differ from the f32 reference "
                           "beyond the tolerance")


def phase_train(seed: int) -> None:
    cfg = dataclasses.replace(get_config(ARCH), n_layers=TRAIN_LAYERS)
    log(f"== phase 2: trainer, {ARCH} widths, depth cut to {TRAIN_LAYERS} "
        f"layers, token batch cut to {TASKS * GROUP} x {TRAIN_SEQ}")
    model = Model(cfg)
    tok = default_tokenizer(cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed))
    log(f"params: {model.n_params():,} (f32)")
    env = search_env(seed)
    engine = paged_engine(model, params, tok, TRAIN_SEQ)
    trainer = RLTrainer(
        model, params, env, tok,
        RewardComposer([(RuleReward(env), 1.0),
                        (SampledLogprobReward(), 1.0)]),
        TrainerConfig(n_tasks_per_iter=TASKS, group_size=GROUP,
                      max_seq_len=TRAIN_SEQ),
        rollout_config(), GRPOConfig(kl_coef=0.0), AdamWConfig(lr=1e-6),
        engine=engine)

    # the learner step at its largest token batch, compiled before it runs
    B, L = TASKS * GROUP, TRAIN_SEQ
    f32 = jax.ShapeDtypeStruct((B, L), jnp.float32)
    batch = {"tokens": jax.ShapeDtypeStruct((B, L), jnp.int32),
             "loss_mask": f32, "old_logprobs": f32, "staleness": f32,
             "ref_logprobs": f32,
             "advantages": jax.ShapeDtypeStruct((B,), jnp.float32)}
    t0 = time.monotonic()
    ma = trainer.learner._train_step.lower(
        trainer.params, trainer.opt_state, batch).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    limit = device_memory().get("bytes_limit", 0)
    log(f"learner step ({B} x {L}): compile {time.monotonic() - t0:.1f} s, "
        f"needs {need:,} B of {limit:,}; headroom {limit - need:,} B")
    if limit - need < HEADROOM:
        raise RuntimeError("the learner step leaves under 2 GB free")

    times = []
    for i in range(TRAIN_ITERS):
        t0 = time.monotonic()
        out = trainer.train_iteration(jax.random.PRNGKey(seed + i))
        times.append(time.monotonic() - t0)
        log(f"iteration {out['step']}: {times[-1]:.2f} s, "
            f"loss={out['loss']:.6g} grad_norm={out['grad_norm']:.6g} "
            f"model_tokens={out['model_tokens']} "
            f"rollout_s={out['rollout_s']:.2f} train_s={out['train_s']:.2f}")
        if not (np.isfinite(out["loss"]) and np.isfinite(out["grad_norm"])):
            raise RuntimeError("non-finite loss or grad norm")
    log(f"phase 2 compile s (first - fastest iteration): "
        f"{times[0] - min(times[1:]):.2f}; run s (fastest iteration): "
        f"{min(times[1:]):.2f}")
    mem = device_memory()
    log(f"phase 2 peak_bytes_in_use (whole process): "
        f"{mem.get('peak_bytes_in_use', 0):,}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    if dev.platform != "tpu":
        print(f"no TPU: JAX runs on {dev.platform}", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    phase_rollout(args.seed)
    gc.collect()
    log(f"after phase 1: bytes_in_use {device_memory().get('bytes_in_use'):,}")
    phase_train(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
