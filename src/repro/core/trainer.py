"""RLFactory trainer — disaggregated rollout producer / learner consumer.

One iteration (paper Fig. 4):
  1. sample tasks; the :class:`RolloutProducer` drives the engine through the
     Generate-Parse-Invoke-Update loop — by default the continuous-batching
     scheduler's trajectory stream (decode overlaps tool I/O; finished rows
     retire and their slots refill from the task queue), whose
     slot-occupancy/overlap stats are logged under ``rollout/*`` alongside
     the per-reason ``stop/*`` episode-termination distribution;
  2. score trajectories with the configured reward composer (rule / judge /
     verify, §2.4.1) — streaming-safe composers score each trajectory the
     moment it retires, pipelining rewards with decoding;
  3. group-normalize advantages (GRPO);
  4. recompute reference logprobs (frozen policy) if KL is enabled;
  5. the :class:`Learner` runs the clipped-surrogate update on loss-masked
     tokens (observation tokens are excluded — §2.2);
  6. refreshed params are published back into the engine's
     :class:`~repro.serving.engine.WeightStore`.

Two handoff disciplines connect the halves (``TrainerConfig.mode``):

* ``mode="sync"`` — the parity oracle: the learner waits for the whole
  rollout, runs one update over all trajectories, and the refreshed weights
  swap in before the next iteration.  Token-for-token the seed behavior.
* ``mode="async"`` — in-flight refresh: the learner consumes *complete GRPO
  groups* off the trajectory stream as they retire and publishes refreshed
  params every ``refresh_groups`` groups; the producer swaps them in at its
  next decode-round boundary (never mid-round).  Trajectories that straddle
  a publish carry mixed per-token ``policy_versions``; the loss corrects
  with importance ratios against the *recorded* sampling logprobs and logs
  the staleness distribution (``train/staleness_*``, clip_frac split by
  freshness).  Because the learner runs between scheduler rounds while tool
  futures fly on the executor's background loop, learner compute overlaps
  tool I/O (``train/learner_overlap_s``).

Sequence lengths are bucketed so the jitted train step recompiles O(log) times.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.grpo import (GRPOConfig, grpo_advantages, make_grpo_train_step,
                             token_logprobs)
from repro.core.mdp import STOP_REASONS, to_training_batch
from repro.core.rollout import RolloutConfig, RolloutWorker
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serving.engine import GenerationEngine


def _bucket_len(n: int, step: int = 64) -> int:
    return max(step, ((n + step - 1) // step) * step)


@dataclasses.dataclass
class TrainerConfig:
    n_tasks_per_iter: int = 8
    group_size: int = 4
    max_seq_len: int = 512
    eval_every: int = 0
    checkpoint_every: int = 0
    checkpoint_dir: str = "results/checkpoints"
    log_path: str = ""
    mode: str = "sync"             # "sync" (parity oracle) | "async"
    refresh_groups: int = 1        # async: learner update + weight publish
    #                                every N complete GRPO groups off the
    #                                stream (0 = single end-of-stream update:
    #                                async plumbing, sync semantics)


class RolloutProducer:
    """Rollout half of the disaggregated trainer.

    Drives the engine through the continuous scheduler and emits
    trajectories onto the stream in completion order; with a streaming-safe
    composer each trajectory is scored the moment it retires, so rewards
    (including judge decoding, which opens its own session) pipeline with
    the rollout still in flight.
    """

    def __init__(self, worker: RolloutWorker, rewards, group_size: int):
        self.worker = worker
        self.rewards = rewards
        self.group_size = group_size
        self.n_emitted = 0
        self.n_pipelined = 0      # scored while other rows still decoded

    @property
    def streams_scores(self) -> bool:
        return (getattr(self.rewards, "streaming_safe", False)
                and self.worker.config.mode != "reference"
                and hasattr(self.worker.executor, "submit"))

    def stream(self, tasks, key):
        self.n_emitted = 0
        self.n_pipelined = 0
        streaming = self.streams_scores
        o = obs.get()
        for tr in self.worker.rollout_stream(tasks, key,
                                             group_size=self.group_size):
            if streaming:
                t_sc = o.tracer.now() if o.tracing else 0.0
                with o.registry.timer("reward/score_s").time():
                    self.rewards.score_one(tr, tr.meta["ground_truth"])
                if o.tracing:
                    o.tracer.complete("reward", "score", t_sc,
                                      o.tracer.now(),
                                      job=tr.meta.get("job_index", -1))
            self.n_emitted += 1
            yield tr
        if streaming:
            # every retiree but the last was scored while the rollout ran
            # (the last by definition ends the stream)
            self.n_pipelined = max(0, self.n_emitted - 1)


class Learner:
    """Learner half: consumes trajectory micro-batches, runs the GRPO
    clipped-surrogate update, and publishes refreshed params into the
    engine's :class:`~repro.serving.engine.WeightStore` — the producer swaps
    them in at its next round boundary, never mid-round.
    """

    def __init__(self, model, tokenizer, params, grpo_cfg: GRPOConfig,
                 opt_cfg: AdamWConfig, max_seq_len: int, engine=None,
                 ref_params=None):
        self.model = model
        self.tok = tokenizer
        self.params = params
        self.opt_state = adamw_init(params)
        self.grpo_cfg = grpo_cfg
        self.max_seq_len = max_seq_len
        self.engine = engine
        self.ref_params = ref_params          # frozen; None => no KL
        # the optimizer state (argument 1) belongs to this learner alone and
        # is donated: its two f32 moments are 8 bytes per parameter, which a
        # step would otherwise hold twice.  The params are not donated —
        # the engine's WeightStore may still be serving them.
        self._train_step = jax.jit(make_grpo_train_step(
            model, opt_cfg, grpo_cfg), donate_argnums=1)
        self._ref_logprob_fn = jax.jit(self._ref_logprobs_impl)
        self.n_updates = 0
        # masked per-token version lag of the last micro-batch (host copy,
        # for the iteration-level staleness distribution)
        self.last_staleness = np.zeros((0,), np.float32)

    def _ref_logprobs_impl(self, params, tokens):
        logits, _, _ = self.model.apply(params, {"tokens": tokens})
        lp = token_logprobs(logits, tokens)
        return jnp.concatenate([jnp.zeros((tokens.shape[0], 1)), lp], axis=1)

    @property
    def version(self) -> int:
        """Latest published weight version (0 for versionless engines)."""
        return int(getattr(self.engine, "latest_version", 0))

    def make_batch(self, trajs, adv):
        """Pack trajectories into the padded device batch, including the
        per-token staleness (learner's latest version minus the version that
        sampled each token — recorded by the scheduler at round boundaries)."""
        old_lps = [np.array(t.meta["logprobs"], np.float32) for t in trajs]
        batch_np = to_training_batch(trajs, self.max_seq_len, self.tok.pad_id,
                                     old_logprobs=old_lps)
        L = _bucket_len(batch_np["tokens"].shape[1])
        B = batch_np["tokens"].shape[0]
        learner_v = self.version
        stal = np.zeros_like(batch_np["old_logprobs"])
        for i, tr in enumerate(trajs):
            vers = tr.meta.get("policy_versions") or []
            n = min(len(vers), stal.shape[1])
            if n:
                stal[i, :n] = np.maximum(
                    0.0, learner_v - np.asarray(vers[:n], np.float32))
        batch = {
            "tokens": _pad_to(batch_np["tokens"], L, self.tok.pad_id),
            "loss_mask": _pad_to(batch_np["loss_mask"], L, 0.0),
            "old_logprobs": _pad_to(batch_np["old_logprobs"], L, 0.0),
            "staleness": _pad_to(stal, L, 0.0),
            "advantages": jnp.asarray(adv),
        }
        if self.ref_params is not None and self.grpo_cfg.kl_coef > 0:
            batch["ref_logprobs"] = self._ref_logprob_fn(self.ref_params,
                                                         batch["tokens"])
        else:
            batch["ref_logprobs"] = jnp.zeros((B, L), jnp.float32)
        self.last_staleness = stal[batch_np["loss_mask"] > 0]
        if self.last_staleness.size:
            # process-wide staleness distribution (versions of lag), beyond
            # the per-iteration p50/p90 scalars in the jsonl log
            obs.get().registry.histogram(
                "train/staleness").observe_many(self.last_staleness)
        return batch, batch_np

    def update(self, trajs, adv, publish: bool = True):
        """One optimizer step on a micro-batch of complete GRPO groups.

        Publishes the refreshed params into the engine's weight store
        (staged — the rollout side swaps at its next round boundary).
        Returns ``(metrics, n_model_tokens)``.
        """
        batch, batch_np = self.make_batch(trajs, adv)
        o = obs.get()
        t_up = o.tracer.now() if o.tracing else 0.0
        with o.registry.timer("train/update_s").time():
            self.params, self.opt_state, metrics = self._train_step(
                self.params, self.opt_state, batch)
        if o.tracing:
            o.tracer.complete("learner", "learner_update", t_up,
                              o.tracer.now(), n_trajs=len(trajs))
        self.n_updates += 1
        if publish and self.engine is not None:
            if hasattr(self.engine, "publish"):
                self.engine.publish(self.params)
            else:
                self.engine.params = self.params
        return metrics, int(batch_np["loss_mask"].sum())


class RLTrainer:
    def __init__(self, model, params, env, tokenizer, reward_composer,
                 trainer_cfg: TrainerConfig, rollout_cfg: RolloutConfig,
                 grpo_cfg: GRPOConfig, opt_cfg: AdamWConfig,
                 ref_params=None, executor=None, engine=None):
        self.model = model
        self.env = env
        self.tok = tokenizer
        self.rewards = reward_composer
        self.cfg = trainer_cfg
        self.grpo_cfg = grpo_cfg
        self.opt_cfg = opt_cfg
        self.ref_params = ref_params          # frozen; None => no KL
        self.engine = engine if engine is not None else GenerationEngine(
            model, params, pad_id=tokenizer.pad_id,
            stop_ids=(tokenizer.eos_id,), max_len=trainer_cfg.max_seq_len,
            temperature=rollout_cfg.temperature)
        self.worker = RolloutWorker(self.engine, env, tokenizer, rollout_cfg,
                                    executor=executor)
        self.learner = Learner(model, tokenizer, params, grpo_cfg, opt_cfg,
                               trainer_cfg.max_seq_len, engine=self.engine,
                               ref_params=ref_params)
        self.producer = RolloutProducer(self.worker, reward_composer,
                                        trainer_cfg.group_size)
        self.step = 0
        self.history: List[dict] = []

    # learner-owned state, surfaced for callers that read trainer.params /
    # trainer.opt_state directly (launch scripts, benchmarks, tests)
    @property
    def params(self):
        return self.learner.params

    @params.setter
    def params(self, p):
        self.learner.params = p

    @property
    def opt_state(self):
        return self.learner.opt_state

    @opt_state.setter
    def opt_state(self, s):
        self.learner.opt_state = s

    # ------------------------------------------------------------------
    def _rollout_and_score(self, tasks, key):
        """Roll the tasks out; with a streaming-safe composer, score each
        trajectory the moment it retires from the scheduler's stream instead
        of in a terminal phase.  Returns ``(trajs in task x group order,
        n_pipelined)``; ``n_pipelined`` is None when the batch path was used
        (the caller scores)."""
        if not self.producer.streams_scores:
            return (self.worker.rollout(tasks, key,
                                        group_size=self.cfg.group_size),
                    None)
        from repro.core.scheduler import order_by_job_index
        trajs = list(self.producer.stream(tasks, key))
        return order_by_job_index(trajs), self.producer.n_pipelined

    def train_iteration(self, key: jax.Array) -> dict:
        t0 = time.monotonic()
        key, k_task, k_roll = jax.random.split(key, 3)
        seed = int(jax.random.randint(k_task, (), 0, 2**31 - 1))
        tasks = self.env.sample_tasks(self.cfg.n_tasks_per_iter,
                                      split="train", seed=seed)
        if self.cfg.mode == "async":
            out = self._iterate_async(tasks, k_roll, t0)
        else:
            out = self._iterate_sync(tasks, k_roll, t0)
        self.step += 1
        out["step"] = self.step
        self.history.append(out)
        if self.cfg.log_path:
            os.makedirs(os.path.dirname(self.cfg.log_path) or ".",
                        exist_ok=True)
            with open(self.cfg.log_path, "a") as f:
                f.write(json.dumps(out) + "\n")
        if (self.cfg.checkpoint_every
                and self.step % self.cfg.checkpoint_every == 0):
            self.save_checkpoint()
        return out

    # --------------------------------------------------------- sync handoff
    def _iterate_sync(self, tasks, k_roll, t0):
        """The seed behavior: one update over the whole rollout, weights
        swapped in before the next iteration (the parity oracle)."""
        trajs, n_pipelined = self._rollout_and_score(tasks, k_roll)
        t_roll = time.monotonic() - t0

        gts = [t.meta["ground_truth"] for t in trajs]
        if n_pipelined is None:
            rewards = self.rewards(trajs, gts)
            pipelined_fraction = 0.0
        else:
            rewards = np.array([t.reward for t in trajs], np.float32)
            pipelined_fraction = n_pipelined / max(len(trajs), 1)
        adv = grpo_advantages(rewards, [t.group_id for t in trajs])

        t1 = time.monotonic()
        metrics, n_model_tokens = self.learner.update(trajs, adv)
        if hasattr(self.engine, "refresh_weights"):
            self.engine.refresh_weights()     # sync handoff: swap immediately
        t_train = time.monotonic() - t1
        return self._finalize(trajs, rewards,
                              {k: float(v) for k, v in metrics.items()},
                              n_model_tokens, t_roll, t_train,
                              pipelined_fraction, n_updates=1,
                              stal_values=self.learner.last_staleness)

    # ---------------------------------------------------- in-flight refresh
    def _iterate_async(self, tasks, k_roll, t0):
        """Consume complete GRPO groups off the trajectory stream; run a
        learner update (and publish refreshed weights) every
        ``refresh_groups`` groups while the rollout is still in flight."""
        from repro.core.scheduler import order_by_job_index
        gs = self.cfg.group_size
        rg = max(0, self.cfg.refresh_groups)
        streaming = self.producer.streams_scores

        all_trajs: List = []
        open_groups: dict = {}
        ready: List[list] = []
        metrics_acc: List[dict] = []
        stal_acc: List[np.ndarray] = []
        n_model_tokens = 0
        n_batch_pipelined = 0
        t_learn = 0.0
        t_learn_overlap = 0.0
        n_updates = 0

        def run_update(group_list, in_flight):
            nonlocal n_model_tokens, t_learn, t_learn_overlap, n_updates
            mb = order_by_job_index([t for g in group_list for t in g])
            if not streaming:
                self.rewards(mb, [t.meta["ground_truth"] for t in mb])
            rewards_mb = np.array([t.reward for t in mb], np.float32)
            adv = grpo_advantages(rewards_mb, [t.group_id for t in mb])
            tl = time.monotonic()
            metrics, ntok = self.learner.update(mb, adv)
            dt = time.monotonic() - tl
            t_learn += dt
            if in_flight:
                t_learn_overlap += dt     # rows still decoding / tool
                #                           futures on the background loop
            metrics_acc.append({k: float(v) for k, v in metrics.items()})
            stal_acc.append(self.learner.last_staleness)
            n_model_tokens += ntok
            n_updates += 1

        for tr in self.producer.stream(tasks, k_roll):
            all_trajs.append(tr)
            open_groups.setdefault(tr.group_id, []).append(tr)
            if len(open_groups[tr.group_id]) >= gs:
                ready.append(open_groups.pop(tr.group_id))
            while rg and len(ready) >= rg:
                mb, ready = ready[:rg], ready[rg:]
                if not streaming:
                    n_batch_pipelined += sum(len(g) for g in mb)
                run_update(mb, in_flight=True)
        ready.extend(open_groups.values())    # stream never leaves a group
        #                                       open, but don't drop rows
        if ready:
            run_update(ready, in_flight=False)
        if hasattr(self.engine, "refresh_weights"):
            self.engine.refresh_weights()     # iteration boundary sync point

        wall = time.monotonic() - t0
        rewards = np.array([t.reward for t in all_trajs], np.float32)
        if streaming:
            pipelined = self.producer.n_pipelined / max(len(all_trajs), 1)
        else:
            pipelined = n_batch_pipelined / max(len(all_trajs), 1)
        train_metrics = _mean_metrics(metrics_acc)
        stal_values = (np.concatenate(stal_acc) if stal_acc
                       else np.zeros((0,), np.float32))
        out = self._finalize(all_trajs, rewards, train_metrics,
                             n_model_tokens, max(wall - t_learn, 0.0),
                             t_learn, pipelined, n_updates=n_updates,
                             stal_values=stal_values)
        out["train/learner_overlap_s"] = t_learn_overlap
        out["train/learner_overlap_frac"] = (t_learn_overlap
                                             / max(t_learn, 1e-9))
        return out

    # ------------------------------------------------------------------
    def _finalize(self, trajs, rewards, train_metrics, n_model_tokens,
                  t_roll, t_train, pipelined_fraction, n_updates,
                  stal_values):
        out = {
            "reward_mean": float(rewards.mean()),
            "reward_std": float(rewards.std()),
            "exact_match": float(np.mean([
                t.reward_breakdown.get("rule/exact_match", 0.0) for t in trajs])),
            "finished_frac": float(np.mean([t.finished for t in trajs])),
            "tool_calls_mean": float(np.mean([t.n_tool_calls for t in trajs])),
            "traj_len_mean": float(np.mean([len(t) for t in trajs])),
            "rollout_s": t_roll,
            "train_s": t_train,
            "model_tokens": n_model_tokens,
            "throughput_tok_s": n_model_tokens / max(t_roll + t_train, 1e-9),
            **train_metrics,
        }
        out["reward/pipelined_fraction"] = float(pipelined_fraction)
        # in-flight refresh observability: weight-version lag of the tokens
        # that entered the loss, and clip_frac split by freshness
        out["train/staleness_mean"] = out.get("staleness_mean", 0.0)
        out["train/staleness_max"] = out.get("staleness_max", 0.0)
        out["train/clip_frac_fresh"] = out.get("clip_frac_fresh", 0.0)
        out["train/clip_frac_stale"] = out.get("clip_frac_stale", 0.0)
        out["train/n_updates"] = float(n_updates)
        out["train/weight_version"] = float(
            getattr(self.engine, "latest_version", 0))
        if stal_values is not None and stal_values.size:
            out["train/staleness_p50"] = float(np.percentile(stal_values, 50))
            out["train/staleness_p90"] = float(np.percentile(stal_values, 90))
        # episode-termination distribution: over-budget/truncated rows are
        # distinguishable from answered ones in the logs
        for reason in STOP_REASONS:
            out[f"stop/{reason}"] = float(np.mean(
                [t.stop_reason == reason for t in trajs]))
        # continuous-batching scheduler stats (empty in reference mode)
        sched = getattr(self.worker, "last_stats", None) or {}
        for k in ("slot_occupancy", "overlap_factor", "tool_wait_s", "gen_s",
                  "rounds", "refills", "n_slots", "cache_utilization",
                  "cache_utilization_peak", "min_round_budget",
                  "adaptive_rounds", "admission_deferrals", "evictions",
                  "preemptions", "swap_out", "swap_in",
                  "weight_refreshes", "prefix_hit_rate", "shared_blocks",
                  "cow_count", "prefix_evictions", "tool_timeouts",
                  "decode_round_p50_s", "decode_round_p99_s",
                  "admission_wait_p90_s", "starved_rounds"):
            if k in sched:
                out[f"rollout/{k}"] = float(sched[k])
        return out

    # ------------------------------------------------------------------
    def save_checkpoint(self, path: Optional[str] = None) -> str:
        """Persist params/opt-state and the weight-version counter, so a
        resumed run keeps version monotonicity (staleness accounting stays
        correct across restarts)."""
        from repro.checkpoint.checkpointer import save_checkpoint
        path = path or os.path.join(self.cfg.checkpoint_dir,
                                    f"step_{self.step}.ckpt")
        save_checkpoint(path, self.params, self.opt_state, step=self.step,
                        weight_version=int(
                            getattr(self.engine, "latest_version", 0)))
        return path

    def load_checkpoint(self, path: str) -> dict:
        """Restore params/opt-state/step and re-base the engine's weight
        store at the persisted version counter."""
        from repro.checkpoint.checkpointer import load_checkpoint
        params, opt_state, step, meta = load_checkpoint(
            path, self.params, self.opt_state)
        self.params = params
        if opt_state is not None:
            self.opt_state = opt_state
        self.step = int(step)
        self.engine.params = params           # publish + swap restored weights
        wv = meta.get("weight_version")
        if wv is not None and hasattr(self.engine, "weights"):
            self.engine.weights.set_version(int(wv))
        return meta

    # ------------------------------------------------------------------
    def evaluate(self, n_tasks: int = 32, seed: int = 1234,
                 key: Optional[jax.Array] = None) -> dict:
        """Greedy rollouts on the held-out split; exact-match score.

        The default reproduces the fixed held-out draw (``seed=1234``).
        Callers that want eval tasks to vary — e.g. periodic eval inside a
        training loop — pass their own ``key`` (or a different ``seed``):
        the task draw and rollout stream are derived from it instead.
        """
        if key is not None:
            key, k_task = jax.random.split(key)
            seed = int(jax.random.randint(k_task, (), 0, 2**31 - 1))
        else:
            key = jax.random.PRNGKey(seed)
        tasks = self.env.sample_tasks(n_tasks, split="test", seed=seed)
        old_temp = self.worker.config.temperature
        self.worker.config.temperature = 0.0
        try:
            trajs = self.worker.rollout(tasks, key, group_size=1)
        finally:
            self.worker.config.temperature = old_temp
        gts = [t.meta["ground_truth"] for t in trajs]
        scores = [self.env.compute_score(t, g) for t, g in zip(trajs, gts)]
        return {
            "test_score": float(np.mean([s["score"] for s in scores])),
            "test_exact_match": float(np.mean([s["exact_match"]
                                               for s in scores])),
            "test_answer_format": float(np.mean([s["answer_format"]
                                                 for s in scores])),
            "test_tool_format": float(np.mean([s["tool_format"]
                                               for s in scores])),
        }


def _mean_metrics(metric_dicts: List[dict]) -> dict:
    """Average train metrics across micro-updates (max for *_max keys)."""
    if not metric_dicts:
        return {}
    out = {}
    for k in metric_dicts[0]:
        vals = [m[k] for m in metric_dicts if k in m]
        out[k] = max(vals) if k.endswith("_max") else float(np.mean(vals))
    return out


def _pad_to(arr: np.ndarray, L: int, fill) -> jnp.ndarray:
    B, cur = arr.shape
    if cur >= L:
        return jnp.asarray(arr[:, :L])
    out = np.full((B, L), fill, arr.dtype)
    out[:, :cur] = arr
    return jnp.asarray(out)
