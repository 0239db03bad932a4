"""GRPO — group-relative policy optimization on observation-masked
trajectories (paper Fig. 4; veRL-native algorithm reused by RLFactory).

Advantage: A_i = (r_i - mean(group)) / (std(group) + eps), one scalar per
trajectory, broadcast over its MODEL tokens.  The policy loss is the PPO
clipped surrogate with a k3 KL penalty to the reference policy; observation
and prompt tokens contribute nothing — their loss-mask is zero (paper §2.2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GRPOConfig:
    clip_eps: float = 0.2
    kl_coef: float = 0.001
    aux_coef: float = 0.001           # MoE router load-balance weight
    adv_eps: float = 1e-6
    micro_batch: int = 0              # 0 = no gradient accumulation
    accum_unroll: bool = False        # python-loop accumulation (dry-run aux
                                      # compiles: exact cost_analysis)
    max_staleness: int = -1           # in-flight refresh: tokens sampled
                                      # more than this many weight versions
                                      # behind the learner are masked out of
                                      # the loss (-1 = keep all; the clipped
                                      # importance ratio already corrects
                                      # mild off-policyness)


# --------------------------------------------------------------- advantages
def grpo_advantages(rewards: np.ndarray, group_ids: np.ndarray,
                    eps: float = 1e-6) -> np.ndarray:
    """Group-normalized advantages (host-side, ragged groups allowed)."""
    rewards = np.asarray(rewards, np.float32)
    group_ids = np.asarray(group_ids)
    adv = np.zeros_like(rewards)
    for g in np.unique(group_ids):
        m = group_ids == g
        r = rewards[m]
        adv[m] = (r - r.mean()) / (r.std() + eps)
    return adv


def grpo_advantages_jnp(rewards: jnp.ndarray, group_ids: jnp.ndarray,
                        n_groups: int, eps: float = 1e-6) -> jnp.ndarray:
    """Device-side variant for fixed group counts (used in the jitted path)."""
    one_hot = jax.nn.one_hot(group_ids, n_groups, dtype=jnp.float32)  # (B,G)
    counts = one_hot.sum(0)                                           # (G,)
    mean = (one_hot * rewards[:, None]).sum(0) / jnp.maximum(counts, 1)
    var = (one_hot * jnp.square(rewards[:, None] - mean[None, :])).sum(0) \
        / jnp.maximum(counts, 1)
    std = jnp.sqrt(var)
    return (rewards - one_hot @ mean) / (one_hot @ std + eps)


# --------------------------------------------------------------- logprobs
def token_logprobs(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """logits (B,S,V), tokens (B,S) -> logprob of tokens[t] given prefix < t,
    shape (B, S-1) aligned to target positions 1..S-1.

    Sharding-safe formulation: the label logit is extracted by a one-hot
    contraction (fuses into a masked reduction per vocab shard + a tiny
    all-reduce) instead of take_along_axis, which would all-gather the full
    (B,S,V) logits when the vocab dim is sharded.
    """
    x = logits[:, :-1].astype(jnp.float32)                   # (B,S-1,V)
    labels = tokens[:, 1:]
    lse = jax.scipy.special.logsumexp(x, axis=-1)            # (B,S-1)
    V = x.shape[-1]
    hit = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
           == labels[:, :, None])
    label_logit = jnp.sum(jnp.where(hit, x, 0.0), axis=-1)
    return label_logit - lse


def token_logprobs_fused(logits, tokens):
    """Same, via the streaming Pallas kernel (vocab-tiled log-softmax)."""
    from repro.kernels.ops import fused_token_logprob
    return fused_token_logprob(logits[:, :-1], tokens[:, 1:])


# --------------------------------------------------------------- loss
def grpo_loss(logits: jnp.ndarray, batch: dict, cfg: GRPOConfig,
              aux: jnp.ndarray = 0.0, use_fused: bool = False):
    """Clipped-surrogate GRPO loss.

    batch: tokens (B,S) int32; loss_mask (B,S) in {0,1} — 1 on MODEL tokens;
    advantages (B,); old_logprobs (B,S) — logprob recorded at sampling time,
    0 elsewhere; ref_logprobs (B,S) — reference-policy logprobs (0 => no KL).

    Optional ``staleness`` (B,S) int32: per-token weight-version lag
    (learner version at update time minus the version that sampled the
    token; in-flight refresh makes this > 0 for trajectories that straddled
    a publish).  The importance ratio against the *recorded* ``old_logprobs``
    is already exact for any lag; staleness additionally (a) masks tokens
    beyond ``cfg.max_staleness`` out of the loss, and (b) splits
    ``clip_frac`` into fresh/stale so off-policy drift is observable.
    Absent or all-zero staleness reproduces the synchronous loss bit-for-bit.
    """
    lp = (token_logprobs_fused(logits, batch["tokens"]) if use_fused
          else token_logprobs(logits, batch["tokens"]))          # (B,S-1)
    mask = batch["loss_mask"][:, 1:].astype(jnp.float32)
    adv = batch["advantages"][:, None].astype(jnp.float32)
    old = batch["old_logprobs"][:, 1:].astype(jnp.float32)
    ref = batch["ref_logprobs"][:, 1:].astype(jnp.float32)
    stale = (batch["staleness"][:, 1:].astype(jnp.float32)
             if "staleness" in batch
             else jnp.zeros_like(mask))
    if cfg.max_staleness >= 0:
        # per-token version mask: drop tokens whose sampling policy lags
        # the learner by more than the configured budget
        mask = mask * (stale <= float(cfg.max_staleness)).astype(jnp.float32)

    ratio = jnp.exp(lp - old)
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1 - cfg.clip_eps, 1 + cfg.clip_eps) * adv
    surrogate = jnp.minimum(unclipped, clipped)

    # k3 KL estimator vs reference policy (veRL convention)
    log_r = ref - lp
    kl = jnp.exp(log_r) - log_r - 1.0
    kl = jnp.where(jnp.abs(ref) > 0, kl, 0.0)

    denom = jnp.maximum(mask.sum(), 1.0)
    pg_loss = -(surrogate * mask).sum() / denom
    kl_loss = (kl * mask).sum() / denom
    loss = pg_loss + cfg.kl_coef * kl_loss + cfg.aux_coef * aux
    clipped_tok = (jnp.abs(ratio - 1) > cfg.clip_eps).astype(jnp.float32)
    fresh_m = mask * (stale == 0)
    stale_m = mask * (stale > 0)
    metrics = {
        "loss": loss,
        "pg_loss": pg_loss,
        "kl": kl_loss,
        "aux": aux,
        "ratio_mean": (ratio * mask).sum() / denom,
        "clip_frac": (clipped_tok * mask).sum() / denom,
        "entropy_proxy": -(lp * mask).sum() / denom,
        # in-flight refresh observability: version-lag distribution over the
        # tokens in the loss, and clip_frac split by freshness
        "staleness_mean": (stale * mask).sum() / denom,
        "staleness_max": (stale * mask).max(),
        "staleness_frac": stale_m.sum() / denom,
        "clip_frac_fresh": ((clipped_tok * fresh_m).sum()
                            / jnp.maximum(fresh_m.sum(), 1.0)),
        "clip_frac_stale": ((clipped_tok * stale_m).sum()
                            / jnp.maximum(stale_m.sum(), 1.0)),
    }
    return loss, metrics


# --------------------------------------------------------------- train step
def make_grpo_train_step(model, opt_cfg, grpo_cfg: GRPOConfig,
                         use_flash: bool = False, use_fused_logprob: bool = False):
    """Returns jit-able ``train_step(params, opt_state, batch) ->
    (params, opt_state, metrics)`` with optional microbatch grad accumulation.

    batch layout == Model.input_specs("train_4k") (+ optional prefix_embeds).
    The step's ops carry the ``forward``, ``loss`` and ``optimizer`` name
    scopes (their backward parts ``transpose(jvp(...))`` of the first two),
    so a profile splits a step by them.
    """
    from repro.optim.adamw import adamw_update

    def loss_fn(params, mb):
        fwd = {"tokens": mb["tokens"]}
        if "prefix_embeds" in mb:
            fwd["prefix_embeds"] = mb["prefix_embeds"]
        with jax.named_scope("forward"):
            logits, aux, _ = model.apply(params, fwd, use_flash=use_flash)
        if "prefix_embeds" in mb and model.cfg.family == "vlm":
            # vlm: logits cover [prefix, text]; the RL loss is text-only
            logits = logits[:, mb["prefix_embeds"].shape[1]:, :]
        with jax.named_scope("loss"):
            return grpo_loss(logits, mb, grpo_cfg, aux=aux,
                             use_fused=use_fused_logprob)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        nm = grpo_cfg.micro_batch
        if nm and batch["tokens"].shape[0] > nm:
            B = batch["tokens"].shape[0]
            assert B % nm == 0, (B, nm)
            k = B // nm

            def mb_slice(i):
                return jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, i * nm, nm, 0)
                    if hasattr(a, "shape") and a.ndim >= 1 and a.shape[0] == B
                    else a, batch)

            def body(carry, i):
                gsum, msum = carry
                (l, m), g = grad_fn(params, mb_slice(i))
                gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                msum = jax.tree_util.tree_map(jnp.add, msum, m)
                return (gsum, msum), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zero_m = {k_: jnp.zeros((), jnp.float32) for k_ in
                      ("loss", "pg_loss", "kl", "aux", "ratio_mean",
                       "clip_frac", "entropy_proxy", "staleness_mean",
                       "staleness_max", "staleness_frac",
                       "clip_frac_fresh", "clip_frac_stale")}
            if grpo_cfg.accum_unroll:
                carry = (zero_g, zero_m)
                for i in range(k):
                    carry, _ = body(carry, jnp.int32(i))
                gsum, msum = carry
            else:
                (gsum, msum), _ = jax.lax.scan(body, (zero_g, zero_m),
                                               jnp.arange(k))
            grads = jax.tree_util.tree_map(lambda g: g / k, gsum)
            metrics = jax.tree_util.tree_map(lambda m: m / k, msum)
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        with jax.named_scope("optimizer"):
            params, opt_state, opt_metrics = adamw_update(opt_cfg, grads,
                                                          opt_state, params)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return train_step
