"""The learner entry under a window: ``Learner.update`` (``make_batch`` and
the jitted GRPO step with AdamW) on back-to-back GRPO groups.

Set-up builds one learner, drives it from the seed through its first
steps on batches that all differ (the first ``checked_steps`` are the ones
the reference follows), and hands that same learner to the window, which
cycles the traffic's batches.  The program's step timer measures dispatch,
so the window ends on a ``block_until_ready`` of the parameters: it holds
whole steps, and the host may prepare the next batch while the device
runs the last, as a trainer's loop would.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import flops as F
import harness as H
import program as P
import traffic as TR
import weights as W
from harness import Span, log

from repro import obs
from repro.core.grpo import GRPOConfig
from repro.core.mdp import Role, Trajectory
from repro.core.trainer import Learner
from repro.data.tokenizer import default_tokenizer
from repro.optim.adamw import AdamWConfig

ROLES = (Role.PROMPT, Role.MODEL, Role.OBSERVATION)


def trajectories(group: list, group_id: int) -> list:
    """A traffic group as the program's trajectories."""
    out = []
    for t in group:
        tr = Trajectory(group_id=group_id, meta={
            "logprobs": list(t["old"]), "policy_versions": [0] * len(t["old"])})
        start = 0
        roles = t["roles"]
        for i in range(1, len(roles) + 1):
            if i == len(roles) or roles[i] != roles[start]:
                tr.append(ROLES[roles[start]], t["tokens"][start:i])
                start = i
        out.append(tr)
    return out


def leaf_norms(cfg, tree) -> dict:
    """(name, layer) -> float norm of each per-layer slice of a program
    tree (the layout's ``layer_leaves``), computed on the device."""
    leaves = H.layout(cfg).layer_leaves(tree)
    names = list(leaves)
    vals = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        leaves[n].astype(jnp.float32)))) for n in names])
    return {n: float(v) for n, v in zip(names, vals)}


def diff_norms(cfg, a, b) -> dict:
    leaves = H.layout(cfg).layer_leaves
    la, lb = leaves(a), leaves(b)
    names = list(la)
    vals = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        la[n].astype(jnp.float32) - lb[n].astype(jnp.float32))))
        for n in names])
    return {n: float(v) for n, v in zip(names, vals)}


def run(cell: H.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, fault=None, variants: bool = False) -> dict:
    cfg, mix = cell.config, cell.traffic
    batches = TR.learner_batches(mix, cfg, seed)
    prog = program_part(cell, seed, seconds, traced, t_start, batches, fault)
    gc.collect()                 # the learner's state is gone: the
    t_ref = time.perf_counter()  # reference has the chip
    numbers, extra = check(cfg, mix, seed, batches, prog.pop("losses"),
                           prog.pop("grads"), prog.pop("change"), variants)
    log(f"reference check: {time.perf_counter() - t_ref:.1f} s")
    return dict(prog, numbers=numbers, extra=extra)


def program_part(cell, seed, seconds, traced, t_start, batches, fault):
    """Set-up, the checked first steps and the window; returns host
    values only, so that the learner's device state dies with the call."""
    cfg, mix = cell.config, cell.traffic
    opt, grpo = mix["optimizer"], mix["grpo"]
    model = P.model_of(cfg)
    tok = default_tokenizer(model.cfg.vocab_size)
    groups = [trajectories(g, i) for i, g in enumerate(batches)]
    advs = [np.array([t["adv"] for t in g], np.float32) for g in batches]
    learner = Learner(
        model, tok, P.make_params(model, cfg, seed,
                                  P.dtype_of(cfg["param_dtype"])),
        GRPOConfig(clip_eps=grpo["clip_eps"], kl_coef=grpo["kl_coef"]),
        AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    weight_decay=opt["weight_decay"],
                    clip_norm=opt["clip_norm"]),
        mix["max_seq_len"])
    if fault is not None:
        fault(learner)
    spans: dict = {}
    make_batch = learner.make_batch

    def spanned_make_batch(*a, **kw):
        with Span("learner.make_batch", spans):
            return make_batch(*a, **kw)

    learner.make_batch = spanned_make_batch

    def step(i):
        with Span("learner.step", spans):
            metrics, _ = learner.update(groups[i % len(groups)],
                                        advs[i % len(groups)], publish=False)
        return metrics

    # the checked steps: every batch differs; the first gradient is read
    # from the optimizer's first moment after one step
    n_chk = mix["checked_steps"]
    p0 = learner.params
    losses, grads = [], None
    for i in range(n_chk):
        metrics = step(i)
        losses.append(float(metrics["loss"]))
        if i == 0:
            m1 = leaf_norms(cfg, learner.opt_state["m"])
            grads = {k: v / (1 - opt["b1"]) for k, v in m1.items()}
    change = diff_norms(cfg, learner.params, p0)
    del p0
    for i in range(n_chk, len(groups)):      # every remaining batch shape
        step(i)
    jax.block_until_ready(learner.params)
    log(f"set-up steps done: {time.perf_counter() - t_start:.1f} s")

    window = H.TracedWindow(traced, cell.name, seed)
    program_open = obs.get().registry.snapshot()
    window.open()
    cc0 = H.CompileCount().snapshot()
    spans.clear()
    t_open = time.perf_counter()
    n_steps, n_tok, n_keys, i = 0, 0, 0, len(groups)
    window_losses = []
    while time.perf_counter() - t_open < seconds:
        window_losses.append(step(i)["loss"])
        group = batches[i % len(groups)]
        n_tok += TR.n_tokens(group)
        n_keys += F.causal_keys([len(t["tokens"]) for t in group])
        n_steps += 1
        i += 1
    jax.block_until_ready(learner.params)
    t_close = time.perf_counter()
    window.close()
    program_close = obs.get().registry.snapshot()
    cc = H.CompileCount.since(cc0)
    failed = int(sum(not np.isfinite(float(x)) for x in window_losses))
    log(f"window {t_close - t_open:.2f} s, {n_steps} steps, {n_tok} tokens; "
        f"in the window: {cc['compiled']} programs compiled, "
        f"{cc['cache_hit']} loaded from the compile cache, {cc['trace']} "
        f"traces: {cc['names']}")
    return {"setup_s": t_open - t_start, "window_s": t_close - t_open,
            "counts": {"steps": n_steps, "tokens": n_tok, "keys": n_keys,
                       "spans_s": dict(spans), "compiles": cc["compiled"],
                       "program_open": program_open,
                       "program_close": program_close},
            "attempted": n_steps, "failed": failed,
            "memory_peak_bytes": H.memory_peak_bytes(cell.chips),
            "trace_dir": window.dir if traced else None,
            "losses": losses, "grads": grads, "change": change}


# ------------------------------------------------------------- the check
def reference_steps(ref, cfg, mix, seed, batches, dtype, rows=None):
    """The plain reference's first steps from the seed's weights: the loss
    of each, the norm of each leaf's first (clipped) gradient, and each
    leaf's change after the steps.  ``rows`` keeps only those rows of each
    batch (the half-batch fault), ``dtype`` is the parameters' and
    activations' precision.  Buffers are donated step to step so that the
    float32 model, its gradient and both moments fit the chip."""
    opt, clip_eps = mix["optimizer"], mix["grpo"]["clip_eps"]
    key = W.base_key(seed)
    n = cfg["num_hidden_layers"]
    init = jax.jit(lambda k: {
        "layers": [W.layer_weights(k, cfg, i, dtype) for i in range(n)],
        **W.global_weights(k, cfg, dtype)})
    params = init(key)
    state = {m: jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params) for m in "mv"}

    @jax.jit
    def grad_chunk(params, b, denom):
        def f(p):
            return ref.grpo_loss_sum(p, b, cfg, clip_eps, dtype) / denom
        return jax.value_and_grad(f)(params)

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)

    def _update(p, g, s, t):
        p, s, clipped = ref.adamw(p, g, s, t, opt)
        return p, s, flat_norms(clipped)

    update = jax.jit(_update, donate_argnums=(0, 1, 2))
    losses, first = [], None
    for step in range(mix["checked_steps"]):
        group = batches[step]
        if rows is not None:
            group = [group[r] for r in rows]
        b = TR.padded(group, TR.ceil_to(max(len(t["tokens"]) for t in group),
                                        64))
        denom = jnp.float32(max(float(b["mask"][:, 1:].sum()), 1.0))
        loss, grads = 0.0, None
        for c in range(len(group)):
            part = {k: jnp.asarray(v[c:c + 1]) for k, v in b.items()}
            l, g = grad_chunk(params, part, denom)
            loss += float(l)
            grads = g if grads is None else add(grads, g)
            del g
        params, state, norms = update(params, grads, state,
                                      jnp.float32(step + 1))
        del grads
        losses.append(loss)
        if step == 0:
            first = {k: float(v) for k, v in jax.device_get(norms).items()}
    del state
    diff = jax.jit(lambda a, b: flat_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    change = {k: float(v) for k, v in
              jax.device_get(diff(params, init(key))).items()}
    return losses, first, change


def flat_norms(tree) -> dict:
    """(name, layer) -> norm of each tensor of a reference tree; inside
    jit, the values are device scalars."""
    out = {}
    for name, arr in tree.items():
        if name == "layers":
            for i, lw in enumerate(arr):
                for k, v in lw.items():
                    out[(k, i)] = jnp.sqrt(jnp.sum(jnp.square(
                        v.astype(jnp.float32))))
        else:
            out[(name, None)] = jnp.sqrt(jnp.sum(jnp.square(
                arr.astype(jnp.float32))))
    return out


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """The widest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's and the median leaf's reference
    norm."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def moved_leaves(ref_grad: dict) -> set:
    """Leaves whose first reference gradient is at least a thousandth of
    the median leaf's: the others move under AdamW by round-off alone."""
    med = float(np.median(list(ref_grad.values())))
    return {n for n, v in ref_grad.items() if v >= 1e-3 * med}


def loss_scales(mix, batches) -> list:
    """Each checked step's natural unit of loss: the mean |advantage| over
    its masked tokens (the surrogate of a token is about r * A, r ~ 1)."""
    out = []
    for group in batches[:mix["checked_steps"]]:
        n = [sum(1 for x in t["roles"][1:] if x == 1) for t in group]
        out.append(sum(abs(t["adv"]) * k for t, k in zip(group, n))
                   / max(sum(n), 1))
    return out


def numbers_of(losses, grads, change, ref, scales) -> dict:
    r_loss, r_grad, r_change = ref
    keep = moved_leaves(r_grad)
    return {"loss_gap": max(abs(a - b) / s
                            for a, b, s in zip(losses, r_loss, scales)),
            "grad_gap": worst_leaf_gap(grads, r_grad),
            "change_gap": worst_leaf_gap(change, r_change, keep)}


def check(cfg, mix, seed, batches, losses, grads, change, variants):
    """The program's first steps against the plain reference's, in float32
    at HIGHEST precision.  With ``variants``, also the readings that set
    the limits' upper ends: the reference one precision lower (bfloat16
    parameters and activations) and the half-batch fault, each put in the
    program's place."""
    ref = H.config_module(cfg, "reference")
    full = reference_steps(ref, cfg, mix, seed, batches, jnp.float32)
    scales = loss_scales(mix, batches)
    numbers = numbers_of(losses, grads, change, full, scales)
    log(f"losses program {losses} reference {full[0]}")
    extra = {}
    if variants:
        G = mix["group_size"]
        for name, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                         ("fault_half_batch", {"dtype": jnp.float32,
                                               "rows": list(range(G // 2))})):
            v = reference_steps(ref, cfg, mix, seed, batches, **kw)
            extra[name] = numbers_of(v[0], v[1], v[2], full, scales)
            log(f"{name}: {extra[name]}")
    return numbers, extra
