"""Plain reference of a Qwen2/Qwen3-style dense decoder, its GRPO loss and
AdamW, in float32 with every matrix product at HIGHEST precision.

Written from the published description (Hugging Face ``modeling_qwen3`` /
``modeling_qwen2``): RMSNorm with a learned scale, rotate-half RoPE, GQA
with optional q/k RMSNorm per head (Qwen3) and optional q/k/v bias (Qwen2),
causal softmax attention scaled by 1/sqrt(head_dim), a SwiGLU MLP, a final
RMSNorm and a tied or separate output head.  No cache, no kernel, no
batching trick; it imports nothing of the program.  ``dtype`` lets the same
code run one precision lower, as the correctness control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8 = "fp8"     # every matrix product's inputs in float8 e4m3, f32 sums


def fp8(x):
    """``x`` through float8 e4m3 with one scale for the tensor."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def act(x, dtype):
    """Activations carried between products in ``dtype`` (float32 when
    the products themselves quantize)."""
    return x.astype(jnp.float32 if dtype == FP8 else dtype)


def _mm(eq, a, b, dtype):
    if dtype == FP8:
        return jnp.einsum(eq, fp8(a), fp8(b), precision=HI,
                          preferred_element_type=jnp.float32)
    prec = HI if dtype == jnp.float32 else None
    return jnp.einsum(eq, a, b.astype(dtype), precision=prec,
                      preferred_element_type=dtype)


def rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """x (..., L, heads, D), pos (..., L): rotate-half RoPE."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def qkv(x, w, cfg, dtype):
    """Queries, keys and values (L, heads, D) of one normed sequence x
    (L, d) at positions 0 .. L-1, keys and queries rotated."""
    L = x.shape[0]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = _mm("ld,dhe->lhe", x, w["q"], dtype)
    k = _mm("ld,dhe->lhe", x, w["k"], dtype)
    v = _mm("ld,dhe->lhe", x, w["v"], dtype)
    if "q_bias" in w:
        q, k, v = (q + act(w["q_bias"], dtype), k + act(w["k_bias"], dtype),
                   v + act(w["v_bias"], dtype))
    if "q_norm" in w:
        q, k = rmsnorm(q, w["q_norm"], eps), rmsnorm(k, w["k_norm"], eps)
    pos = jnp.arange(L)
    return rope(q, pos, theta), rope(k, pos, theta), v


def attention(x, w, cfg, dtype):
    """x (L, d) of one sequence at positions 0 .. L-1."""
    L = x.shape[0]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q, k, v = qkv(x, w, cfg, dtype)
    D = q.shape[-1]
    qg = q.reshape(L, Hk, H // Hk, D)
    s = _mm("qkgd,mkd->kgqm", qg, k, dtype).astype(jnp.float32) / D ** 0.5
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = act(jax.nn.softmax(s, axis=-1), dtype)
    o = _mm("kgqm,mkd->qkgd", p, v, dtype).reshape(L, H, D)
    return _mm("lhe,hed->ld", o, w["o"], dtype)


def layer_values(x, w, cfg, dtype=jnp.float32):
    """The values (L, kv_heads, D) that a layer caches for its input x."""
    return qkv(rmsnorm(x, w["ln1"], cfg["rms_norm_eps"]), w, cfg, dtype)[2]


def layer(x, w, cfg, dtype=jnp.float32):
    """One decoder layer on one sequence x (L, d)."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(rmsnorm(x, w["ln1"], eps), w, cfg, dtype)
    y = rmsnorm(h, w["ln2"], eps)
    g = _mm("ld,df->lf", y, w["gate"], dtype)
    u = _mm("ld,df->lf", y, w["up"], dtype)
    return h + _mm("lf,fd->ld", jax.nn.silu(g) * u, w["down"], dtype)


def head(x, final_norm, out_w, cfg, dtype=jnp.float32):
    """Logits (..., V) from the last hidden states; ``out_w`` is the
    embedding (V, d) when tied, else the head (d, V)."""
    y = rmsnorm(x, final_norm, cfg["rms_norm_eps"])
    if cfg.get("tie_word_embeddings", False):
        return _mm("...d,vd->...v", y, out_w, dtype).astype(jnp.float32)
    return _mm("...d,dv->...v", y, out_w, dtype).astype(jnp.float32)


def forward(params, tokens, cfg, dtype=jnp.float32):
    """Logits (B, L, V) of right-padded sequences (B, L); the pads sit
    after every real token, so causal attention never sees them."""
    x = act(params["embed"], dtype)[tokens]

    def one(seq):
        for w in params["layers"]:
            seq = layer(seq, w, cfg, dtype)
        return seq

    x = jax.vmap(one)(x)
    out_w = params["embed"] if cfg.get("tie_word_embeddings") \
        else params["unembed"]
    return head(x, params["final_norm"], out_w, cfg, dtype)


def grpo_loss_sum(params, batch, cfg, clip_eps, dtype=jnp.float32):
    """Sum over masked target tokens of the clipped surrogate
    min(r A, clip(r, 1-e, 1+e) A), r = exp(logp - old logp), negated.
    ``batch``: tokens (B, L), mask / old (B, L) on target positions
    (mask[t] = 1 when token t is a model token), adv (B,)."""
    logits = forward(params, batch["tokens"], cfg, dtype)[:, :-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lp = jnp.take_along_axis(logp, batch["tokens"][:, 1:, None], -1)[..., 0]
    ratio = jnp.exp(lp - batch["old"][:, 1:])
    adv = batch["adv"][:, None]
    surr = jnp.minimum(ratio * adv,
                       jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv)
    return -jnp.sum(surr * batch["mask"][:, 1:])


def adamw(params, grads, state, step, opt):
    """Global-norm clipping, then AdamW (Loshchilov and Hutter) with bias
    correction; ``step`` counts from 1.  The moments stay float32 and each
    parameter keeps its own dtype."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / gnorm) \
        if opt["clip_norm"] > 0 else 1.0
    b1, b2 = opt["b1"], opt["b2"]

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** step)
        vh = v / (1 - b2 ** step)
        p32 = p.astype(jnp.float32)
        p32 = p32 - opt["lr"] * (mh / (jnp.sqrt(vh) + opt["eps"])
                                 + opt["weight_decay"] * p32)
        return p32.astype(p.dtype), m, v, g

    out = jax.tree_util.tree_map(upd, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), {"m": pick(1), "v": pick(2)}, pick(3)
