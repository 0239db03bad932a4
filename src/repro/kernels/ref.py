"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are intentionally the *simplest possible* formulations (naive softmax
attention, sequential SSM recurrence, full log-softmax) — independent of both
the kernels and the model-path implementations they accelerate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, window: int = 0, causal: bool = True):
    """q (B,S,H,D), k/v (B,S,Hk,D) -> (B,S,H,D).  GQA by head folding."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    G = H // Hk
    qh = q.reshape(B, S, Hk, G, D)
    scores = jnp.einsum("bqkgd,bmkd->bkgqm", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(D).astype(jnp.float32)
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= ki <= qi
    if window:
        mask &= ki > qi - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqm,bmkd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, table, q_pos):
    """Paged single-token decode attention by dense gather (ground truth for
    kernels/paged_attention.py).

    q (B,H,D); k_pool (N,Hk,bs,D) / v_pool (N,Hk,bs,Dv) global block pools
    (last block = trash); table (B,T) int32 (-1 = unallocated); q_pos (B,)
    the query's absolute position.  Slot i of table slot j holds position
    j*bs+i, so the mask is simply pos <= q_pos (unallocated slots gather the
    trash block but sit beyond q_pos for any live row).  Returns (B,H,Dv).
    """
    B, H, D = q.shape
    N, Hk, bs, _ = k_pool.shape
    T = table.shape[1]
    G = H // Hk
    ids = jnp.where(table < 0, N - 1, table)                  # (B,T)
    k = k_pool[ids].transpose(0, 2, 1, 3, 4).reshape(B, Hk, T * bs, D)
    v = v_pool[ids].transpose(0, 2, 1, 3, 4).reshape(B, Hk, T * bs,
                                                     v_pool.shape[-1])
    pos = (jnp.arange(T)[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
    mask = pos[None, :] <= q_pos[:, None]                     # (B, T*bs)
    qh = q.reshape(B, Hk, G, D).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bkmd->bkgm", qh, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(D).astype(jnp.float32)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgm,bkmv->bkgv", w, v.astype(jnp.float32))
    return out.reshape(B, H, v_pool.shape[-1]).astype(q.dtype)


def ssd_ref(x, dt, A_log, Bm, Cm, D=None, init_state=None):
    """Sequential (step-by-step) SSM recurrence — the simplest correct SSD.

    x (B,S,H,P), dt (B,S,H) post-softplus, Bm/Cm (B,S,G,N).
    Returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    f32 = jnp.float32
    A = -jnp.exp(A_log.astype(f32))                      # (H,)
    Bh = jnp.repeat(Bm.astype(f32), rep, axis=2)         # (B,S,H,N)
    Ch = jnp.repeat(Cm.astype(f32), rep, axis=2)
    h0 = (jnp.zeros((Bsz, H, P, N), f32) if init_state is None
          else init_state.astype(f32))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp                        # (B,H,P),(B,H),(B,H,N),(B,H,N)
        dA = jnp.exp(dt_t * A[None, :])                  # (B,H)
        h = h * dA[..., None, None] + (x_t * dt_t[..., None])[..., None] \
            * b_t[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, c_t)
        return h, y

    xs = (jnp.moveaxis(x.astype(f32), 1, 0), jnp.moveaxis(dt.astype(f32), 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)                           # (B,S,H,P)
    if D is not None:
        y = y + x.astype(f32) * D.astype(f32)[None, None, :, None]
    return y, h_final


def token_logprob_ref(logits, labels):
    """logits (B,S,V), labels (B,S) -> logprob of labels, (B,S) f32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
