"""Flash attention Pallas TPU kernel: causal GQA with optional sliding window.

TPU adaptation (DESIGN.md §2): the FlashAttention-2 GPU algorithm re-blocked
for VMEM/MXU —
  * grid (batch, q_head, q_blocks, kv_blocks); the kv dim is innermost and
    TPU grids execute sequentially, so the online-softmax state (m, l, acc)
    lives in VMEM scratch that persists across kv iterations;
  * BlockSpecs tile q/k/v so each step holds (BQ,D) + (BK,D) tiles in VMEM,
    MXU-aligned (block sizes are multiples of 128 on the contracted dims);
  * GQA is expressed in the k/v index_map (q head h reads kv head h//G) —
    no repeat/gather materialization;
  * causal + sliding-window masking is applied per (q,kv) tile; fully-masked
    tiles short-circuit via pl.when (the TPU analogue of FA2's block skip).

Validated in interpret mode against kernels/ref.py::attention_ref.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, seq_len: int, window: int,
                  causal: bool, scale: float):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qb * block_q
    k_start = kb * block_k

    # tile-level reachability (any (q,k) pair in-range?)
    q_last = q_start + block_q - 1
    k_first = k_start
    reachable = True
    if causal:
        reachable = k_first <= q_last
    if window:
        # newest q must still see oldest useful k: k_last > q_first - window
        k_last = k_start + block_k - 1
        q_first = q_start
        reachable = jnp.logical_and(reachable, k_last > q_first - window) \
            if causal else (k_last > q_first - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale          # (BQ,D)
        k = k_ref[0, 0, :, :].astype(jnp.float32)                  # (BK,D)
        v = v_ref[0, 0, :, :].astype(jnp.float32)                  # (BK,D)
        # zero padded kv rows: 0 * garbage = NaN would poison p @ v
        col_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_len
        k = jnp.where(col_valid, k, 0.0)
        v = jnp.where(col_valid, v, 0.0)
        s = q @ k.T                                             # (BQ,BK)

        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = ki < seq_len
        if causal:
            mask &= ki <= qi
        if window:
            mask &= ki > qi - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                     # (BQ,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_cur

    @pl.when(kb == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, window: int = 0, causal: bool = True,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool | None = None):
    """q (B,S,H,D), k/v (B,S,Hk,D) -> (B,S,H,D).  ``interpret=None`` follows
    ``repro.kernels.interpret_mode``."""
    if interpret is None:
        interpret = kernels.interpret_mode()
    B, S, H, D = q.shape
    Hk = k.shape[2]
    G = H // Hk
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    n_q = pl.cdiv(S, block_q)
    n_k = pl.cdiv(S, block_k)
    scale = 1.0 / math.sqrt(D)

    # layout: (B, H, S, D) per-head blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_len=S,
        window=window, causal=causal, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qb, kb, G=G: (b, h // G, kb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qb, kb, G=G: (b, h // G, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
