"""jit'd public wrappers for the Pallas kernels.

Each kernel compiles on a TPU and runs in interpret mode on the CPU (the
kernel body executes as traced jnp ops); ``repro.kernels.interpret_mode`` is
the one place that decides.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.paged_attention import paged_attention_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.kernels.token_logprob import fused_token_logprob_fwd


@functools.partial(jax.jit, static_argnames=("window", "causal", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, window: int = 0, causal: bool = True,
                    block_q: int = 128, block_k: int = 128):
    """Causal GQA flash attention. q (B,S,H,D), k/v (B,S,Hk,D) -> (B,S,H,D)."""
    return flash_attention_fwd(q, k, v, window=window, causal=causal,
                               block_q=block_q, block_k=block_k)


@jax.jit
def paged_attention(q, k_pool, v_pool, table, q_pos, k_scale=None,
                    v_scale=None):
    """Paged single-token decode attention over a block-table KV pool.

    q (B,H,D), head-major k_pool/v_pool (N,Hk,bs,·) with trash block last,
    table (B,T) int32, q_pos (B,) int32 -> (B,H,Dv).  The block table is a
    scalar-prefetch operand, so K/V blocks stream from HBM in table order
    with no gather copy.  int8 pools pass per-slot f32 ``k_scale``/``v_scale``
    (N,Hk,bs); the kernel dequantizes in its inner loop.
    """
    return paged_attention_fwd(q, k_pool, v_pool, table, q_pos,
                               k_scale=k_scale, v_scale=v_scale)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A_log, Bm, Cm, chunk: int = 64, D=None):
    """Mamba2 SSD chunked scan. Returns (y, final_state)."""
    return ssd_scan_fwd(x, dt, A_log, Bm, Cm, chunk=chunk, D=D)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_v"))
def fused_token_logprob(logits, labels, block_rows: int = 256,
                        block_v: int = 2048):
    """Streaming log p(label) without materializing log-softmax."""
    return fused_token_logprob_fwd(logits, labels, block_rows=block_rows,
                                   block_v=block_v)
