"""Paged decode attention Pallas TPU kernel: block-table K/V gather.

The serving-side twin of kernels/flash_attention.py for the paged cache
layout (models/attention.py): K/V for the whole batch live in one global
pool of ``block_size``-token blocks and each row addresses its blocks
through a block table.  A dense gather (``pool[table]``) would materialize
every row's K/V contiguously in HBM before attending — exactly the copy
paging exists to avoid.  Here the *grid itself* walks the table:

  * grid (batch, kv_head, table_slot); the table is a scalar-prefetch
    operand, so the k/v BlockSpec ``index_map`` resolves ``table[b, j]`` to
    a physical pool block and the DMA engine fetches blocks in table order —
    the gather costs zero extra HBM traffic;
  * unallocated table slots (-1) map to the pool's trash block (last index)
    and their compute is skipped via ``pl.when`` on the row's length;
  * one q vector per row attends all blocks of its row (decode: q is the
    newest token); GQA folds the G query heads of one kv head into the
    sublane dim so the (G, bs) score tile feeds the MXU;
  * online-softmax state (m, l, acc) persists across the sequentially
    executed table_slot dimension in VMEM scratch, as in flash attention;
  * int8 pools carry per-(block, kv_head, slot) f32 scales alongside the
    values; a block's scales for all kv heads arrive as one (Hk, bs) tile
    and the inner loop applies this head's row to the scores (K) and the
    softmax weights (V), so the HBM read per cached token is halved
    relative to bf16 and quartered vs f32;
  * every block's last two dims are either (8, 128)-aligned or whole array
    dims, as Mosaic requires: the pools are head-major (N, Hk, bs, D), so a
    K/V block is one head's (bs, D) tile, and the softmax state is kept 2-D.

Slot ``i`` of the block at table slot ``j`` holds absolute position
``j*bs + i`` by construction (models/attention.py writes position p to block
``p // bs``, offset ``p % bs``), so masking needs only the per-row query
position: positions <= q_pos are guaranteed to have been written by the
current occupant, and stale slots from a previous occupant always sit at
masked positions.  Rows with ``q_pos < 0`` (dead/padded lanes) compute no
block at all and emit exact zeros.

Validated in interpret mode against kernels/ref.py::paged_attention_ref, and
compiled for a described TPU v5e by tests/test_tpu_compile.py.
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


def _paged_kernel(table_ref, qpos_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, n_table: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    hk = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qpos_ref[b]

    @pl.when(j * block_size <= q_pos)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0, :, :].astype(jnp.float32)            # (bs, D)
        v = v_ref[0, 0, :, :].astype(jnp.float32)            # (bs, Dv)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = (q * scale) @ k.T                                # (G, bs)
        if quantized:
            # per-slot scales of this kv head, (1, bs); a per-slot K scale
            # scales that slot's score column, a V scale its softmax weight
            s = s * ks_ref[0, pl.ds(hk, 1), :]
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos <= q_pos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                  # (G, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, pl.ds(hk, 1), :]
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_cur

    @pl.when(j == n_table - 1)
    def _flush():
        # rows whose every slot was masked (q_pos < 0: dead lane, all-trash
        # table) never accumulated — emit exact zeros, not acc/eps garbage
        l = l_scr[...]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0.0, out, 0.0)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def paged_attention_fwd(q, k_pool, v_pool, table, q_pos, *,
                        k_scale=None, v_scale=None,
                        interpret: bool | None = None):
    """Paged single-token decode attention.

    q (B,H,D) — the newest token's queries; k_pool (N,Hk,bs,D),
    v_pool (N,Hk,bs,Dv) — head-major global block pools whose last block is
    trash, so one kv head's block is a ``(bs, D)`` tile;
    table (B,T) int32 block table (-1 = unallocated); q_pos (B,) int32 —
    each row's query position (the row's cache holds positions
    ``0..q_pos`` inclusive; ``q_pos < 0`` => dead row, output is exact
    zeros).  int8 pools pass ``k_scale``/``v_scale`` (N,Hk,bs) f32
    per-slot dequant scales.  ``interpret=None`` follows
    ``repro.kernels.interpret_mode``.  Returns (B,H,Dv) in q's dtype.
    """
    if interpret is None:
        interpret = kernels.interpret_mode()
    B, H, D = q.shape
    N, Hk, bs, _ = k_pool.shape
    Dv = v_pool.shape[-1]
    T = table.shape[1]
    G = H // Hk
    quantized = k_scale is not None
    qh = q.reshape(B, Hk, G, D)
    table = table.astype(jnp.int32).reshape(-1)          # (B*T,) for prefetch

    def kv_map(b, hk, j, table_ref, qpos_ref):
        blk = table_ref[b * T + j]
        return (jnp.where(blk < 0, N - 1, blk), hk, 0, 0)

    def scale_map(b, hk, j, table_ref, qpos_ref):
        blk = table_ref[b * T + j]
        return (jnp.where(blk < 0, N - 1, blk), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, G, D), lambda b, hk, j, *_: (b, hk, 0, 0)),
        pl.BlockSpec((1, 1, bs, D), kv_map),
        pl.BlockSpec((1, 1, bs, Dv), kv_map),
    ]
    operands = [qh, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, Hk, bs), scale_map),
                     pl.BlockSpec((1, Hk, bs), scale_map)]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hk, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, hk, j, *_: (b, hk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, block_size=bs, n_table=T,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, Dv), q.dtype),
        interpret=interpret,
    )(table, q_pos.astype(jnp.int32), *operands)
    return out.reshape(B, H, Dv)
