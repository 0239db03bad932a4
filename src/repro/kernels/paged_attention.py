"""Paged decode attention Pallas TPU kernel: block-table K/V gather.

The serving-side twin of kernels/flash_attention.py for the paged cache
layout (models/attention.py): K/V for the whole batch live in one global
pool of ``block_size``-token blocks and each row addresses its blocks
through a block table.  A dense gather (``pool[table]``) would materialize
every row's K/V contiguously in HBM before attending — exactly the copy
paging exists to avoid.  Here the kernel walks the table itself, and only
as far as each row's length:

  * grid (batch, chunk): one step covers one row, all of its kv heads and
    ``P`` consecutive table slots (a chunk of ``P * bs`` positions).  ``P``
    follows from the shapes: the largest divisor of the table width ``T``
    with ``P * bs <= 512`` (``pages_per_step``).  A grid step costs about
    as much as moving a few blocks, so on a TPU v5e 512-position chunks
    beat 128 and 256 on short and long rows alike;
  * the pools stay in HBM and the kernel copies them itself: for each live
    table slot of a chunk one async copy moves ``pool[blk]``, the block's
    contiguous ``(Hk, bs, D)`` slab of every kv head, into a VMEM buffer.
    The table and ``q_pos`` are scalar-prefetch operands, so the copies are
    issued in table order with no gather in HBM;
  * live-bounded: a chunk that starts past ``q_pos`` issues neither a copy
    nor compute, and inside the last live chunk the slots past the row's
    last live block (``q_pos // bs``) are not copied either, so unallocated
    slots (-1) are never read.  Rows with ``q_pos < 0`` issue nothing;
  * double-buffered: while a chunk is computed, the next live chunk's
    copies run into the other buffer — the row's next chunk, or the first
    chunk of the next live row (``next_live``, computed outside);
  * heads folded: q for all kv heads, ``(Hk, G, D)``, meets the chunk's K
    head by head (a static loop), GQA folding a kv head's G query heads into
    the sublanes of a ``(G, P * bs)`` score tile.  The online-softmax state
    (m, l, acc) of every head persists across the chunk dimension in VMEM
    scratch, as in flash attention;
  * precision: scores, the running max and sum and the softmax weights are
    float32.  bf16 (or int8) keys meet a bf16 q on the MXU with float32
    accumulation, where every product is exact; the weights meet V in
    float32;
  * int8 pools carry per-(block, kv_head, slot) f32 scales; a block's
    scales for all kv heads arrive as one ``Hk * bs`` row beside its values
    (the wrapper views the scale pools as (N, 1, Hk*bs), since a copy of an
    (Hk, bs) tile is refused for lanes narrower than 128), and each head's
    span scales its scores (K) and its softmax weights (V), so the HBM read
    per cached token is halved relative to bf16.

Slot ``i`` of the block at table slot ``j`` holds absolute position
``j*bs + i`` by construction (models/attention.py writes position p to block
``p // bs``, offset ``p % bs``), so masking needs only the per-row query
position: positions <= q_pos are guaranteed to have been written by the
current occupant, and stale slots from a previous occupant always sit at
masked positions.  A buffer slot that no copy refilled holds an earlier
block's finite values (or the zeros the buffers start from), again only at
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
CHUNK_TOKENS = 512     # positions a grid step covers at most


def pages_per_step(block_size: int, n_table: int) -> int:
    """The largest divisor ``P`` of the table width with
    ``P * block_size <= CHUNK_TOKENS`` (1 if no larger one exists)."""
    return max(p for p in range(1, n_table + 1) if n_table % p == 0
               and (p == 1 or p * block_size <= CHUNK_TOKENS))


def _head_lanes(x, h: int, block_size: int):
    """Head ``h``'s scales of P pages, ``x`` (P, Hk*bs), as one (1, P*bs)
    row in page order, exactly.  Mosaic reshapes no sublanes into lanes, so
    a 0/1 matmul spreads each page's span of head h along the lanes and
    each page keeps its own span."""
    P, bs = x.shape[0], block_size
    width = P * bs
    src = jax.lax.broadcasted_iota(jnp.int32, (x.shape[1], width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[1], width), 1)
    spread = (src == h * bs + lane % bs).astype(x.dtype)
    tiled = jnp.dot(x, spread, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)      # (P, P*bs)
    own = (jax.lax.broadcasted_iota(jnp.int32, tiled.shape, 1) // bs
           == jax.lax.broadcasted_iota(jnp.int32, tiled.shape, 0))
    return jnp.sum(jnp.where(own, tiled, 0.0), axis=0, keepdims=True)


def _paged_kernel(table_ref, qpos_ref, next_ref, q_ref, k_hbm, v_hbm, *rest,
                  block_size: int, n_table: int, pages: int, n_blocks: int,
                  quantized: bool, dot_dtype):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, slot_ref,
         m_scr, l_scr, acc_scr) = rest
    else:
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
        o_ref, k_buf, v_buf, sem, slot_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    c = pl.program_id(1)
    n_chunks = n_table // pages
    chunk = pages * block_size
    Hk = k_buf.shape[2]

    pairs = [(k_hbm, k_buf), (v_hbm, v_buf)]
    if quantized:
        pairs += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]

    def copy_chunk(row, ch, slot, action):
        """``action`` ("start" or "wait") the copies of chunk ``ch`` of
        ``row`` into buffer ``slot``: one per table slot up to the row's
        last live block."""
        last = qpos_ref[row] // block_size
        for p in range(pages):
            j = ch * pages + p
            blk = table_ref[row * n_table + j]
            blk = jnp.where(blk < 0, n_blocks - 1, blk)

            @pl.when(j <= last)
            def _():
                for src, dst in pairs:
                    getattr(pltpu.make_async_copy(
                        src.at[blk], dst.at[slot, p], sem.at[slot]), action)()

    @pl.when((b == 0) & (c == 0))
    def _first():
        # slot_ref: the buffer slot of the next live step, and whether its
        # copies were started already.  Buffers start at zero, so a slot
        # that is never refilled holds finite values at masked positions.
        slot_ref[0] = 0
        slot_ref[1] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            ks_buf[...] = jnp.zeros_like(ks_buf)
            vs_buf[...] = jnp.zeros_like(vs_buf)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qpos_ref[b]

    @pl.when(c * chunk <= q_pos)
    def _compute():
        slot = slot_ref[0]

        @pl.when(slot_ref[1] == 0)
        def _():
            copy_chunk(b, c, slot, "start")

        # the next live chunk: this row's next, else the next live row's first
        more = (c + 1 < n_chunks) & ((c + 1) * chunk <= q_pos)
        nb = jnp.where(more, b, next_ref[b])
        nc = jnp.where(more, c + 1, 0)
        has_next = nb < pl.num_programs(0)

        @pl.when(has_next)
        def _():
            copy_chunk(nb, nc, 1 - slot, "start")

        slot_ref[0] = 1 - slot
        slot_ref[1] = has_next.astype(jnp.int32)
        copy_chunk(b, c, slot, "wait")

        kpos = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk), 1)
        mask = kpos <= q_pos                                 # (1, P*bs)
        scale = 1.0 / math.sqrt(q_ref.shape[-1])
        for h in range(Hk):
            q = q_ref[0, h].astype(dot_dtype)                # (G, D)
            k = k_buf[slot, :, h].astype(dot_dtype)          # (P, bs, D)
            k = k.reshape(chunk, k.shape[-1])
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, P*bs)
            if quantized:
                # a per-slot K scale scales that slot's score column
                s = s * _head_lanes(ks_buf[slot, :, 0], h, block_size)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h]                                # (G, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * _head_lanes(vs_buf[slot, :, 0], h, block_size)
            v = v_buf[slot, :, h].astype(jnp.float32)        # (P, bs, Dv)
            v = v.reshape(chunk, v.shape[-1])
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[h] = m_cur

    @pl.when(c == n_chunks - 1)
    def _flush():
        # rows that attended nothing (q_pos < 0: dead lane) never
        # accumulated — emit exact zeros, not acc/eps garbage
        l = l_scr[...]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0.0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_fwd(q, k_pool, v_pool, table, q_pos, *,
                        k_scale=None, v_scale=None,
                        interpret: bool | None = None):
    """Paged single-token decode attention.

    q (B,H,D) — the newest token's queries; k_pool (N,Hk,bs,D),
    v_pool (N,Hk,bs,Dv) — head-major global block pools whose last block is
    trash, so one block of all kv heads is a contiguous ``(Hk, bs, D)`` slab;
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
    P = pages_per_step(bs, T)
    quantized = k_scale is not None
    qh = q.reshape(B, Hk, G, D)
    q_pos = q_pos.astype(jnp.int32)
    table = table.astype(jnp.int32).reshape(-1)          # (B*T,) for prefetch
    # next_live[b]: the first row after b with q_pos >= 0, else B
    rows = jnp.arange(B, dtype=jnp.int32)
    later = jnp.where(q_pos >= 0, rows, B)
    next_live = jnp.flip(jax.lax.cummin(jnp.flip(later)))
    next_live = jnp.concatenate([next_live[1:], jnp.full((1,), B, jnp.int32)])
    # bf16 q meets bf16 or int8 keys (exact in bf16) in bf16 on the MXU,
    # with float32 accumulation; anything else in float32
    exact_bf16 = (q.dtype == jnp.bfloat16
                  and k_pool.dtype in (jnp.bfloat16, jnp.int8))
    dot_dtype = jnp.bfloat16 if exact_bf16 else jnp.float32

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, Hk, G, D), lambda b, c, *_: (b, 0, 0, 0)),
                hbm, hbm]
    operands = [qh, k_pool, v_pool]
    scratch = [pltpu.VMEM((2, P, Hk, bs, D), k_pool.dtype),
               pltpu.VMEM((2, P, Hk, bs, Dv), v_pool.dtype)]
    if quantized:
        in_specs += [hbm, hbm]
        # a block's scales of every kv head as one row: (N, 1, Hk*bs)
        operands += [x.astype(jnp.float32).reshape(N, 1, Hk * bs)
                     for x in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, P, 1, Hk * bs), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((2,), jnp.int32),         # buffer slot, copies started
        pltpu.VMEM((Hk, G, 1), jnp.float32),
        pltpu.VMEM((Hk, G, 1), jnp.float32),
        pltpu.VMEM((Hk, G, Dv), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, T // P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hk, G, Dv),
                               lambda b, c, *_: (b, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_paged_kernel, block_size=bs, n_table=T,
                               pages=P, n_blocks=N, quantized=quantized,
                               dot_dtype=dot_dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, Dv), q.dtype),
        # the steps run in order: each one's copies start in the step before
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        # on the CPU, Pallas's TPU interpreter: it runs the copies and
        # semaphores, and fills never-written VMEM with NaN
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",     # the kernel's name in a profile
    )(table, q_pos, next_live, *operands)
    return out.reshape(B, H, Dv)
