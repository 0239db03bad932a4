"""Attention: GQA (qk-norm / qkv-bias / sliding-window) and MLA (DeepSeek-V2).

One ``apply`` covers train (full-seq causal), prefill (full-seq causal +
returns a filled cache) and decode (q_len tokens against a cache).  Caches are
plain dicts (pytree-friendly; dry-runnable as ShapeDtypeStructs) in one of two
layouts:

contiguous (one lane per batch row)::

  GQA : {"k": (B,M,Hk,D), "v": (B,M,Hk,Dv), "pos": (B,M) int32}
  MLA : {"ckv": (B,M,R), "krope": (B,M,Dr), "pos": (B,M) int32}

paged (vLLM-style global block pool + per-row block table; the ``table`` key
marks the layout)::

  GQA : {"k": (N+1,Hk,bs,D), "v": (N+1,Hk,bs,Dv), "pos": (N+1,bs),
         "table": (B,T) int32}
  MLA : {"ckv": (N+1,bs,R), "krope": (N+1,bs,Dr), "pos": (N+1,bs),
         "table": (B,T) int32}

Block ``table[b, j]`` names the pool block holding row ``b``'s absolute
positions ``[j*bs, (j+1)*bs)``; -1 = unallocated.  The last pool block
(index N) is the *trash block*: writes for invalid positions (right-pads,
inactive decode rows) are routed there so they can never corrupt a live
row's block, and -1 table entries gather it (its ``pos`` is always -1, so
it is never attended).  GQA pools are head-major so that one kv head's
block is a contiguous ``(bs, D)`` tile, the block the decode kernel fetches
(kernels/paged_attention.py).  Block allocation itself is host-side
(serving.engine.BlockAllocator); this module only scatters/gathers through
the table.  Paged layout requires window=0 (full attention).

``pos`` holds the absolute position stored in each slot (-1 = empty); sliding
windows use a ring buffer (slot = pos % window) which keeps the long-context
decode cache O(window) — this is the sub-quadratic variant used by long_500k.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_hint
from repro.models.layers import apply_rope, rmsnorm, rmsnorm_specs, rope_angles
from repro.models.params import ParamSpec

NEG_INF = -1e30


# ---------------------------------------------------------------- specs
def attention_specs(cfg) -> dict:
    d = cfg.d_model
    if cfg.uses_mla:
        specs = {
            "kv_a": ParamSpec((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                              ("embed_p", "kv_lora"), init="scaled"),
            "kv_a_norm": rmsnorm_specs(cfg.kv_lora_rank)["scale"],
            "k_b": ParamSpec((cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim),
                             ("kv_lora", "heads", None), init="scaled"),
            "v_b": ParamSpec((cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim),
                             ("kv_lora", "heads", None), init="scaled"),
            "o": ParamSpec((cfg.n_heads, cfg.v_head_dim, d),
                           ("heads", None, "embed_p"), init="scaled",
                           fan_in_axes=(0, 1)),
        }
        qd = cfg.qk_nope_dim + cfg.qk_rope_head_dim
        if cfg.q_lora_rank:
            specs["q_a"] = ParamSpec((d, cfg.q_lora_rank), ("embed_p", None), init="scaled")
            specs["q_a_norm"] = rmsnorm_specs(cfg.q_lora_rank)["scale"]
            specs["q_b"] = ParamSpec((cfg.q_lora_rank, cfg.n_heads, qd),
                                     (None, "heads", None), init="scaled")
        else:
            specs["q"] = ParamSpec((d, cfg.n_heads, qd), ("embed_p", "heads", None),
                                   init="scaled")
        return specs

    hd, vd = cfg.head_dim, cfg.v_dim
    specs = {
        "q": ParamSpec((d, cfg.n_heads, hd), ("embed_p", "heads", None), init="scaled"),
        "k": ParamSpec((d, cfg.n_kv_heads, hd), ("embed_p", "kv_heads", None), init="scaled"),
        "v": ParamSpec((d, cfg.n_kv_heads, vd), ("embed_p", "kv_heads", None), init="scaled"),
        "o": ParamSpec((cfg.n_heads, vd, d), ("heads", None, "embed_p"),
                       init="scaled", fan_in_axes=(0, 1)),
    }
    if cfg.qkv_bias:
        specs["q_bias"] = ParamSpec((cfg.n_heads, hd), ("heads", None), init="zeros")
        specs["k_bias"] = ParamSpec((cfg.n_kv_heads, hd), ("kv_heads", None), init="zeros")
        specs["v_bias"] = ParamSpec((cfg.n_kv_heads, vd), ("kv_heads", None), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = rmsnorm_specs(hd)["scale"]
        specs["k_norm"] = rmsnorm_specs(hd)["scale"]
    return specs


def cross_attention_specs(cfg) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "q": ParamSpec((d, cfg.n_heads, hd), ("embed_p", "heads", None), init="scaled"),
        "k": ParamSpec((d, cfg.n_kv_heads, hd), ("embed_p", "kv_heads", None), init="scaled"),
        "v": ParamSpec((d, cfg.n_kv_heads, hd), ("embed_p", "kv_heads", None), init="scaled"),
        "o": ParamSpec((cfg.n_heads, hd, d), ("heads", None, "embed_p"),
                       init="scaled", fan_in_axes=(0, 1)),
    }


# ---------------------------------------------------------------- caches
def init_kv_cache(cfg, batch: int, max_len: int, window: int = 0) -> dict:
    m = min(max_len, window) if window else max_len
    dt = cfg.activation_dtype
    pos = jnp.full((batch, m), -1, jnp.int32)
    if cfg.uses_mla:
        return {
            "ckv": jnp.zeros((batch, m, cfg.kv_lora_rank), dt),
            "krope": jnp.zeros((batch, m, cfg.qk_rope_head_dim), dt),
            "pos": pos,
        }
    return {
        "k": jnp.zeros((batch, m, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, m, cfg.n_kv_heads, cfg.v_dim), dt),
        "pos": pos,
    }


def kv_cache_specs(cfg, batch: int, max_len: int, window: int = 0) -> dict:
    """ShapeDtypeStruct cache stand-ins for the dry-run."""
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, batch, max_len, window))
    return cache


def init_paged_kv_cache(cfg, num_blocks: int, block_size: int, batch: int,
                        max_blocks_per_row: int,
                        kv_dtype: str = "fp") -> dict:
    """Paged cache for one attention layer: ``num_blocks`` allocatable pool
    blocks + 1 trash block, and a (batch, max_blocks_per_row) block table
    initialized to -1 (unallocated).

    ``kv_dtype="int8"`` stores pool values as int8 with per-(block[,
    kv_head], slot) f32 absmax scales in ``<leaf>_scale`` companions — half
    the bytes per cached token vs bf16 (quarter vs f32), so the same HBM
    holds twice the blocks.  Values are quantized on cache write and
    dequantized on read (kernel inner loop / gather); fp stays the default
    and the accuracy oracle.
    """
    n = num_blocks + 1                       # last block = trash
    dt = cfg.activation_dtype
    quant = kv_dtype == "int8"
    if kv_dtype not in ("fp", "int8"):
        raise ValueError(f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r}")
    pos = jnp.full((n, block_size), -1, jnp.int32)
    table = jnp.full((batch, max_blocks_per_row), -1, jnp.int32)
    if cfg.uses_mla:
        cache = {
            "ckv": jnp.zeros((n, block_size, cfg.kv_lora_rank), dt),
            "krope": jnp.zeros((n, block_size, cfg.qk_rope_head_dim), dt),
            "pos": pos,
            "table": table,
        }
        if quant:
            for name in ("ckv", "krope"):
                cache[name] = cache[name].astype(jnp.int8)
                cache[name + "_scale"] = jnp.zeros((n, block_size),
                                                   jnp.float32)
        return cache
    cache = {
        "k": jnp.zeros((n, cfg.n_kv_heads, block_size, cfg.head_dim), dt),
        "v": jnp.zeros((n, cfg.n_kv_heads, block_size, cfg.v_dim), dt),
        "pos": pos,
        "table": table,
    }
    if quant:
        for name in ("k", "v"):
            cache[name] = cache[name].astype(jnp.int8)
            cache[name + "_scale"] = jnp.zeros(
                (n, cfg.n_kv_heads, block_size), jnp.float32)
    return cache


def _scatter_cache(buf: jax.Array, new: jax.Array, slots: jax.Array) -> jax.Array:
    """buf (B,M,...), new (B,Q,...), slots (B,Q) int32 -> buf with rows written."""
    b_idx = jnp.arange(buf.shape[0])[:, None]
    return buf.at[b_idx, slots].set(new.astype(buf.dtype))


INT8_QMAX = 127.0


def _quantize_int8(new: jax.Array) -> tuple:
    """(B,Q,...,F) f values -> (int8 values, f32 scales (B,Q,...)).

    Symmetric per-token absmax over the feature dim: scale = max|x|/127,
    so dequant error per element is bounded by scale/2."""
    amax = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1)
    scale = amax / INT8_QMAX
    q = jnp.round(new.astype(jnp.float32) / jnp.maximum(scale, 1e-12)[..., None])
    return q.astype(jnp.int8), scale


def _paged_scatter(cache: dict, kv_leaves: dict, positions: jax.Array,
                   kv_valid) -> dict:
    """Scatter new tokens through the block table into the pool.

    ``kv_leaves`` maps leaf name -> (B,Q,...) new values; per-head values
    (B,Q,Hk,D) land in a head-major pool (N,Hk,bs,D).  Writes for
    invalid entries (``kv_valid`` False or an unallocated table slot) go to
    the trash block — the last pool block, which no table ever references
    with a valid id — so a pad can never touch a live block.  int8 pools
    (marked by a ``<leaf>_scale`` companion) quantize on write.

    Prefix-sharing contract: this scatter writes through whatever mapping
    the table holds and must NEVER be handed a *shared* one (a block
    refcounted into several rows' tables) — the serving engine's host-side
    copy-on-write barrier (``GenerationEngine._cow_range``) remaps the row
    to a private slab copy *before* the device step that reaches here.
    Reads (`_paged_gather` and the Pallas kernel) go through the table
    unchanged: sharing is invisible to them by construction.
    """
    bs = cache["pos"].shape[1]
    trash = cache["pos"].shape[0] - 1
    table = cache["table"]                                   # (B, T)

    blk = jnp.clip(positions, 0, table.shape[1] * bs - 1) // bs
    off = positions % bs
    ids = jnp.take_along_axis(table, blk, axis=1)            # (B, Q)
    valid = jnp.ones(positions.shape, bool) if kv_valid is None else kv_valid
    valid = valid & (ids >= 0)
    ids_w = jnp.where(valid, ids, trash)
    store_pos = jnp.where(valid, positions, -1)

    new_cache = dict(cache)
    for name, new in kv_leaves.items():
        at = ((ids_w, slice(None), off) if new.ndim == 4 else (ids_w, off))
        if name + "_scale" in cache:
            qv, sc = _quantize_int8(new)
            new_cache[name] = cache[name].at[at].set(qv)
            new_cache[name + "_scale"] = cache[name + "_scale"].at[at].set(sc)
        else:
            new_cache[name] = cache[name].at[at].set(
                new.astype(cache[name].dtype))
    new_cache["pos"] = cache["pos"].at[ids_w, off].set(store_pos)
    return new_cache


def _paged_gather(cache: dict, names, out_dtype) -> tuple:
    """Gather per-row K/V views through the block table.

    Returns ``(gathered, k_pos)`` where ``gathered[name]`` is the row-major
    (B, T*bs, ...) view of the pool through the table and ``k_pos`` is the
    matching (B, T*bs) absolute-position array (-1 = empty/never attend).
    int8 leaves dequantize through their ``<leaf>_scale`` companion.

    The gather materializes each row's K/V contiguously per call —
    XLA-friendly and exact, but per-step HBM traffic still scales with
    table width.  The GQA decode path on TPU uses kernels/paged_attention.py
    (ops.paged_attention) instead, which streams pool blocks via a
    scalar-prefetched table with no gather copy.  This gather serves
    prefill, MLA (its only implementation), and GQA decode on the CPU,
    where it is the kernel's parity oracle.
    """
    table = cache["table"]
    B = table.shape[0]
    trash = cache["pos"].shape[0] - 1
    gather_ids = jnp.where(table < 0, trash, table)          # (B, T)
    gathered = {}
    for name in names:
        g = cache[name][gather_ids]                  # (B, T, [Hk,] bs, F)
        if name + "_scale" in cache:
            sc = cache[name + "_scale"][gather_ids]  # (B, T, [Hk,] bs)
            g = g.astype(jnp.float32) * sc[..., None]
        if g.ndim == 5:                      # head-major: to (B,T,bs,Hk,F)
            g = g.swapaxes(2, 3)
        gathered[name] = g.reshape((B, -1) + g.shape[3:]).astype(out_dtype)
    k_pos = cache["pos"][gather_ids].reshape(B, -1)          # (B, T*bs)
    return gathered, k_pos


def _paged_update(cache: dict, kv_leaves: dict, positions: jax.Array,
                  kv_valid) -> tuple:
    """Scatter new tokens, then gather per-row K/V: the pure-JAX paged
    decode path.  Returns ``(new_cache, gathered, k_pos)``."""
    any_leaf = next(iter(kv_leaves.values()))
    new_cache = _paged_scatter(cache, kv_leaves, positions, kv_valid)
    gathered, k_pos = _paged_gather(new_cache, list(kv_leaves),
                                    any_leaf.dtype)
    return new_cache, gathered, k_pos


# ---------------------------------------------------------------- blockwise attn
def blockwise_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                        causal: bool = True, block_q: int = 512,
                        block_k: int = 1024, scale: float = 1.0,
                        unroll: bool = False, accum_dtype=jnp.float32):
    """Memory-O(block) attention in pure JAX (online softmax over kv tiles).

    Flat-head layout (GQA pre-expanded so the head dim shards over "model"):
      q (B,Q,H,D), k/v (B,M,H,D), q_pos (B,Q), k_pos (B,M) -> (B,Q,H,Dv).

    This is the XLA-compilable twin of kernels/flash_attention.py — used by
    train/prefill at long sequence lengths where materializing (Q,M) scores
    cannot fit HBM.  ``unroll`` replaces the scans with python loops for the
    dry-run cost extrapolation (no `while` in the HLO).
    """
    B, Q, H, D = q.shape
    M = k.shape[1]
    Dv = v.shape[-1]
    block_q = min(block_q, Q)
    block_k = min(block_k, M)
    pad_q = (-Q) % block_q
    pad_k = (-M) % block_k
    f32 = jnp.float32
    adt = jnp.dtype(accum_dtype)   # dtype of the big q/k/v/p tiles

    qt = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    qp = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    kp = jnp.pad(k_pos, ((0, 0), (0, pad_k)), constant_values=-1)
    nQ, nK = (Q + pad_q) // block_q, (M + pad_k) // block_k

    qb = qt.reshape(B, H, nQ, block_q, D)
    kb = kt.reshape(B, H, nK, block_k, D)
    vb = vt.reshape(B, H, nK, block_k, Dv)
    qpb = qp.reshape(B, nQ, block_q)
    kpb = kp.reshape(B, nK, block_k)

    def q_block(q_cur, qp_cur):
        def kv_step(carry, j):
            m_run, l_run, acc = carry
            k_j = kb[:, :, j].astype(adt)                   # (B,H,BK,D)
            v_j = vb[:, :, j].astype(adt)
            kp_j = kpb[:, j]                                # (B,BK)
            # scores + softmax state stay f32 (numerics); tiles in adt
            s = jnp.einsum("bhqd,bhkd->bhqk", q_cur.astype(adt), k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = (kp_j >= 0)[:, None, None, :]
            if causal:
                mask &= kp_j[:, None, None, :] <= qp_cur[:, None, :, None]
            if window:
                mask &= (kp_j[:, None, None, :]
                         > qp_cur[:, None, :, None] - window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkv->bhqv", p.astype(adt), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        init = (jnp.full((B, H, block_q), NEG_INF, f32),
                jnp.zeros((B, H, block_q), f32),
                jnp.zeros((B, H, block_q, Dv), f32))
        if unroll:
            carry = init
            for j in range(nK):
                carry, _ = kv_step(carry, j)
        else:
            carry, _ = jax.lax.scan(kv_step, init, jnp.arange(nK))
        m_f, l_f, acc = carry
        return acc / jnp.maximum(l_f, 1e-30)[..., None]

    if unroll:
        outs = [q_block(qb[:, :, i].astype(f32), qpb[:, i]) for i in range(nQ)]
        out = jnp.stack(outs, axis=2)                       # (B,H,nQ,BQ,Dv)
    else:
        def q_step(_, xs):
            q_i, qp_i = xs
            return None, q_block(q_i.astype(f32), qp_i)
        _, out = jax.lax.scan(
            q_step, None,
            (jnp.moveaxis(qb, 2, 0), jnp.moveaxis(qpb, 1, 0)))
        out = jnp.moveaxis(out, 0, 2)                       # (B,H,nQ,BQ,Dv)
    out = out.reshape(B, H, Q + pad_q, Dv)[:, :, :Q]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------- core attn math
def _sdpa(q, k, v, mask, scale):
    """q (B,Hk,G,Q,D) k (B,Hk,M,D) v (B,Hk,M,Dv) mask (B,1,1,Q,M) -> (B,Hk,G,Q,Dv)."""
    scores = jnp.einsum("bkgqd,bkmd->bkgqm", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqm,bkmv->bkgqv", w, v)


def _causal_mask(q_pos, k_pos, window: int):
    """q_pos (B,Q), k_pos (B,M) -> (B,1,1,Q,M) bool."""
    q_ = q_pos[:, None, None, :, None]
    k_ = k_pos[:, None, None, None, :]
    mask = (k_ <= q_) & (k_ >= 0)
    if window:
        mask &= k_ > q_ - window
    return mask


# ---------------------------------------------------------------- GQA apply
def gqa_apply(params, cfg, x, positions, cache=None, window: int = 0,
              causal: bool = True, use_flash: bool = False, kv_valid=None,
              paged_kernel: bool = False):
    """x (B,Q,d), positions (B,Q).  Returns (out, new_cache).

    ``kv_valid`` (B,Q) bool marks right-pad positions in ragged rollout
    batches: invalid positions are stored with pos=-1 (never attended).

    ``paged_kernel`` routes single-token paged decode (Q==1, "table" cache)
    through the Pallas block-table kernel (kernels/paged_attention.py)
    instead of the dense pool gather.  The kernel compiles on TPU and runs
    in interpret mode on the CPU (``repro.kernels.interpret_mode``).
    """
    B, Q, _ = x.shape
    H, Hk, hd, vd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.v_dim
    G = H // Hk
    dt = x.dtype

    q = jnp.einsum("bqd,dhe->bqhe", x, params["q"].astype(dt))
    k = jnp.einsum("bqd,dhe->bqhe", x, params["k"].astype(dt))
    v = jnp.einsum("bqd,dhe->bqhe", x, params["v"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["q_bias"].astype(dt)
        k = k + params["k_bias"].astype(dt)
        v = v + params["v_bias"].astype(dt)
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": params["k_norm"]}, k, cfg.norm_eps)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = shard_hint(q, ("batch", "seq", "heads", None))
    k = shard_hint(k, ("batch", "seq", "kv_heads", None))

    new_cache = None
    if cache is not None and "table" in cache:
        new_cache = _paged_scatter(cache, {"k": k, "v": v}, positions,
                                   kv_valid)
        if paged_kernel and causal and Q == 1:
            # decode hot path: stream pool blocks through the Pallas
            # block-table kernel — no dense gather copy.  Dead rows
            # (kv_valid False) pass q_pos=-1 and emit exact zeros.
            from repro.kernels.ops import paged_attention
            q_pos = positions[:, 0]
            if kv_valid is not None:
                q_pos = jnp.where(kv_valid[:, 0], q_pos, -1)
            outv = paged_attention(
                q[:, 0], new_cache["k"], new_cache["v"], new_cache["table"],
                q_pos, k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"))
            out = outv[:, None].astype(dt)                   # (B,1,H,vd)
            out = jnp.einsum("bqhe,hed->bqd", out, params["o"].astype(dt))
            return shard_hint(out, ("batch", "seq", "embed")), new_cache
        gathered, k_pos = _paged_gather(new_cache, ("k", "v"), dt)
        k_all, v_all = gathered["k"], gathered["v"]
    elif cache is not None:
        M = cache["k"].shape[1]
        slots = positions % M
        store_pos = (positions if kv_valid is None
                     else jnp.where(kv_valid, positions, -1))
        ck = _scatter_cache(cache["k"], k, slots)
        cv = _scatter_cache(cache["v"], v, slots)
        cpos = cache["pos"].at[jnp.arange(B)[:, None], slots].set(store_pos)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        k_all, v_all, k_pos = ck, cv, cpos
    else:
        k_pos = (positions if kv_valid is None
                 else jnp.where(kv_valid, positions, -1))
        k_all, v_all = k, v

    if use_flash and cache is None and causal and kv_valid is None:
        from repro.kernels.ops import flash_attention
        out = flash_attention(q, k_all, v_all, window=window)
    elif cache is None and causal and Q > 1024:
        # long-sequence train/prefill: blockwise (online-softmax) attention —
        # materializing (Q,M) scores would not fit HBM at 4k-32k
        k_exp = jnp.repeat(k_all, G, axis=2)                # (B,M,H,hd)
        v_exp = jnp.repeat(v_all, G, axis=2)
        k_exp = shard_hint(k_exp, ("batch", "seq", "heads", None))
        v_exp = shard_hint(v_exp, ("batch", "seq", "heads", None))
        out = blockwise_attention(q, k_exp, v_exp, positions, k_pos,
                                  window=window, causal=True,
                                  scale=1.0 / math.sqrt(hd),
                                  block_q=cfg.attn_block_q,
                                  block_k=cfg.attn_block_k,
                                  unroll=cfg.unroll_scans,
                                  accum_dtype=jnp.dtype(cfg.accum_dtype))
    else:
        mask = (_causal_mask(positions, k_pos, window) if causal else
                (k_pos[:, None, None, None, :] >= 0))
        qh = q.reshape(B, Q, Hk, G, hd).transpose(0, 2, 3, 1, 4)
        out = _sdpa(qh, k_all.transpose(0, 2, 1, 3), v_all.transpose(0, 2, 1, 3),
                    mask, 1.0 / math.sqrt(hd))
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, Q, H, vd)

    out = jnp.einsum("bqhe,hed->bqd", out, params["o"].astype(dt))
    return shard_hint(out, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------- MLA apply
def mla_apply(params, cfg, x, positions, cache=None, window: int = 0,
              kv_valid=None):
    """DeepSeek-V2 multi-head latent attention.

    Train/prefill: expanded path (materialize per-head K/V from the latent).
    Decode (q_len small w/ cache): absorbed path — queries are mapped into the
    latent space so attention reads the compressed cache directly.
    """
    B, Q, _ = x.shape
    H = cfg.n_heads
    nd, rd, vd, R = cfg.qk_nope_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dt = x.dtype
    scale = 1.0 / math.sqrt(nd + rd)

    # ---- queries
    if cfg.q_lora_rank:
        qa = rmsnorm({"scale": params["q_a_norm"]}, x @ params["q_a"].astype(dt),
                     cfg.norm_eps)
        q = jnp.einsum("bqr,rhe->bqhe", qa, params["q_b"].astype(dt))
    else:
        q = jnp.einsum("bqd,dhe->bqhe", x, params["q"].astype(dt))
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    cos, sin = rope_angles(positions, rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    # ---- compressed kv
    kv = x @ params["kv_a"].astype(dt)
    ckv, k_rope = kv[..., :R], kv[..., R:]
    ckv = rmsnorm({"scale": params["kv_a_norm"]}, ckv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]  # shared head

    new_cache = None
    if cache is not None and "table" in cache:
        new_cache, gathered, k_pos = _paged_update(
            cache, {"ckv": ckv, "krope": k_rope}, positions, kv_valid)
        ckv_all, krope_all = gathered["ckv"], gathered["krope"]
    elif cache is not None:
        M = cache["ckv"].shape[1]
        slots = positions % M
        store_pos = (positions if kv_valid is None
                     else jnp.where(kv_valid, positions, -1))
        cc = _scatter_cache(cache["ckv"], ckv, slots)
        cr = _scatter_cache(cache["krope"], k_rope, slots)
        cpos = cache["pos"].at[jnp.arange(B)[:, None], slots].set(store_pos)
        new_cache = {"ckv": cc, "krope": cr, "pos": cpos}
        ckv_all, krope_all, k_pos = cc, cr, cpos
    else:
        k_pos = (positions if kv_valid is None
                 else jnp.where(kv_valid, positions, -1))
        ckv_all, krope_all = ckv, k_rope

    if cache is not None and Q <= 8:
        mask = _causal_mask(positions, k_pos, window)[:, 0, 0]  # (B,Q,M)
        # absorbed decode path: score in latent space
        q_lat = jnp.einsum("bqhe,rhe->bqhr", q_nope, params["k_b"].astype(dt))
        scores = (jnp.einsum("bqhr,bmr->bhqm", q_lat, ckv_all)
                  + jnp.einsum("bqhe,bme->bhqm", q_rope, krope_all))
        scores = scores.astype(jnp.float32) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        ctx = jnp.einsum("bhqm,bmr->bqhr", w, ckv_all)
        out = jnp.einsum("bqhr,rhe->bqhe", ctx, params["v_b"].astype(dt))
    elif Q > 1024:
        # long-sequence expanded path, blockwise: build per-head K=[k_nope;
        # k_rope], Q=[q_nope; q_rope] and stream kv tiles
        k_nope = jnp.einsum("bmr,rhe->bmhe", ckv_all, params["k_b"].astype(dt))
        v = jnp.einsum("bmr,rhe->bmhe", ckv_all, params["v_b"].astype(dt))
        k_nope = shard_hint(k_nope, ("batch", "seq", "heads", None))
        v = shard_hint(v, ("batch", "seq", "heads", None))
        M = k_nope.shape[1]
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_all[:, :, None, :],
                                      (B, M, H, rd))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = blockwise_attention(q_full, k_full, v, positions, k_pos,
                                  window=window, causal=True, scale=scale,
                                  block_q=cfg.attn_block_q,
                                  block_k=cfg.attn_block_k,
                                  unroll=cfg.unroll_scans,
                                  accum_dtype=jnp.dtype(cfg.accum_dtype))
    else:
        # expanded path
        mask = _causal_mask(positions, k_pos, window)[:, 0, 0]  # (B,Q,M)
        k_nope = jnp.einsum("bmr,rhe->bmhe", ckv_all, params["k_b"].astype(dt))
        v = jnp.einsum("bmr,rhe->bmhe", ckv_all, params["v_b"].astype(dt))
        k_nope = shard_hint(k_nope, ("batch", "seq", "heads", None))
        scores = (jnp.einsum("bqhe,bmhe->bhqm", q_nope, k_nope)
                  + jnp.einsum("bqhe,bme->bhqm", q_rope, krope_all))
        scores = scores.astype(jnp.float32) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        out = jnp.einsum("bhqm,bmhe->bqhe", w, v)

    out = jnp.einsum("bqhe,hed->bqd", out, params["o"].astype(dt))
    return shard_hint(out, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------- cross-attn
def cross_attention_apply(params, cfg, x, enc_kv):
    """x (B,Q,d); enc_kv = (k,v) each (B,M,Hk,hd) precomputed from encoder out."""
    B, Q, _ = x.shape
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // Hk
    dt = x.dtype
    q = jnp.einsum("bqd,dhe->bqhe", x, params["q"].astype(dt))
    k, v = enc_kv
    M = k.shape[1]
    if Q > 2048:
        # long decoder sequences: stream q blocks (scores (Q,M) won't fit)
        k_exp = jnp.repeat(k, G, axis=2)
        v_exp = jnp.repeat(v, G, axis=2)
        q_pos = jnp.broadcast_to(jnp.arange(Q, dtype=jnp.int32), (B, Q))
        k_pos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (B, M))
        out = blockwise_attention(q, k_exp, v_exp, q_pos, k_pos,
                                  causal=False, scale=1.0 / math.sqrt(hd),
                                  block_q=cfg.attn_block_q,
                                  block_k=cfg.attn_block_k,
                                  unroll=cfg.unroll_scans,
                                  accum_dtype=jnp.dtype(cfg.accum_dtype))
    else:
        mask = jnp.ones((B, 1, 1, Q, M), bool)
        qh = q.reshape(B, Q, Hk, G, hd).transpose(0, 2, 3, 1, 4)
        out = _sdpa(qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                    mask, 1.0 / math.sqrt(hd))
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, Q, H, hd)
    return jnp.einsum("bqhe,hed->bqd", out, params["o"].astype(dt))


def encode_cross_kv(params, cfg, enc_out):
    dt = enc_out.dtype
    k = jnp.einsum("bmd,dhe->bmhe", enc_out, params["k"].astype(dt))
    v = jnp.einsum("bmd,dhe->bmhe", enc_out, params["v"].astype(dt))
    return k, v


def attention_apply(params, cfg, x, positions, cache=None, window: int = 0,
                    causal: bool = True, use_flash: bool = False, kv_valid=None,
                    paged_kernel: bool = False):
    if cfg.uses_mla:
        # MLA decodes absorbed (scores in latent space over ckv/krope) — the
        # two-pool kernel variant is future work, so paged MLA keeps the
        # dense gather (int8 pools still dequant through _paged_gather)
        return mla_apply(params, cfg, x, positions, cache=cache, window=window,
                         kv_valid=kv_valid)
    return gqa_apply(params, cfg, x, positions, cache=cache, window=window,
                     causal=causal, use_flash=use_flash, kv_valid=kv_valid,
                     paged_kernel=paged_kernel)
