"""Unified Model facade over every architecture family.

``Model(cfg)`` exposes:
  specs() / init(key) / abstract()          parameters
  apply(params, batch, caches=None, ...)    logits for train/prefill
  decode_step(params, tokens, positions, caches)  one-token decode
  init_cache / cache_struct                 decode caches (KV / SSM / hybrid)
  input_specs(shape_name)                   ShapeDtypeStruct stand-ins (dry-run)

Decode-cache allocation is routed through a :class:`CachePolicy`:
``ContiguousCache`` (the default — one fixed-width lane per batch row) or
``PagedCache`` (a global block pool + per-row block tables for the attention
families; SSM state is O(1)/row and stays per-row under either policy).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as ATT
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.models.params import abstract_params, init_params, param_count


def _bcast_stack(tree, n: int):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)


def _set_rows(axis: int, idx: jax.Array):
    """tree_map fn writing ``new``'s rows into ``live`` at ``idx`` on ``axis``."""
    def f(live, new):
        sl = (slice(None),) * axis + (idx,)
        return live.at[sl].set(new.astype(live.dtype))
    return f


# ================================================================ cache policy
class CachePolicy:
    """Strategy object for per-family decode-cache allocation.

    ``init_cache`` builds the cache pytree for a batch; ``reset_rows``
    returns individual batch rows to their pristine state (the continuous
    batching slot-refill primitive).  Implementations resolve the per-family
    batch-axis/stack-axis layouts (GQA/MLA attention stacks, SSM state,
    hybrid groups, enc-dec decoder stacks) so no caller needs to know them.
    """

    def init_cache(self, model: "Model", batch: int, max_len: int,
                   window: int = 0):
        raise NotImplementedError

    def reset_rows(self, model: "Model", cache, rows, max_len: int,
                   window: int = 0, freed_blocks=None):
        raise NotImplementedError


class ContiguousCache(CachePolicy):
    """Seed layout: one ``[max_len]``-wide lane per batch row per layer."""

    def init_cache(self, model, batch, max_len, window=0):
        c = model.cfg
        if c.family in ("dense", "moe", "vlm", "encdec"):
            n_stack = (c.n_layers - c.first_k_dense
                       if c.family != "encdec" else c.n_layers)
            single = ATT.init_kv_cache(c, batch, max_len, window)
            out = {"stack": _bcast_stack(single, n_stack)}
            if c.first_k_dense and c.family != "encdec":
                out["dense"] = [ATT.init_kv_cache(c, batch, max_len, window)
                                for _ in range(c.first_k_dense)]
            return out
        if c.family == "ssm":
            single = SSM.init_ssm_cache(c, batch)
            return {"stack": _bcast_stack(single, c.n_layers)}
        if c.family == "hybrid":
            G = c.n_layers // c.attn_every
            mamba = _bcast_stack(_bcast_stack(SSM.init_ssm_cache(c, batch),
                                              c.attn_every), G)
            kv = _bcast_stack(ATT.init_kv_cache(c, batch, max_len, window), G)
            return {"stack": {"mamba": mamba, "attn": kv}}
        raise ValueError(c.family)

    def reset_rows(self, model, cache, rows, max_len, window=0,
                   freed_blocks=None):
        c = model.cfg
        idx = jnp.asarray(np.asarray(rows, np.int32).reshape(-1))
        fresh = self.init_cache(model, int(idx.shape[0]), max_len, window)
        tmap = jax.tree_util.tree_map
        if c.family == "hybrid":
            return {"stack": {
                # mamba leaves: (G, attn_every, B, ...); attn leaves: (G, B, ...)
                "mamba": tmap(_set_rows(2, idx), cache["stack"]["mamba"],
                              fresh["stack"]["mamba"]),
                "attn": tmap(_set_rows(1, idx), cache["stack"]["attn"],
                             fresh["stack"]["attn"]),
            }}
        # dense/moe/vlm/encdec/ssm: "stack" leaves (n_stack, B, ...),
        # optional "dense" list entries (B, ...)
        out = {"stack": tmap(_set_rows(1, idx), cache["stack"],
                             fresh["stack"])}
        if "dense" in cache:
            out["dense"] = [tmap(_set_rows(0, idx), cl, fl)
                            for cl, fl in zip(cache["dense"], fresh["dense"])]
        return out


@dataclasses.dataclass
class PagedCache(CachePolicy):
    """vLLM-style paging: attention K/V lives in a global pool of
    ``num_blocks`` x ``block_size`` token blocks shared by the whole batch,
    addressed through per-row block tables (see models/attention.py for the
    layout and trash-block convention).  Block ids are assigned host-side by
    ``serving.engine.BlockAllocator``; this policy only shapes the pytree.

    ``reset_rows`` is "free blocks to pool": the freed blocks' ``pos``
    entries go to -1 (so a future occupant can never attend a previous
    occupant's stale K/V) and the rows' table entries to -1.  SSM /
    hybrid-mamba state keeps the per-row contiguous layout and per-row reset.
    Requires window=0 — sliding-window ring buffers stay contiguous.

    ``kv_dtype`` is "fp" (default; training-parity oracle) or "int8"
    (quantize-on-write block pools with per-slot scales: half the bytes per
    cached token vs bf16, so the same HBM holds 2x the blocks).
    """
    block_size: int
    num_blocks: int
    kv_dtype: str = "fp"

    def max_blocks_per_row(self, max_len: int) -> int:
        return max(1, math.ceil(max_len / self.block_size))

    def init_cache(self, model, batch, max_len, window=0):
        c = model.cfg
        if c.family == "ssm":       # attention-free: nothing to page
            return ContiguousCache().init_cache(model, batch, max_len, window)
        if window:
            raise ValueError("paged KV cache requires window=0 "
                             "(sliding windows use the contiguous ring buffer)")
        T_blk = self.max_blocks_per_row(max_len)

        def paged_single():
            return ATT.init_paged_kv_cache(c, self.num_blocks,
                                           self.block_size, batch, T_blk,
                                           kv_dtype=self.kv_dtype)

        if c.family in ("dense", "moe", "vlm", "encdec"):
            n_stack = (c.n_layers - c.first_k_dense
                       if c.family != "encdec" else c.n_layers)
            out = {"stack": _bcast_stack(paged_single(), n_stack)}
            if c.first_k_dense and c.family != "encdec":
                out["dense"] = [paged_single()
                                for _ in range(c.first_k_dense)]
            return out
        if c.family == "hybrid":
            G = c.n_layers // c.attn_every
            mamba = _bcast_stack(_bcast_stack(SSM.init_ssm_cache(c, batch),
                                              c.attn_every), G)
            return {"stack": {"mamba": mamba,
                              "attn": _bcast_stack(paged_single(), G)}}
        raise ValueError(c.family)

    # -- helpers ---------------------------------------------------------
    def _reset_paged(self, paged: dict, idx: jax.Array, blocks: jax.Array,
                     stack: bool) -> dict:
        """Free ``blocks`` (pos -> -1) and clear ``idx``'s table rows in one
        per-layer paged dict (leaves optionally stacked on a leading axis)."""
        out = dict(paged)
        if stack:
            out["pos"] = paged["pos"].at[:, blocks, :].set(-1)
            out["table"] = paged["table"].at[:, idx, :].set(-1)
        else:
            out["pos"] = paged["pos"].at[blocks, :].set(-1)
            out["table"] = paged["table"].at[idx, :].set(-1)
        return out

    def reset_rows(self, model, cache, rows, max_len, window=0,
                   freed_blocks=None):
        c = model.cfg
        if c.family == "ssm":
            return ContiguousCache().reset_rows(model, cache, rows, max_len,
                                                window)
        idx = jnp.asarray(np.asarray(rows, np.int32).reshape(-1))
        blocks = jnp.asarray(
            np.asarray([] if freed_blocks is None else list(freed_blocks),
                       np.int32).reshape(-1))
        if c.family == "hybrid":
            fresh = SSM.init_ssm_cache(c, int(idx.shape[0]))
            G = c.n_layers // c.attn_every
            fresh = _bcast_stack(_bcast_stack(fresh, c.attn_every), G)
            tmap = jax.tree_util.tree_map
            return {"stack": {
                "mamba": tmap(_set_rows(2, idx), cache["stack"]["mamba"],
                              fresh),
                "attn": self._reset_paged(cache["stack"]["attn"], idx,
                                          blocks, stack=True),
            }}
        out = {"stack": self._reset_paged(cache["stack"], idx, blocks,
                                          stack=True)}
        if "dense" in cache:
            out["dense"] = [self._reset_paged(cl, idx, blocks, stack=False)
                            for cl in cache["dense"]]
        return out

    def _copy_paged(self, paged: dict, src: jax.Array, dst: jax.Array,
                    stack: bool) -> dict:
        """Slab-copy ``src`` pool blocks onto ``dst`` in one per-layer paged
        dict: every pool leaf (K/V values, int8 scales, ``pos``) moves so the
        destination block is indistinguishable from the source to any reader.
        ``table`` is host-owned and untouched (the allocator remaps it)."""
        out = dict(paged)
        for k, v in paged.items():
            if k == "table":
                continue
            out[k] = v.at[:, dst].set(v[:, src]) if stack \
                else v.at[dst].set(v[src])
        return out

    def copy_blocks(self, model, cache, src, dst):
        """Copy-on-write primitive: duplicate pool blocks ``src`` -> ``dst``
        across every attention layer (block tables are identical across
        layers, so one logical CoW is one slab copy per layer-group).  The
        engine calls this *before* the device step that would write through
        a shared mapping — the paged scatter itself never needs to know a
        block was shared."""
        s = np.asarray(list(src), np.int32).reshape(-1)
        d = np.asarray(list(dst), np.int32).reshape(-1)
        if s.size == 0:
            return cache
        c = model.cfg
        if c.family == "ssm":
            return cache
        s, d = jnp.asarray(s), jnp.asarray(d)
        if c.family == "hybrid":
            return {"stack": {
                "mamba": cache["stack"]["mamba"],
                "attn": self._copy_paged(cache["stack"]["attn"], s, d,
                                         stack=True),
            }}
        out = {"stack": self._copy_paged(cache["stack"], s, d, stack=True)}
        if "dense" in cache:
            out["dense"] = [self._copy_paged(cl, s, d, stack=False)
                            for cl in cache["dense"]]
        return out

    def set_tables(self, cache, table: np.ndarray):
        """Broadcast a fresh host block table (B, T) into every ``table``
        leaf of the cache (tables are identical across layers).  Each leaf
        gets its own buffer: the engine donates the cache to its jitted
        steps, and one buffer cannot be donated twice."""
        def walk(tree):
            if isinstance(tree, dict):
                return {k: (jnp.array(np.broadcast_to(table, v.shape),
                                      jnp.int32) if k == "table"
                            else walk(v)) for k, v in tree.items()}
            if isinstance(tree, list):
                return [walk(x) for x in tree]
            return tree
        return walk(cache)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ---------------------------------------------------------- params
    def specs(self):
        c = self.cfg
        if c.family in ("dense", "moe", "vlm"):
            return T.lm_specs(c)
        if c.family == "ssm":
            return T.mamba_lm_specs(c)
        if c.family == "hybrid":
            return T.zamba_specs(c)
        if c.family == "encdec":
            return T.encdec_specs(c)
        raise ValueError(c.family)

    def init(self, key):
        return init_params(key, self.specs())

    def abstract(self):
        return abstract_params(self.specs())

    def n_params(self) -> int:
        return param_count(self.specs())

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: routed top-k only)."""
        c = self.cfg
        total = param_count(self.specs())
        if not c.n_experts:
            return total
        from repro.models.moe import moe_specs
        from repro.models.params import param_count as pc
        expert_block = pc({k: v for k, v in moe_specs(c).items()
                           if k in ("gate", "up", "down")})
        n_moe_layers = c.n_layers - c.first_k_dense
        inactive = expert_block * n_moe_layers * (
            (c.n_experts - c.moe_top_k) / c.n_experts)
        return int(total - inactive)

    # ---------------------------------------------------------- forward
    def apply(self, params, batch: dict, caches=None, positions=None,
              window: int = 0, use_flash: bool = False, use_kernel: bool = False,
              moe_dense_ref: bool = False, kv_valid=None,
              last_token_only=False, paged_kernel: bool = False):
        """Full-sequence forward (train / prefill).

        Returns (logits, aux_loss, new_caches).  ``batch`` carries "tokens"
        and, for vlm/encdec, "prefix_embeds".
        """
        c = self.cfg
        if c.family in ("dense", "moe", "vlm"):
            return T.lm_apply(params, c, batch["tokens"], positions=positions,
                              prefix_embeds=batch.get("prefix_embeds"),
                              caches=caches, window=window, use_flash=use_flash,
                              moe_dense_ref=moe_dense_ref, kv_valid=kv_valid,
                              last_token_only=last_token_only,
                              paged_kernel=paged_kernel)
        if c.family == "ssm":
            return T.mamba_lm_apply(params, c, batch["tokens"],
                                    caches=caches, use_kernel=use_kernel,
                                    kv_valid=kv_valid,
                                    last_token_only=last_token_only)
        if c.family == "hybrid":
            return T.zamba_apply(params, c, batch["tokens"], positions=positions,
                                 caches=caches, window=window,
                                 use_flash=use_flash, use_kernel=use_kernel,
                                 kv_valid=kv_valid,
                                 last_token_only=last_token_only,
                                 paged_kernel=paged_kernel)
        if c.family == "encdec":
            return T.encdec_apply(params, c, batch["tokens"],
                                  prefix_embeds=batch["prefix_embeds"],
                                  positions=positions, caches=caches,
                                  window=window, use_flash=use_flash,
                                  kv_valid=kv_valid,
                                  last_token_only=last_token_only,
                                  paged_kernel=paged_kernel)
        raise ValueError(c.family)

    def decode_step(self, params, tokens, positions, caches, window: int = 0,
                    cross_kv=None, kv_valid=None, paged_kernel: bool = False):
        """tokens (B,Q small), positions (B,Q) -> (logits, new_caches).

        Contract (the serving engine traces this inside a jitted
        ``lax.while_loop``): pure function of its arguments, no host
        callbacks, and ``new_caches`` must have exactly the same pytree
        structure/shapes/dtypes as ``caches`` so it can be loop-carried.
        Rows with ``kv_valid=False`` must leave the sequence state untouched
        (attention stores pos=-1; SSM freezes the recurrent state via dt=0).

        ``paged_kernel`` (resolved by the serving engine) routes
        single-token GQA decode through the Pallas block-table kernel.
        """
        c = self.cfg
        if c.family == "encdec":
            logits, _, nc = T.encdec_decode_stack(
                params, c, tokens, cross_kv, positions=positions,
                caches=caches, window=window, kv_valid=kv_valid,
                paged_kernel=paged_kernel)
            return logits, nc
        logits, _, nc = self.apply(params, {"tokens": tokens}, caches=caches,
                                   positions=positions, window=window,
                                   kv_valid=kv_valid,
                                   paged_kernel=paged_kernel)
        return logits, nc

    # ---------------------------------------------------------- caches
    def init_cache(self, batch: int, max_len: int, window: int = 0,
                   policy: Optional[CachePolicy] = None):
        """Build the decode cache under ``policy`` (contiguous by default)."""
        return (policy or ContiguousCache()).init_cache(
            self, batch, max_len, window)

    def cache_struct(self, batch: int, max_len: int, window: int = 0,
                     policy: Optional[CachePolicy] = None):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len, window, policy=policy))

    def reset_cache_rows(self, cache, rows, max_len: int, window: int = 0,
                         policy: Optional[CachePolicy] = None,
                         freed_blocks=None):
        """Return ``cache`` with the given batch rows re-initialized.

        The selected rows go back to their :meth:`init_cache` state while
        every other row is untouched — the continuous-batching slot-refill
        primitive.  Under :class:`ContiguousCache` that re-zeros the rows'
        fixed lanes (attention pos=-1, SSM conv/state zero); under
        :class:`PagedCache` it frees the rows' blocks back to the pool
        (``freed_blocks`` from the host allocator) and clears their block
        tables.  The batch axis sits at a different depth per family, which
        the policy resolves.
        """
        return (policy or ContiguousCache()).reset_rows(
            self, cache, rows, max_len, window, freed_blocks=freed_blocks)

    def copy_cache_blocks(self, cache, src, dst,
                          policy: Optional[CachePolicy] = None):
        """Device-side copy-on-write: duplicate paged pool blocks ``src`` ->
        ``dst`` in every attention layer (no-op under a contiguous policy).
        Used by the serving engine when a row is about to write into a block
        it shares with other rows (prefix sharing)."""
        if not isinstance(policy, PagedCache):
            return cache
        return policy.copy_blocks(self, cache, src, dst)

    # ---------------------------------------------------------- dry-run inputs
    def input_specs(self, shape_name: str, variant: str = "baseline") -> dict:
        """ShapeDtypeStruct stand-ins for every model input of a given
        (shape, kind).  See configs.INPUT_SHAPES."""
        from repro.configs import INPUT_SHAPES
        c = self.cfg
        info = INPUT_SHAPES[shape_name]
        B, S, kind = info["global_batch"], info["seq_len"], info["kind"]
        i32 = jnp.int32
        f32 = jnp.float32
        sds = jax.ShapeDtypeStruct

        def with_prefix(d, n_text):
            if c.family in ("vlm", "encdec"):
                d["prefix_embeds"] = sds((B, c.n_prefix_embeds,
                                          T.PREFIX_EMBED_DIM), f32)
            return d

        if kind == "train":
            n_text = S - (c.n_prefix_embeds if c.family == "vlm" else 0)
            batch = {
                "tokens": sds((B, n_text), i32),
                "loss_mask": sds((B, n_text), f32),
                "advantages": sds((B,), f32),
                "old_logprobs": sds((B, n_text), f32),
                "ref_logprobs": sds((B, n_text), f32),
            }
            return with_prefix(batch, n_text)
        if kind == "prefill":
            n_text = S - (c.n_prefix_embeds if c.family == "vlm" else 0)
            return with_prefix({"tokens": sds((B, n_text), i32)}, n_text)
        if kind == "decode":
            window = self.decode_window(shape_name)
            batch = {
                "tokens": sds((B, 1), i32),
                "positions": sds((B, 1), i32),
                "cache": self.cache_struct(B, S, window),
            }
            if c.family == "encdec":
                kv = jax.eval_shape(
                    lambda p, e: T.encdec_cross_kv(p, c, e),
                    self.abstract(),
                    sds((B, c.n_prefix_embeds, c.d_model), c.activation_dtype))
                batch["cross_kv"] = kv
            return batch
        raise ValueError(kind)

    def decode_window(self, shape_name: str) -> int:
        """Effective attention window for a decode shape (0 = full cache)."""
        c = self.cfg
        from repro.configs import INPUT_SHAPES
        S = INPUT_SHAPES[shape_name]["seq_len"]
        if shape_name == "long_500k":
            if c.long_context_window == 0:
                raise ValueError(
                    f"{c.arch_id} does not support long_500k (see DESIGN.md)")
            if c.long_context_window > 0:
                return c.long_context_window
            return 0  # natively sub-quadratic (ssm)
        return c.sliding_window or 0

    def supports(self, shape_name: str) -> bool:
        c = self.cfg
        if shape_name == "long_500k":
            return c.long_context_window != 0
        return True
