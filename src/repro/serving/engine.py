"""Batched generation engine — the TPU-native vLLM analogue (DESIGN.md §2).

A :class:`DecodeSession` holds one shared KV/SSM cache for a batch of ragged
contexts.  Turn structure for multi-turn rollouts:

    session = engine.start(contexts)            # prefill prompts
    res = engine.generate(session, n, k)        # sample until stop/budget
    engine.extend(session, obs_token_lists)     # prefill tool observations
    ...                                          # next turn reuses the cache

Continuous batching (core/scheduler.py) additionally drives *per-slot*
session ops so individual rows can be parked, retired and refilled without
disturbing their neighbours:

    engine.extend_rows(session, rows, lists)    # prefill a subset of rows
    engine.reset_rows(session, rows)            # clear cache lanes for reuse

and per-row sampling streams: ``generate(..., row_keys=(B,2))`` draws row
``b``'s tokens from ``fold_in(row_keys[b], step)`` instead of one shared
key, so a trajectory's samples do not depend on which other rows happen to
share the decode batch — the property that makes scheduler-vs-reference
trajectory parity exact.

Ragged rows are right-padded per call; pads carry ``kv_valid=False`` so they
are stored with pos=-1 (attention) / dt=0 (SSM) and never influence later
tokens — rollout logprobs therefore match training-time logprobs exactly
(tests/test_rollout_and_rewards.py asserts this).

The decode hot path is one fused, jitted ``lax.while_loop`` that runs
entirely on device: per-step sampling, stop-id detection, per-row active
masking, logprob capture and cache writes all happen inside the loop, so a
whole turn costs one dispatch and one device->host transfer (the batched
:class:`GenerationResult` plus the updated ``lengths``/``stopped`` vectors)
instead of ``max_new_tokens`` round-trips.  The loop exits early once every
row has stopped.  To bound jit recompiles, the output buffer width is
``max_new_tokens`` bucketed up to a multiple of 32 (the actual budget is a
dynamic operand), and prefill lengths are bucketed the same way; rows that
exhaust ``max_len`` are marked ``stopped`` so later turns never resample
them.  A per-token Python-loop reference (``generate_reference``) is kept
for parity tests and the decode-throughput benchmark.

Two decode-loop extensions support the continuous-batching scheduler's
round-based turns: ``row_budgets`` (B,) caps each row's tokens within one
call, and ``step_offsets`` (B,) shifts the per-row sampling-stream index so a
logical turn can be split across several ``generate`` calls without changing
which random numbers each token draws — row ``b``'s i-th turn token always
samples from ``fold_in(row_keys[b], i)`` no matter how the calls are sliced.

Disaggregated trainer/engine (core/trainer.py ``mode="async"``): the engine
owns a :class:`WeightStore` of *versioned* param handles.  A learner calls
``publish(params) -> version`` at any time; the staged version becomes the
decode params only when ``refresh_weights()`` is called — the continuous
scheduler invokes it **between decode rounds**, so a version swap can never
land mid-round and every sampled token is attributable to exactly one
version (``active_version``).  Old versions stay pinned
(``pin_version``/``unpin_version``) while in-flight trajectories reference
them and are dropped once the last reference retires.

``cache_mode="paged"`` switches the KV layout from per-row contiguous lanes
to a global block pool + per-row block tables (models/attention.py): a
:class:`BlockAllocator` hands out fixed-size token blocks on
prefill/extend/decode and takes them back on ``reset_rows``, so memory scales
with *live tokens* instead of ``batch x max_len`` and a retiring long row can
refill several short queued tasks.  Admission hooks (``blocks_for`` /
``free_blocks`` / ``admission_headroom`` / ``cache_utilization``) let the
scheduler gate refills on free-block availability.  The contiguous layout
(the default) is kept as the parity oracle; both produce token-identical
results (tests/test_paged_cache.py).

Prefix sharing (``prefix_sharing=True``, paged-only): the allocator
refcounts blocks so one physical block can appear in many rows' tables.
Identical prompts prefilled together (GRPO groups) collapse to one leader
prefill — followers remap every leader block (partial tail included) and
copy its ``last_logits`` — and a radix index (serving/prefix_index.py) over
full-block token chains lets later prompts remap any previously prefilled
prefix (system prompt, few-shot header, tool schemas), including the
re-prefill of a swapped-out row on re-admission.  The first write into a
shared block triggers host-side copy-on-write (allocate + device slab copy
+ remap) *before* the device step, so the paged scatter never writes
through a shared mapping and decode stays token- and logprob-identical to
unshared paging (tests/test_prefix_sharing.py).  Unreferenced radix chains
stay *cached* (reclaimable, LRU-evicted under pool pressure), so
``free_count`` still bounds admission.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels, obs
from repro.models import Model, PagedCache
from repro.serving.prefix_index import RadixPrefixIndex

BUCKET = 32


def _bucket(n: int) -> int:
    return max(BUCKET, ((n + BUCKET - 1) // BUCKET) * BUCKET)


class WeightStore:
    """Versioned param handles for in-flight weight refresh.

    The learner *publishes* new params (staging them as ``version``, the
    latest); the serving side *refreshes* at a round boundary, swapping
    ``active`` to the latest staged version.  Versions referenced by
    in-flight trajectories are pinned; an unpinned version that is neither
    active nor latest is dropped immediately (in a multi-host deployment
    this is where its device buffers would be freed).

    Version numbers are monotone across the store's lifetime; a resumed run
    re-bases the counter via :meth:`set_version` so staleness metrics stay
    meaningful across restarts (checkpoint/checkpointer.py persists it).
    """

    def __init__(self, params, version: int = 0):
        self._store = {int(version): params}
        self._pins: dict = {}
        self.version = int(version)     # latest published
        self.active = int(version)      # currently serving decode

    # ------------------------------------------------------------ handles
    @property
    def active_params(self):
        return self._store[self.active]

    @property
    def latest_params(self):
        return self._store[self.version]

    def get(self, version: int):
        return self._store[int(version)]

    @property
    def n_retained(self) -> int:
        return len(self._store)

    # ------------------------------------------------------------ lifecycle
    def publish(self, params) -> int:
        """Stage ``params`` as the next version (does NOT change the active
        decode params — that happens at the next :meth:`refresh`)."""
        self.version += 1
        self._store[self.version] = params
        self._gc()
        return self.version

    def refresh(self) -> int:
        """Swap the active decode params to the latest published version
        (round-boundary sync point); returns the active version."""
        if self.active != self.version:
            self.active = self.version
            self._gc()
        return self.active

    def pin(self, version: int) -> None:
        v = int(version)
        if v not in self._store:
            raise KeyError(f"weight version {v} not retained")
        self._pins[v] = self._pins.get(v, 0) + 1

    def unpin(self, version: int) -> None:
        v = int(version)
        n = self._pins.get(v, 0) - 1
        if n <= 0:
            self._pins.pop(v, None)
        else:
            self._pins[v] = n
        self._gc()

    def set_version(self, version: int) -> None:
        """Re-base the counter (checkpoint restore): the current latest
        params become ``version`` and every older handle is dropped."""
        if self._pins:
            raise RuntimeError("cannot re-base WeightStore with pinned "
                               f"versions: {sorted(self._pins)}")
        params = self.latest_params
        self._store = {int(version): params}
        self.version = self.active = int(version)

    def _gc(self) -> None:
        keep = {self.active, self.version} | set(self._pins)
        for v in [v for v in self._store if v not in keep]:
            del self._store[v]


class BlockAllocator:
    """Host-side refcounted allocator for the paged KV cache.

    Owns the (batch, max_blocks_per_row) block table; blocks are appended to
    a row on ``ensure`` (copy-free growth — extending a row never moves
    existing blocks) and dereferenced on ``free_rows``.  Device tables are
    synced from :attr:`table` by the engine after any change.

    Prefix sharing (ROADMAP item 2): one physical block may appear in many
    rows' tables — :attr:`refcount` counts the table references.  Every
    block is in exactly one of three states:

    * **free** — refcount 0, on the free list, K/V slab is garbage;
    * **used** — refcount >= 1, mapped by at least one row;
    * **cached** — refcount 0 but still registered in the radix
      :attr:`prefix` index: its K/V is intact and a future prompt with the
      same prefix can remap it for free.  Cached blocks are *reclaimable* —
      ``free_count`` includes them (so scheduler admission math is
      unchanged) and allocation evicts them LRU leaf-first when the free
      list runs dry.  Evicted/garbage ids land in :attr:`pending_clear`
      for the engine to pos-reset device-side before reuse.

    ``map_shared`` appends already-filled blocks to a row (refcount++);
    ``cow`` gives a row a private replacement for a shared block it is
    about to write (the engine copies the K/V slab device-side).
    """

    def __init__(self, num_blocks: int, block_size: int, batch: int,
                 max_blocks_per_row: int, prefix=None):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._cached: set = set()   # refcount-0 blocks held by the radix
        self.refcount = np.zeros((num_blocks,), np.int32)
        self.table = np.full((batch, max_blocks_per_row), -1, np.int32)
        self.n_blocks = np.zeros((batch,), np.int32)
        self.prefix = prefix        # RadixPrefixIndex | None
        self.peak_used = 0
        self.dirty = False          # host table changed since last device sync
        self.pending_clear: List[int] = []  # evicted ids awaiting pos-reset
        # cumulative sharing counters (surfaced as rollout/* stats)
        self.shared_maps = 0        # blocks mapped without prefill
        self.cow_count = 0          # copy-on-write block copies
        self.shared_tokens = 0      # prompt tokens served from shared blocks
        self.prompt_tokens = 0      # prompt tokens submitted (from length 0)
        self.peak_shared = 0        # max concurrent blocks with refcount > 1

    @property
    def free_count(self) -> int:
        """Reclaimable blocks: truly free plus cached (evictable) ones."""
        return len(self._free) + len(self._cached)

    @property
    def cached_count(self) -> int:
        return len(self._cached)

    @property
    def used_count(self) -> int:
        """Blocks currently mapped by at least one row."""
        return self.num_blocks - self.free_count

    @property
    def shared_now(self) -> int:
        """Blocks currently mapped by more than one row."""
        return int(np.count_nonzero(self.refcount > 1))

    def blocks_for(self, n_tokens: int) -> int:
        return max(0, math.ceil(n_tokens / self.block_size))

    def capacity(self, row: int) -> int:
        """Tokens the row can hold in its currently mapped blocks."""
        return int(self.n_blocks[row]) * self.block_size

    def _pop_block(self) -> int:
        """Take a block off the free list, evicting LRU cached radix chains
        when it runs dry; -1 when nothing is reclaimable."""
        if not self._free and self._cached:
            evicted = self.prefix.evict(1, self.refcount)
            self._cached.difference_update(evicted)
            self.pending_clear.extend(evicted)
            self._free.extend(evicted)
        if not self._free:
            return -1
        return self._free.pop()

    def ensure(self, row: int, target_len: int) -> int:
        """Map blocks so ``row`` can hold ``target_len`` tokens; allocates as
        many of the missing blocks as the pool can supply and returns the
        resulting capacity (callers decide whether partial coverage is an
        error or a reason to shrink the decode budget)."""
        need = self.blocks_for(target_len) - int(self.n_blocks[row])
        for _ in range(need):
            b = self._pop_block()
            if b < 0:
                break
            self.table[row, self.n_blocks[row]] = b
            self.refcount[b] = 1
            self.n_blocks[row] += 1
            self.dirty = True
        self.peak_used = max(self.peak_used, self.used_count)
        return self.capacity(row)

    def map_shared(self, row: int, block_ids: Sequence[int]) -> None:
        """Append already-filled blocks to ``row``'s table (refcount++) —
        the sharing primitive: no prefill, no copy, just a table remap.
        Cached blocks come back to life (refcount 0 -> 1) with their K/V
        intact."""
        r = int(row)
        for b in block_ids:
            b = int(b)
            self.table[r, self.n_blocks[r]] = b
            if self.refcount[b] == 0:
                self._cached.discard(b)
            self.refcount[b] += 1
            self.n_blocks[r] += 1
        if len(block_ids):
            self.dirty = True
            self.shared_maps += len(block_ids)
            self.peak_used = max(self.peak_used, self.used_count)
            self.peak_shared = max(self.peak_shared, self.shared_now)

    def cow(self, row: int, block_idx: int) -> Tuple[int, int]:
        """Copy-on-write: give ``row`` a private block in table slot
        ``block_idx`` (the old block stays with its other referents).
        Returns ``(src, dst)`` for the engine's device-side slab copy; dst
        is -1 when the pool has nothing reclaimable (caller backpressures).
        """
        r = int(row)
        old = int(self.table[r, block_idx])
        new = self._pop_block()
        if new < 0:
            return old, -1
        self.refcount[new] = 1
        self.refcount[old] -= 1        # precondition: refcount[old] > 1
        self.table[r, block_idx] = new
        self.dirty = True
        self.cow_count += 1
        self.peak_used = max(self.peak_used, self.used_count)
        return old, new

    def free_rows(self, rows: Sequence[int]) -> List[int]:
        """Drop ``rows``' references to their blocks.  A block whose last
        reference goes away is *freed* (returned so the engine pos-resets
        it device-side) unless the radix index still holds it — then it
        stays **cached** with its K/V intact for future prefix hits.  Blocks
        still referenced by other rows survive untouched."""
        freed: List[int] = []
        for r in rows:
            r = int(r)
            n = int(self.n_blocks[r])
            for b in self.table[r, :n]:
                b = int(b)
                self.refcount[b] -= 1
                if self.refcount[b] == 0:
                    if self.prefix is not None and b in self.prefix:
                        self._cached.add(b)
                    else:
                        freed.append(b)
            self.table[r, :] = -1
            self.n_blocks[r] = 0
            if n:
                self.dirty = True
        self._free.extend(freed)
        return freed

    def check(self) -> None:
        """Invariant self-check (wired into the scheduler tests so churn
        can never leak or double-free a shared block): every block is free
        xor cached xor mapped; per-block table references sum to exactly
        its refcount; ``used_count + free_count == num_blocks``."""
        refs = np.zeros((self.num_blocks,), np.int64)
        for r in range(self.table.shape[0]):
            n = int(self.n_blocks[r])
            row_blocks = self.table[r, :n]
            assert np.all(row_blocks >= 0), f"row {r}: unmapped slot < n_blocks"
            assert np.all(self.table[r, n:] == -1), \
                f"row {r}: stale table entry past n_blocks"
            np.add.at(refs, row_blocks, 1)
        assert np.array_equal(refs, self.refcount), (
            "refcount drift: table references "
            f"{refs[refs != self.refcount]} != refcount "
            f"{self.refcount[refs != self.refcount]} at blocks "
            f"{np.nonzero(refs != self.refcount)[0]}")
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate ids on the free list"
        assert not (free & self._cached), "block both free and cached"
        for b in free | self._cached:
            assert self.refcount[b] == 0, f"block {b} free/cached but mapped"
        mapped = set(np.nonzero(self.refcount > 0)[0].tolist())
        assert free | self._cached | mapped == set(range(self.num_blocks)), \
            "leaked blocks: neither free, cached, nor mapped"
        assert self.used_count + self.free_count == self.num_blocks
        if self.prefix is not None:
            self.prefix.check(self.refcount)
            for b in self._cached:
                assert b in self.prefix, f"cached block {b} not in the radix"


@dataclasses.dataclass
class GenerationResult:
    """One turn of batched sampling.

    ``tokens``/``logprobs`` are right-padded (B, T) host arrays; row ``b``
    holds ``counts[b]`` real entries (the pad id can also be a legitimately
    sampled token, so always slice by ``counts``).  Iterating yields
    ``(token_lists, logprob_lists)`` for tuple-unpack compatibility with the
    per-row list API.
    """
    tokens: np.ndarray             # (B, T) int32
    logprobs: np.ndarray           # (B, T) float32
    counts: np.ndarray             # (B,) int32 — real entries per row

    @property
    def batch(self) -> int:
        return self.tokens.shape[0]

    def token_lists(self) -> List[List[int]]:
        return [self.tokens[b, : int(self.counts[b])].tolist()
                for b in range(self.batch)]

    def logprob_lists(self) -> List[np.ndarray]:
        return [np.asarray(self.logprobs[b, : int(self.counts[b])],
                           np.float32) for b in range(self.batch)]

    @classmethod
    def from_lists(cls, token_lists: Sequence[Sequence[int]],
                   logprob_lists: Sequence[Sequence[float]],
                   pad_id: int = 0) -> "GenerationResult":
        B = len(token_lists)
        T = max((len(t) for t in token_lists), default=0)
        toks = np.full((B, T), pad_id, np.int32)
        lps = np.zeros((B, T), np.float32)
        counts = np.zeros((B,), np.int32)
        for b, (t, l) in enumerate(zip(token_lists, logprob_lists)):
            toks[b, : len(t)] = t
            lps[b, : len(l)] = np.asarray(l, np.float32)
            counts[b] = len(t)
        return cls(tokens=toks, logprobs=lps, counts=counts)

    def __iter__(self):
        yield self.token_lists()
        yield self.logprob_lists()


@dataclasses.dataclass
class DecodeSession:
    cache: object
    lengths: np.ndarray            # (B,) real tokens currently in cache
    last_logits: jnp.ndarray       # (B, V) logits at each row's last real token
    stopped: np.ndarray            # (B,) bool
    cross_kv: object = None        # enc-dec only
    allocator: Optional[BlockAllocator] = None   # paged mode only
    cache_policy: object = None                  # paged mode only

    @property
    def batch(self) -> int:
        return len(self.lengths)


class GenerationEngine:
    # Capability flag: this engine's ``generate`` accepts the round-slicing
    # controls (``step_offsets``/``row_budgets``), so the continuous
    # scheduler may split a logical turn across several calls.  Engine
    # doubles that lack the attribute are driven turn-per-round.
    supports_rounds = True

    def __init__(self, model: Model, params, pad_id: int, stop_ids: Sequence[int],
                 max_len: int = 1024, temperature: float = 1.0,
                 window: int = 0, cache_mode: str = "contiguous",
                 page_size: int = 16, num_blocks: int = 0,
                 kv_cache_dtype: str = "fp",
                 paged_kernel: Optional[bool] = None,
                 prefill_chunk: int = 0,
                 prefix_sharing: bool = True):
        """``cache_mode="paged"`` allocates KV memory as ``num_blocks`` blocks
        of ``page_size`` tokens shared by the whole batch (0 = one full
        ``max_len`` worth per row, i.e. the contiguous footprint — pass less
        to actually oversubscribe).  Requires window=0.

        Paged decode hot-path knobs: ``kv_cache_dtype`` "fp" (default,
        training-parity oracle) or "int8" (quantized block pools, 2x
        effective pool capacity).  GQA paged decode runs the Pallas
        block-table kernel on TPU, always: ``paged_kernel=False`` raises
        there.  On the CPU it takes the JAX gather by default, the kernel's
        parity oracle, and ``paged_kernel=True`` runs the kernel in
        interpret mode.  MLA and SSM models have no paged decode kernel and
        ignore the knob.  ``prefill_chunk``
        (0 = off; rounded up to the bucket size) streams long prompts
        through fixed-width compute chunks that write the paged pool
        incrementally, bounding prefill compile shapes at the chunk width.

        ``prefix_sharing`` (paged-only; on by default, inert in contiguous
        mode) dedups prompt prefills: identical prompts prefilled together
        share all their blocks (GRPO groups — the leader prefills once,
        followers remap + copy its ``last_logits``), and a radix index over
        full-block token chains lets *later* prompts remap any shared
        prefix (system prompt, few-shot header) without recompute.  Shared
        blocks are refcounted; the first write into one (the divergent
        token) triggers copy-on-write, so decode stays token-identical to
        unshared paging.
        """
        self.model = model
        self.weights = WeightStore(params)
        self.pad_id = pad_id
        self.stop_ids = tuple(stop_ids)
        self.max_len = max_len
        self.temperature = temperature
        self.window = window
        if cache_mode not in ("contiguous", "paged"):
            raise ValueError(f"unknown cache_mode {cache_mode!r}")
        if cache_mode == "paged" and window:
            raise ValueError("cache_mode='paged' requires window=0")
        if kv_cache_dtype != "fp" and cache_mode != "paged":
            raise ValueError("kv_cache_dtype requires cache_mode='paged' "
                             "(the contiguous cache is the fp oracle)")
        self.cache_mode = cache_mode
        self.page_size = page_size
        self.num_blocks = num_blocks
        self.kv_cache_dtype = kv_cache_dtype
        self.prefix_sharing = bool(prefix_sharing) and cache_mode == "paged"
        self.prefill_chunk = _bucket(prefill_chunk) if prefill_chunk else 0
        # resolved once per engine: the jitted impls read it at trace time
        self._use_paged_kernel = self._resolve_paged_kernel(paged_kernel)
        # the cache (argument 1) is donated: every caller replaces
        # ``session.cache`` with the returned one, so the pool is updated in
        # place instead of living twice in device memory during each call.
        # The programs carry fixed names (HLO modules ``jit_prefill``,
        # ``jit_decode_step``, ``jit_decode_loop``): profiles are read by them
        self._prefill_jit = jax.jit(
            obs.named_program(self._prefill_impl, "prefill"),
            donate_argnums=1)
        self._decode_jit = jax.jit(
            obs.named_program(self._decode_impl, "decode_step"),
            donate_argnums=1)
        self._loop_jit = jax.jit(
            obs.named_program(self._decode_loop_impl, "decode_loop"),
            donate_argnums=1, static_argnames=("T", "per_row"))

    def _resolve_paged_kernel(self, requested: Optional[bool]) -> bool:
        cfg = self.model.cfg
        gqa_paged = (self.cache_mode == "paged" and not cfg.uses_mla
                     and not cfg.is_attention_free)
        if not gqa_paged:
            return False
        on_cpu = kernels.interpret_mode()
        if requested is False and not on_cpu:
            raise ValueError(
                "paged_kernel=False: on TPU the GQA paged decode always runs "
                "the compiled Pallas kernel; the gather is a CPU oracle")
        return (not on_cpu) if requested is None else bool(requested)

    # --------------------------------------------------------- weight store
    @property
    def params(self):
        """The *active* decode params (the version the next round samples
        from).  Assignment keeps the legacy synchronous semantics: publish
        AND refresh immediately, so the new params take effect on the very
        next engine call — each assignment is one policy-version bump."""
        return self.weights.active_params

    @params.setter
    def params(self, new_params) -> None:
        self.weights.publish(new_params)
        self.weights.refresh()

    def publish(self, params) -> int:
        """Stage refreshed params (learner side).  Decoding keeps using the
        previous version until :meth:`refresh_weights` is called at a round
        boundary; returns the new version number."""
        return self.weights.publish(params)

    def refresh_weights(self) -> int:
        """Round-boundary sync point: swap active decode params to the
        latest published version; returns the active version."""
        return self.weights.refresh()

    @property
    def active_version(self) -> int:
        return self.weights.active

    @property
    def latest_version(self) -> int:
        return self.weights.version

    def pin_version(self, version: int) -> None:
        self.weights.pin(version)

    def unpin_version(self, version: int) -> None:
        self.weights.unpin(version)

    # ------------------------------------------------------------- paged API
    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` (0 in contiguous mode — every
        row's lane is preallocated)."""
        if self.cache_mode != "paged":
            return 0
        return max(0, math.ceil(n_tokens / self.page_size))

    @property
    def total_blocks(self) -> Optional[int]:
        """Configured pool size, or None when auto-sized at ``start``."""
        if self.cache_mode != "paged" or not self.num_blocks:
            return None
        return self.num_blocks

    def free_blocks(self, session: DecodeSession) -> Optional[int]:
        if session.allocator is None:
            return None
        return session.allocator.free_count

    def cache_utilization(self, session: DecodeSession) -> Optional[float]:
        """Fraction of pool blocks currently mapped to rows."""
        if session.allocator is None:
            return None
        a = session.allocator
        return a.used_count / max(a.num_blocks, 1)

    def blocks_needed(self, session: DecodeSession, row: int,
                      target_len: int) -> int:
        """Blocks ``row`` still has to claim to grow to ``target_len``
        tokens (0 in contiguous mode)."""
        if session.allocator is None:
            return 0
        a = session.allocator
        return max(0, a.blocks_for(target_len) - int(a.n_blocks[int(row)]))

    def can_alloc(self, session: DecodeSession, row: int,
                  target_len: int) -> bool:
        """Could ``row`` grow to ``target_len`` tokens right now?"""
        if session.allocator is None:
            return True
        return (self.blocks_needed(session, row, target_len)
                <= session.allocator.free_count)

    def admission_headroom(self, session: DecodeSession, budget: int) -> float:
        """Free blocks beyond what currently occupied rows may still claim to
        decode ``budget`` more tokens each — the scheduler admits a new task
        only if its worst-case footprint fits in this headroom, so admitting
        can never starve a live row's decode."""
        if session.allocator is None:
            return float("inf")
        a = session.allocator
        reserve = 0
        for r in range(session.batch):
            if a.n_blocks[r] > 0:
                target = min(int(session.lengths[r]) + budget, self.max_len)
                reserve += max(0, a.blocks_for(target) - int(a.n_blocks[r]))
        return a.free_count - reserve

    def prefix_stats(self, session: DecodeSession) -> Optional[dict]:
        """Sharing observability (None when sharing is off/contiguous):
        cumulative prompt-token hit rate, current/peak shared-block counts,
        copy-on-write and radix-eviction counters."""
        a = session.allocator
        if a is None or a.prefix is None:
            return None
        return {
            "prefix_hit_rate": a.shared_tokens / max(a.prompt_tokens, 1),
            "shared_blocks": a.shared_now,
            "shared_blocks_peak": a.peak_shared,
            "cow_count": a.cow_count,
            "shared_maps": a.shared_maps,
            "cached_blocks": a.cached_count,
            "prefix_evictions": a.prefix.evictions,
        }

    def live_shared_blocks(self, session: DecodeSession,
                           prompt_ids: Sequence[int]) -> int:
        """Full blocks of ``prompt_ids`` already resident AND referenced by
        a live row — the blocks a group-aware admission needn't charge.
        Cached-but-unreferenced radix blocks are *not* discounted: mapping
        them consumes reclaimable pool capacity the admission math already
        counts as free."""
        a = session.allocator
        if a is None or a.prefix is None or not len(prompt_ids):
            return 0
        ids = a.prefix.peek(list(prompt_ids),
                            (len(prompt_ids) - 1) // a.block_size)
        return sum(1 for b in ids if a.refcount[b] >= 1)

    def _clear_evicted(self, session: DecodeSession) -> None:
        """Pos-reset the blocks the radix index evicted since the last call:
        their slabs hold stale K/V a future occupant must not see.  Runs
        before anything writes into them."""
        a = session.allocator
        if a.pending_clear:
            blocks, a.pending_clear = a.pending_clear, []
            session.cache = self.model.reset_cache_rows(
                session.cache, np.zeros((0,), np.int64), self.max_len,
                self.window, policy=session.cache_policy,
                freed_blocks=blocks)

    def _sync_tables(self, session: DecodeSession) -> None:
        """Push the host block table into the device cache, but only when
        the allocator actually changed it — in the steady decode state
        (every row's capacity already covers its budget) this is a no-op.
        Evicted blocks still pending are pos-reset first."""
        a = session.allocator
        if not a.pending_clear and not a.dirty:
            return
        with obs.span("engine/sync_tables"):
            self._clear_evicted(session)
            if a.dirty:
                session.cache = session.cache_policy.set_tables(
                    session.cache, a.table)
                a.dirty = False

    # ------------------------------------------------------------- impl fns
    def _prefill_impl(self, params, cache, tokens, positions, valid, cross_kv):
        kw = {"cross_kv": cross_kv} if self.model.cfg.family == "encdec" else {}
        logits, new_cache = self.model.decode_step(
            params, tokens, positions, cache, window=self.window,
            kv_valid=valid, paged_kernel=self._use_paged_kernel, **kw)
        return logits, new_cache

    def _decode_impl(self, params, cache, tokens, positions, valid, cross_kv):
        """One-token step for the Python-loop reference decoder."""
        kw = {"cross_kv": cross_kv} if self.model.cfg.family == "encdec" else {}
        logits, new_cache = self.model.decode_step(
            params, tokens, positions, cache, window=self.window,
            kv_valid=valid[:, None], paged_kernel=self._use_paged_kernel,
            **kw)
        return logits[:, 0, :], new_cache

    def _decode_loop_impl(self, params, cache, last_logits, lengths, stopped,
                          key, row_keys, n_max, temperature, stop_arr,
                          cross_kv, offsets, budgets, *, T, per_row):
        """Fused decode turn: a while_loop carrying the cache on device.

        ``T`` (static) is the bucketed output-buffer width; ``n_max``
        (dynamic, <= T) is the actual token budget, so different budgets in
        the same bucket share one executable.  Each iteration samples from
        ``last_logits``, records the token + sampling logprob for active
        rows, writes the token into the cache (pads carry kv_valid=False),
        and deactivates rows that emitted a stop id or filled the context.

        ``per_row`` (static) selects the sampling stream: False draws every
        step from one shared split chain of ``key``; True draws row ``b``'s
        step ``t`` from ``fold_in(row_keys[b], offsets[b] + t)`` so each
        row's randomness is independent of the batch composition, and of how
        a logical turn is sliced into calls (continuous batching rounds).
        ``budgets`` (B,) caps tokens per row within this call (<= n_max).
        """
        B = last_logits.shape[0]
        pad = jnp.int32(self.pad_id)
        max_pos = jnp.int32(self.max_len - 1)
        kw = {"cross_kv": cross_kv} if self.model.cfg.family == "encdec" else {}

        def cond(carry):
            t, _, _, _, _, active, _, _, _ = carry
            return (t < n_max) & jnp.any(active)

        def body(carry):
            t, key, cache, last_logits, lengths, active, toks, lps, counts = carry
            if per_row:
                step_keys = jax.vmap(jax.random.fold_in)(row_keys, offsets + t)
                tok, lp = _sample_rows(last_logits, step_keys, temperature)
            else:
                key, sub = jax.random.split(key)
                tok, lp = _sample(last_logits, sub, temperature)
            tok = tok.astype(jnp.int32)
            accept = active
            toks = toks.at[:, t].set(jnp.where(accept, tok, pad))
            lps = lps.at[:, t].set(jnp.where(accept, lp, 0.0))
            counts = counts + accept.astype(jnp.int32)
            hit_stop = jnp.any(tok[:, None] == stop_arr[None, :], axis=-1)
            feed = jnp.where(accept, tok, pad)[:, None]
            pos = lengths[:, None]
            logits, cache = self.model.decode_step(
                params, feed, pos, cache, window=self.window,
                kv_valid=accept[:, None],
                paged_kernel=self._use_paged_kernel, **kw)
            last_logits = jnp.where(accept[:, None], logits[:, 0, :],
                                    last_logits)
            lengths = lengths + accept.astype(lengths.dtype)
            active = (accept & ~hit_stop & (lengths < max_pos)
                      & (counts < budgets))
            return (t + 1, key, cache, last_logits, lengths, active,
                    toks, lps, counts)

        init = (jnp.int32(0), key, cache, last_logits, lengths,
                (~stopped) & (lengths < max_pos) & (budgets > 0),
                jnp.full((B, T), pad, jnp.int32),
                jnp.zeros((B, T), jnp.float32),
                jnp.zeros((B,), jnp.int32))
        (_, _, cache, last_logits, lengths, _, toks, lps, counts) = \
            jax.lax.while_loop(cond, body, init)
        stopped = stopped | (lengths >= max_pos)
        return toks, lps, counts, cache, last_logits, lengths, stopped

    # ------------------------------------------------------------- session ops
    def start(self, contexts: List[List[int]], prefix_embeds=None) -> DecodeSession:
        with obs.span("engine/start", rows=len(contexts)):
            return self._start(contexts, prefix_embeds)

    def _start(self, contexts, prefix_embeds) -> DecodeSession:
        B = len(contexts)
        cross_kv = None
        if self.model.cfg.family == "encdec":
            from repro.models import transformer as T
            enc = T.encdec_encode(self.params, self.model.cfg,
                                  jnp.asarray(prefix_embeds))
            cross_kv = T.encdec_cross_kv(self.params, self.model.cfg, enc)
        allocator = policy = None
        if self.cache_mode == "paged":
            per_row = max(1, math.ceil(self.max_len / self.page_size))
            n_blocks = self.num_blocks or B * per_row
            policy = PagedCache(block_size=self.page_size,
                                num_blocks=n_blocks,
                                kv_dtype=self.kv_cache_dtype)
            prefix = (RadixPrefixIndex(self.page_size)
                      if self.prefix_sharing else None)
            allocator = BlockAllocator(n_blocks, self.page_size, B, per_row,
                                       prefix=prefix)
            cache = self.model.init_cache(B, self.max_len, self.window,
                                          policy=policy)
        else:
            cache = self.model.init_cache(B, self.max_len, self.window)
        session = DecodeSession(
            cache=cache,
            lengths=np.zeros((B,), np.int64),
            last_logits=jnp.zeros((B, self.model.cfg.vocab_size)),
            stopped=np.zeros((B,), bool),
            cross_kv=cross_kv,
            allocator=allocator,
            cache_policy=policy,
        )
        self.extend(session, contexts)
        return session

    def extend(self, session: DecodeSession, new_tokens: List[List[int]]) -> None:
        """Prefill ragged per-row token lists into the session cache.

        With ``prefix_sharing`` on (paged mode), rows prefilled from length
        0 first go through the sharing plan: identical prompts in this call
        collapse to one leader prefill (followers remap every leader block,
        including the partial tail, and copy its ``last_logits``), and each
        leader maps the longest radix-indexed full-block chain of its
        prompt before prefilling only the unshared suffix.  Chunked prefill
        therefore never recomputes a shared block — chunks stream only the
        suffix.

        With ``prefill_chunk`` set, prompts longer than one chunk stream
        through fixed-width compute chunks: each chunk maps only the pool
        blocks it needs, prefills at a bounded (bucketed) width, and updates
        ``last_logits`` for rows whose final new token lands in it — so a
        32k prompt costs many ``prefill_chunk``-wide compiles instead of one
        32k-wide one, and the paged pool fills incrementally.
        """
        lens = np.array([len(t) for t in new_tokens], np.int64)
        if lens.max(initial=0) == 0:
            return
        if not self.window and (session.lengths + lens).max() > self.max_len:
            raise ValueError(
                f"context overflow: extend to {(session.lengths + lens).max()} "
                f"tokens > engine max_len={self.max_len}; raise max_len or "
                f"shorten prompts")
        n_tok = int(lens.sum())
        obs.get().registry.counter("engine/prefill_tokens").add(n_tok)
        with obs.span("engine/extend", tokens=n_tok,
                      rows=int((lens > 0).sum())):
            with obs.span("engine/share"):
                work, shared = self._share_prefixes(session, new_tokens)
            wmax = max((len(t) for t in work), default=0)
            C = self.prefill_chunk
            if C and wmax > C:
                for c0 in range(0, wmax, C):
                    self._extend_once(session,
                                      [list(t[c0:c0 + C]) for t in work])
            elif wmax:
                self._extend_once(session, work)
            if shared is not None:
                with obs.span("engine/share"):
                    self._finish_sharing(session, shared)

    def _share_prefixes(self, session: DecodeSession,
                        new_tokens: List[List[int]]):
        """Sharing plan for one ``extend``: returns ``(work, plan)`` where
        ``work`` is what actually needs prefilling (followers of an
        identical prompt drop to ``[]``, radix-hit rows to their unshared
        suffix) and ``plan`` carries the post-prefill bookkeeping for
        :meth:`_finish_sharing`.  Only rows starting from length 0 are
        share-eligible: their token list IS their full context, so full
        blocks can be keyed by absolute position in the radix.

        Radix lookups are capped at full blocks covering at most
        ``len(prompt) - 1`` tokens, so a leader always prefills >= 1 token
        and its ``last_logits`` come from a real forward of its own row.
        """
        a = session.allocator
        if a is None or a.prefix is None:
            return new_tokens, None
        bs = a.block_size
        o = obs.get()
        shared_tokens = o.registry.counter("engine/shared_prefix_tokens")
        work = [list(t) for t in new_tokens]
        leaders: dict = {}          # prompt tuple -> leader row
        followers: List[Tuple[int, int, int]] = []   # (row, leader, n_tok)
        registrations: List[Tuple[int, List[int]]] = []
        for i, t in enumerate(new_tokens):
            if len(t) == 0 or int(session.lengths[i]) != 0:
                continue
            a.prompt_tokens += len(t)
            key = tuple(t)
            lead = leaders.get(key)
            if lead is not None:
                # group member: share EVERYTHING (partial tail included) and
                # skip prefill entirely; the tail copy-on-writes on the
                # first decoded token
                followers.append((i, lead, len(t)))
                work[i] = []
                a.shared_tokens += len(t)
                shared_tokens.add(len(t))
                continue
            leaders[key] = i
            hit = a.prefix.lookup(t, (len(t) - 1) // bs)
            if hit:
                a.map_shared(i, hit)
                n_hit = len(hit) * bs
                session.lengths[i] += n_hit
                a.shared_tokens += n_hit
                shared_tokens.add(n_hit)
                work[i] = list(t[n_hit:])
                o.registry.counter("engine/radix_hits").add()
                o.registry.counter("engine/radix_hit_tokens").add(n_hit)
                if o.tracing:
                    o.tracer.instant("cache", "radix_hit", row=i,
                                     tokens=n_hit, blocks=len(hit))
            registrations.append((i, list(t)))
        return work, (followers, registrations)

    def _finish_sharing(self, session: DecodeSession, plan) -> None:
        """Post-prefill half of the sharing plan: register every leader's
        full prompt blocks in the radix (now that they hold real K/V), then
        map followers onto their leader's blocks and copy its
        ``last_logits`` — an identical prompt under identical params yields
        identical logits, so the follower's decode is indistinguishable
        from having prefilled itself."""
        followers, registrations = plan
        a = session.allocator
        for row, toks in registrations:
            n_full = len(toks) // a.block_size
            if n_full:
                a.prefix.insert(toks,
                                [int(b) for b in a.table[row, :n_full]])
        if followers:
            o = obs.get()
            for row, lead, n in followers:
                a.map_shared(row, [int(b)
                                   for b in a.table[lead, :a.blocks_for(n)]])
                session.lengths[row] += n
                if o.tracing:
                    # the write-after-share contract trace_check verifies:
                    # G sharers must produce G-1 cow events before decoding
                    o.tracer.instant("cache", "shared_tail", row=row,
                                     leader=lead)
            rows = jnp.asarray([f[0] for f in followers])
            leads = jnp.asarray([f[1] for f in followers])
            session.last_logits = session.last_logits.at[rows].set(
                session.last_logits[leads])
        if a.dirty:
            self._sync_tables(session)

    def _cow_plan(self, session: DecodeSession, row: int, start: int,
                  end: int, copies: List[Tuple[int, int, int]]) -> bool:
        """Host-side copy-on-write barrier: before any device write to
        positions ``[start, end)`` of ``row``, replace each block in that
        range the row shares with other rows (refcount > 1) by a private
        one — allocate and remap the row's table slot, appending
        ``(row, src, dst)`` to ``copies`` for :meth:`_cow_copy`, which
        slab-copies the K/V device-side before the table sync.  Radix-indexed
        *full* blocks never appear in a write range (writes start at the
        row's length, past every full block), so only group-shared partial
        tails ever copy.  Returns False when the pool cannot supply a
        replacement (caller backpressures; planned copies stay valid)."""
        a = session.allocator
        if a is None or end <= start:
            return True
        bs = a.block_size
        b1 = min((end - 1) // bs, int(a.n_blocks[row]) - 1)
        for bi in range(start // bs, b1 + 1):
            if a.refcount[int(a.table[row, bi])] > 1:
                s, d = a.cow(row, bi)
                if d < 0:
                    return False
                copies.append((row, s, d))
        return True

    def _cow_copy(self, session: DecodeSession,
                  copies: List[Tuple[int, int, int]]) -> None:
        """The device half of copy-on-write: every planned block copy of
        one engine call, in one pool update."""
        if not copies:
            return
        # (the tracer's ``cow`` name belongs to the per-row instants below,
        # which trace_check reads)
        with obs.span("engine/cow", event="cow_copy", blocks=len(copies)):
            # a copy may land in a block the radix just evicted: reset it
            # first, or the reset that follows would erase the copy
            self._clear_evicted(session)
            session.cache = self.model.copy_cache_blocks(
                session.cache, [c[1] for c in copies],
                [c[2] for c in copies], policy=session.cache_policy)
        o = obs.get()
        o.registry.counter("engine/cow_copies").add(len(copies))
        if o.tracing:
            for row in sorted({c[0] for c in copies}):
                o.tracer.instant("cache", "cow", row=row,
                                 blocks=sum(c[0] == row for c in copies))

    def _extend_once(self, session: DecodeSession,
                     new_tokens: List[List[int]]) -> None:
        """One bucketed prefill call (a whole extend, or one chunk of it)."""
        lens = np.array([len(t) for t in new_tokens], np.int64)
        if lens.max(initial=0) == 0:
            return
        if session.allocator is not None:
            # prefill needs full coverage: map blocks for every new token
            # before any position is written (no partial prefills)
            copies: List[Tuple[int, int, int]] = []
            with obs.span("engine/alloc"):
                for i, n in enumerate(lens):
                    if n == 0:
                        continue
                    start = int(session.lengths[i])
                    target = start + int(n)
                    if session.allocator.ensure(i, target) < target \
                            or not self._cow_plan(session, i, start, target,
                                                  copies):
                        raise RuntimeError(
                            f"paged KV pool exhausted: row {i} needs "
                            f"{session.allocator.blocks_for(target)} blocks, "
                            f"{session.allocator.free_count} free; raise "
                            f"num_blocks or gate admission on free blocks")
            self._cow_copy(session, copies)
            self._sync_tables(session)
        with obs.span("engine/prefill", tokens=int(lens.sum()),
                      rows=int((lens > 0).sum())):
            self._prefill(session, new_tokens, lens)

    def _prefill(self, session: DecodeSession, new_tokens: List[List[int]],
                 lens) -> None:
        """Pad, dispatch the prefill program, gather ``last_logits``."""
        B = session.batch
        L = _bucket(int(lens.max()))
        toks = np.full((B, L), self.pad_id, np.int32)
        pos = np.zeros((B, L), np.int32)
        valid = np.zeros((B, L), bool)
        for i, t in enumerate(new_tokens):
            toks[i, :len(t)] = t
            valid[i, :len(t)] = True
            pos[i] = session.lengths[i] + np.arange(L)
        if not self.window:
            # Right-pad positions can exceed max_len when a row is near the
            # end of its context (L is bucketed): unclamped they would wrap
            # modulo the cache width and overwrite the *start* of the row's
            # lane with pos=-1.  Clamp pads onto the last slot instead (real
            # positions are < max_len by the overflow check above).
            pos = np.minimum(pos, self.max_len - 1)
        logits, session.cache = self._prefill_jit(
            self.params, session.cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(valid), session.cross_kv)
        # logits at each row's last *new* real token (rows w/o new tokens keep old)
        idx = np.maximum(lens - 1, 0)
        gathered = jnp.take_along_axis(
            logits, jnp.asarray(idx)[:, None, None], axis=1)[:, 0, :]
        has_new = jnp.asarray(lens > 0)[:, None]
        session.last_logits = jnp.where(has_new, gathered, session.last_logits)
        session.lengths = session.lengths + lens

    def extend_rows(self, session: DecodeSession, rows: Sequence[int],
                    token_lists: List[List[int]]) -> None:
        """Prefill tokens into a *subset* of rows and revive them.

        ``token_lists`` aligns with ``rows``; every other row's cache lane,
        length and ``last_logits`` are untouched (the prefill sees them with
        zero new tokens).  Used by the continuous-batching scheduler to
        deliver a tool observation to a parked row, or a fresh prompt to a
        just-reset slot, while the rest of the batch keeps its state.
        """
        full: List[List[int]] = [[] for _ in range(session.batch)]
        for r, t in zip(rows, token_lists):
            full[int(r)] = list(t)
        self.extend(session, full)
        stopped = np.asarray(session.stopped).copy()
        stopped[np.asarray(list(rows), np.int64)] = False
        session.stopped = stopped

    def reset_rows(self, session: DecodeSession, rows: Sequence[int]) -> None:
        """Return individual cache lanes to their pristine state for reuse.

        The rows' lanes are re-initialized (attention pos=-1 everywhere, SSM
        conv/state zeroed) so no KV/state from the previous occupant can leak
        into the next one; lengths go to 0, ``last_logits`` to 0, and the
        rows are marked ``stopped`` until re-primed via :meth:`extend_rows`.
        Neighbouring rows are untouched.  (encdec ``cross_kv`` is per-episode
        and not re-primed here — continuous batching targets decoder-only
        families.)
        """
        idx = np.asarray(list(rows), np.int64)
        if idx.size == 0:
            return
        with obs.span("engine/reset", rows=int(idx.size)):
            a = session.allocator
            with obs.span("engine/free"):
                freed = None if a is None else a.free_rows(idx)
                session.cache = self.model.reset_cache_rows(
                    session.cache, idx, self.max_len, self.window,
                    policy=session.cache_policy, freed_blocks=freed)
            if a is not None:
                self._sync_tables(session)
            session.last_logits = session.last_logits.at[
                jnp.asarray(idx)].set(0.0)
        lengths = np.asarray(session.lengths).copy()
        lengths[idx] = 0
        session.lengths = lengths
        stopped = np.asarray(session.stopped).copy()
        stopped[idx] = True
        session.stopped = stopped

    def generate(self, session: DecodeSession, max_new_tokens: int,
                 key: Optional[jax.Array] = None,
                 temperature: Optional[float] = None,
                 row_keys: Optional[jax.Array] = None,
                 step_offsets=None, row_budgets=None) -> GenerationResult:
        """Sample per-row continuations until a stop id / budget / max_len.

        Runs the fused on-device decode loop; the result (including the stop
        id, when one was emitted) comes back as one batched
        :class:`GenerationResult`.  Rows already stopped generate nothing;
        rows that fill the context are marked ``session.stopped`` so later
        turns skip them.

        ``row_keys`` (B, 2) switches sampling to independent per-row streams
        (row ``b``, step ``t`` draws from ``fold_in(row_keys[b], t)``): a
        row's tokens then depend only on its own key and context, never on
        which rows share the batch — required by the continuous-batching
        scheduler for parity with the turn-synchronous reference.

        ``step_offsets`` (B,) shifts each row's sampling-stream index (step
        ``t`` draws from ``fold_in(row_keys[b], step_offsets[b] + t)``) and
        ``row_budgets`` (B,) caps tokens per row within this call: together
        they let the scheduler split one logical turn across several calls
        (adaptive round budgets) without changing any sampled token.

        In paged mode, blocks for each active row's worst-case growth are
        mapped before entering the loop; if the pool cannot cover a row's
        full budget, that row's budget shrinks to its mapped capacity (0 =
        starved this call — the caller retries once blocks free up).
        """
        per_row = row_keys is not None
        if not per_row and key is None:
            raise ValueError("generate() needs either key or row_keys")
        temp = self.temperature if temperature is None else temperature
        T = _bucket(max_new_tokens)
        B = session.batch
        budgets = np.full((B,), min(max_new_tokens, T), np.int32)
        if row_budgets is not None:
            budgets = np.minimum(budgets, np.asarray(row_budgets, np.int32))
        offsets = (np.zeros((B,), np.int32) if step_offsets is None
                   else np.asarray(step_offsets, np.int32))
        with obs.span("engine/generate", width=T):
            if session.allocator is not None:
                self._map_decode_blocks(session, budgets)
            stop_arr = jnp.asarray(np.asarray(self.stop_ids, np.int32)
                                   .reshape(-1))
            with obs.span("engine/decode_loop"):
                toks, lps, counts, cache, last_logits, lengths, stopped = \
                    self._loop_jit(
                        self.params, session.cache, session.last_logits,
                        jnp.asarray(session.lengths, jnp.int32),
                        jnp.asarray(session.stopped),
                        None if per_row else key,
                        jnp.asarray(row_keys) if per_row else None,
                        jnp.int32(int(budgets.max(initial=0))),
                        jnp.float32(temp), stop_arr, session.cross_kv,
                        jnp.asarray(offsets), jnp.asarray(budgets), T=T,
                        per_row=per_row)
            session.cache = cache
            session.last_logits = last_logits
            # single host materialization per turn
            with obs.span("engine/fetch"):
                toks, lps, counts, lengths, stopped = jax.device_get(
                    (toks, lps, counts, lengths, stopped))
        # writable host copies (device_get buffers are read-only; rollout
        # mutates session.stopped per row)
        session.lengths = np.array(lengths, np.int64)
        session.stopped = np.array(stopped, bool)
        return GenerationResult(tokens=np.asarray(toks),
                                logprobs=np.asarray(lps),
                                counts=np.asarray(counts))

    def _map_decode_blocks(self, session: DecodeSession,
                           budgets: np.ndarray) -> None:
        """Map blocks for each live row's decode budget (in place: a row
        the pool cannot cover shrinks to its mapped capacity, 0 = starved
        this call), copy-on-write the shared blocks its writes will reach,
        and push the table to the device."""
        copies: List[Tuple[int, int, int]] = []
        with obs.span("engine/alloc"):
            stopped_now = np.asarray(session.stopped)
            for r in range(session.batch):
                if stopped_now[r] or budgets[r] <= 0:
                    continue
                cur = int(session.lengths[r])
                target = min(cur + int(budgets[r]), self.max_len)
                cap = session.allocator.ensure(r, target)
                budgets[r] = max(0, min(int(budgets[r]), cap - cur))
                # copy-on-write barrier: the first decoded token may land in
                # a block shared with the row's prompt-group (the partial
                # tail); give the row a private copy before the device loop
                # writes.  A failed copy starves the row this call.
                if budgets[r] > 0 and not self._cow_plan(
                        session, r, cur, cur + int(budgets[r]), copies):
                    budgets[r] = 0
        self._cow_copy(session, copies)
        self._sync_tables(session)

    def generate_reference(self, session: DecodeSession, max_new_tokens: int,
                           key: Optional[jax.Array] = None,
                           temperature: Optional[float] = None,
                           row_keys: Optional[jax.Array] = None,
                           step_offsets=None, row_budgets=None
                           ) -> GenerationResult:
        """Per-token Python-loop decoder (the seed implementation).

        Semantically identical to :meth:`generate` (including the per-row
        ``row_keys`` sampling mode and the ``step_offsets``/``row_budgets``
        round-slicing controls) — kept as the parity oracle
        (tests/test_serving.py) and the baseline the decode-throughput
        benchmark measures the fused loop against.
        """
        temp = self.temperature if temperature is None else temperature
        B = session.batch
        out_tokens: List[List[int]] = [[] for _ in range(B)]
        out_logps: List[List[float]] = [[] for _ in range(B)]
        budgets = np.full((B,), max_new_tokens, np.int64)
        if row_budgets is not None:
            budgets = np.minimum(budgets, np.asarray(row_budgets, np.int64))
        offsets = (np.zeros((B,), np.int32) if step_offsets is None
                   else np.asarray(step_offsets, np.int32))
        if session.allocator is not None:
            # same block mapping and CoW barrier as the fused path: without
            # it, decoded positions past the last mapped block would route
            # to the trash block and silently vanish from attention
            self._map_decode_blocks(session, budgets)
        active = (~session.stopped & (session.lengths < self.max_len - 1)
                  & (budgets > 0))

        for step in range(max_new_tokens):
            if not active.any():
                break
            if row_keys is not None:
                step_keys = jax.vmap(jax.random.fold_in)(
                    jnp.asarray(row_keys),
                    jnp.asarray(offsets + step, jnp.int32))
                cur_tok, cur_lp = _sample_rows(session.last_logits, step_keys,
                                               jnp.float32(temp))
            else:
                key, sub = jax.random.split(key)
                cur_tok, cur_lp = _sample(session.last_logits, sub,
                                          jnp.float32(temp))
            cur_tok, cur_lp = np.asarray(cur_tok), np.asarray(cur_lp)
            accept = active.copy()
            for i in range(B):
                if accept[i]:
                    t = int(cur_tok[i])
                    out_tokens[i].append(t)
                    out_logps[i].append(float(cur_lp[i]))
                    if t in self.stop_ids:
                        active[i] = False
            feed = np.where(accept, cur_tok, self.pad_id).astype(np.int32)
            pos = session.lengths.astype(np.int32)
            logits, session.cache = self._decode_jit(
                self.params, session.cache, jnp.asarray(feed)[:, None],
                jnp.asarray(pos)[:, None], jnp.asarray(accept),
                session.cross_kv)
            session.last_logits = jnp.where(jnp.asarray(accept)[:, None],
                                            logits, session.last_logits)
            session.lengths = session.lengths + accept.astype(np.int64)
            active &= session.lengths < self.max_len - 1
            active &= np.array([len(t) for t in out_tokens]) < budgets

        session.stopped = session.stopped | (session.lengths >= self.max_len - 1)
        return GenerationResult.from_lists(out_tokens, out_logps,
                                           pad_id=self.pad_id)


def _sample(logits: jnp.ndarray, key: jax.Array, temperature) -> tuple:
    """Returns (token (B,), logprob-of-token (B,)) at the given temperature.

    The recorded logprob is taken from the *sampling distribution itself*
    (softmax of ``logits / temperature``), matching veRL/RLFactory: the
    behaviour distribution the importance ratio divides by is the tempered
    one actually used to draw the token.  Greedy decoding (temperature ~ 0)
    is a delta distribution, so its logprob is 0.
    """
    temperature = jnp.asarray(temperature, jnp.float32)

    def do_sample(_):
        scaled = jax.nn.log_softmax(logits / jnp.maximum(temperature, 1e-6),
                                    axis=-1)
        tok = jax.random.categorical(key, scaled, axis=-1)
        lp = jnp.take_along_axis(scaled, tok[:, None], axis=-1)[:, 0]
        return tok, lp

    def do_greedy(_):
        tok = jnp.argmax(logits, axis=-1)
        return tok, jnp.zeros(logits.shape[:-1], jnp.float32)

    return jax.lax.cond(temperature > 1e-6, do_sample, do_greedy,
                        operand=None)


def _sample_rows(logits: jnp.ndarray, keys: jnp.ndarray, temperature) -> tuple:
    """Per-row-key variant of :func:`_sample`: row ``b`` draws with its own
    ``keys[b]``, so the sample is a function of that row's logits and key
    alone (batch-composition independence for continuous batching).  Same
    tempered-distribution logprob contract as :func:`_sample`."""
    temperature = jnp.asarray(temperature, jnp.float32)

    def do_sample(_):
        scaled = jax.nn.log_softmax(logits / jnp.maximum(temperature, 1e-6),
                                    axis=-1)
        tok = jax.vmap(jax.random.categorical)(keys, scaled)
        lp = jnp.take_along_axis(scaled, tok[:, None], axis=-1)[:, 0]
        return tok, lp

    def do_greedy(_):
        tok = jnp.argmax(logits, axis=-1)
        return tok, jnp.zeros(logits.shape[:-1], jnp.float32)

    return jax.lax.cond(temperature > 1e-6, do_sample, do_greedy,
                        operand=None)
