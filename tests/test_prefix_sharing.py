"""Prefix sharing over the paged KV cache (ISSUE 8): refcounted group
sharing of prompt blocks, copy-on-write on first divergence, radix-index
reuse across extend calls, LRU eviction of cached chains under pool
pressure, and the scheduler-level stats surface.  Every scenario ends with
``BlockAllocator.check()`` — the free/used/cached partition and table
refcount sums must balance after any sequence of share/CoW/free."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.rollout import RolloutConfig, RolloutWorker
from repro.data.tokenizer import default_tokenizer
from repro.models import Model
from repro.serving.engine import GenerationEngine
from repro.serving.prefix_index import RadixPrefixIndex
from repro.tools.search_env import SearchEnv

BS = 16  # page size used throughout


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = default_tokenizer(cfg.vocab_size)
    return cfg, model, params, tok


def _engine(model, params, tok, *, sharing, max_len=256, num_blocks=0):
    return GenerationEngine(model, params, pad_id=tok.pad_id,
                            stop_ids=(tok.eos_id,), max_len=max_len,
                            temperature=1.0, cache_mode="paged",
                            page_size=BS, num_blocks=num_blocks,
                            prefix_sharing=sharing)


def _ids(n, seed=0):
    """Deterministic prompt of n token ids (kept < 50, well inside vocab)."""
    return [(i * 7 + seed * 11 + 3) % 50 for i in range(n)]


def _assert_parity(ra, rb):
    assert ra.token_lists() == rb.token_lists()
    for la, lb in zip(ra.logprob_lists(), rb.logprob_lists()):
        np.testing.assert_allclose(la, lb, atol=1e-5)


def test_group_sharing_exact_block_multiple(setup):
    """A prompt that is an exact multiple of block_size shares fully with
    zero copy-on-write: every prompt block stays at refcount G and the first
    decoded token opens each row's own fresh block."""
    cfg, model, params, tok = setup
    prompt = _ids(2 * BS)                    # exactly 2 full blocks
    ctx = [list(prompt)] * 3 + [_ids(20, seed=5)]
    rk = jax.random.split(jax.random.PRNGKey(4), len(ctx))

    on = _engine(model, params, tok, sharing=True)
    s = on.start([list(c) for c in ctx])
    a = s.allocator
    assert a.shared_maps == 2 * 2            # 2 followers x 2 blocks each
    assert a.used_count == 2 + 2             # shared pair + the odd row's 2
    r_on = on.generate(s, 10, row_keys=rk)
    assert a.cow_count == 0                  # nothing ever wrote a shared block
    assert a.shared_now == 2
    a.check()

    off = _engine(model, params, tok, sharing=False)
    s2 = off.start([list(c) for c in ctx])
    r_off = off.generate(s2, 10, row_keys=rk)
    _assert_parity(r_on, r_off)
    assert s2.allocator.used_count > a.used_count   # sharing saved blocks


def test_group_sharing_partial_tail_cow(setup):
    """G identical prompts with a partial tail block: followers map the tail
    too (refcount G) and the first decoded token copy-on-writes it — exactly
    G-1 copies, since the last writer owns the block at refcount 1."""
    cfg, model, params, tok = setup
    G = 3
    prompt = _ids(2 * BS + 8, seed=1)        # 2 full blocks + 8-token tail
    rk = jax.random.split(jax.random.PRNGKey(6), G)

    on = _engine(model, params, tok, sharing=True)
    s = on.start([list(prompt)] * G)
    r_on = on.generate(s, 8, row_keys=rk)
    assert s.allocator.cow_count == G - 1
    s.allocator.check()

    off = _engine(model, params, tok, sharing=False)
    s2 = off.start([list(prompt)] * G)
    r_off = off.generate(s2, 8, row_keys=rk)
    _assert_parity(r_on, r_off)


def test_single_row_group_no_overhead(setup):
    """G=1: no followers, no shared blocks, no CoW — sharing must be inert
    apart from registering the prompt's full blocks in the radix."""
    cfg, model, params, tok = setup
    prompt = _ids(BS + 5, seed=2)
    rk = jax.random.split(jax.random.PRNGKey(8), 1)

    on = _engine(model, params, tok, sharing=True)
    s = on.start([list(prompt)])
    r_on = on.generate(s, 8, row_keys=rk)
    a = s.allocator
    assert a.shared_maps == 0 and a.cow_count == 0 and a.shared_now == 0
    assert len(a.prefix) == 1                # the single full block, indexed
    a.check()

    off = _engine(model, params, tok, sharing=False)
    s2 = off.start([list(prompt)])
    r_off = off.generate(s2, 8, row_keys=rk)
    _assert_parity(r_on, r_off)
    assert s2.allocator.used_count == a.used_count


def test_radix_hit_on_prefix_of_full_blocks(setup):
    """Cross-call reuse where the radix covers only a *prefix* of the new
    prompt's full blocks: prompt B = P + fresh suffix hits P's 2 indexed
    blocks out of the 3 full blocks it asked for, prefills only the suffix,
    and still decodes token-identically to an unshared engine."""
    cfg, model, params, tok = setup
    P = _ids(2 * BS, seed=3)                           # the shared header
    A = P + _ids(8, seed=4)                            # first occupant
    B = P + _ids(20, seed=9)                           # 52 tokens, 3 full blocks
    rk = jax.random.split(jax.random.PRNGKey(11), 1)

    on = _engine(model, params, tok, sharing=True)
    s = on.start([list(A)])
    on.generate(s, 6, row_keys=rk)
    on.reset_rows(s, [0])                              # A's full blocks -> cached
    a = s.allocator
    assert a.cached_count == 2 and a.used_count == 0
    h0, l0 = a.prefix.hit_blocks, a.prefix.lookup_blocks
    on.extend_rows(s, [0], [list(B)])
    assert a.prefix.hit_blocks - h0 == 2               # P's chain served
    assert a.prefix.lookup_blocks - l0 == 3            # of the 3 asked for
    assert int(s.lengths[0]) == len(B)
    r_on = on.generate(s, 8, row_keys=rk)
    a.check()

    off = _engine(model, params, tok, sharing=False)
    s2 = off.start([list(B)])
    r_off = off.generate(s2, 8, row_keys=rk)
    _assert_parity(r_on, r_off)


def test_radix_lru_eviction_under_pressure(setup):
    """When the free list runs dry, cached (refcount-0) radix chains are
    reclaimed LRU-leaf-first and their slabs pos-cleared before reuse: a
    distinct prompt displacing a cached chain still decodes exactly like a
    fresh unshared engine, and the allocator partition stays balanced."""
    cfg, model, params, tok = setup
    # 4-block pool: A occupies 3 (2 full + tail), reset caches the 2 full
    eng = _engine(model, params, tok, sharing=True, max_len=64, num_blocks=4)
    A = _ids(2 * BS + 1, seed=6)
    B = _ids(2 * BS + 8, seed=7)
    rk = jax.random.split(jax.random.PRNGKey(13), 1)

    s = eng.start([list(A)])
    eng.generate(s, 4, row_keys=rk)
    eng.reset_rows(s, [0])
    a = s.allocator
    assert a.cached_count == 2
    eng.extend_rows(s, [0], [list(B)])       # needs 3 blocks, 2 free -> evict
    assert a.prefix.evictions >= 1
    r_on = eng.generate(s, 6, row_keys=rk)
    a.check()

    off = _engine(model, params, tok, sharing=False, max_len=64, num_blocks=4)
    s2 = off.start([list(B)])
    r_off = off.generate(s2, 6, row_keys=rk)
    _assert_parity(r_on, r_off)


def test_cow_into_an_evicted_radix_block_keeps_the_copy(setup):
    """With the free list empty, a copy-on-write takes a block the radix
    evicts in the same call.  The evicted block's reset must come before
    the copy, not after it: decode stays token-identical to unshared
    paging."""
    cfg, model, params, tok = setup
    cached, group = _ids(40, seed=1), _ids(40, seed=2)   # 2 full + 8
    rk = jax.random.split(jax.random.PRNGKey(1), 3)

    on = _engine(model, params, tok, sharing=True, num_blocks=16)
    s = on.start([cached, group, group])
    on.reset_rows(s, [0])           # its 2 full blocks stay cached
    a = s.allocator
    assert a.cached_count == 2
    on.extend_rows(s, [0], [_ids(BS * a.free_count - BS * 2, seed=3)])
    assert a.free_count == a.cached_count == 2   # only the cached chain
    res_on = on.generate(s, 8, None, row_keys=rk)
    assert a.cow_count == 1 and a.prefix.evictions == 2
    a.check()

    off = _engine(model, params, tok, sharing=False)
    s2 = off.start([cached, group, group])
    off.reset_rows(s2, [0])
    res_off = off.generate(s2, 8, None, row_keys=rk)
    # rows 1-2: the group; row 1 copied its tail into an evicted block
    for la, lb in zip(res_on.logprob_lists()[1:],
                      res_off.logprob_lists()[1:]):
        np.testing.assert_allclose(la, lb, atol=1e-5)
    assert res_on.token_lists()[1:] == res_off.token_lists()[1:]


def test_scheduler_parity_and_prefix_stats(setup):
    """Under the continuous scheduler, sharing-on paged rollouts reproduce
    the contiguous reference token-for-token, the new rollout stats report a
    live hit rate and shared-block peak, and the allocator self-check wired
    into the scheduler's teardown passes."""
    cfg, model, params, tok = setup
    env = SearchEnv(n_entities=20, seed=0)
    tasks = env.sample_tasks(2, seed=3)

    ref_eng = GenerationEngine(model, params, pad_id=tok.pad_id,
                               stop_ids=(tok.eos_id,), max_len=512)
    ref = RolloutWorker(ref_eng, env, tok,
                        RolloutConfig(max_turns=2, max_new_tokens=16,
                                      group_size=4, mode="reference")
                        ).rollout(tasks, jax.random.PRNGKey(7))

    eng = GenerationEngine(model, params, pad_id=tok.pad_id,
                           stop_ids=(tok.eos_id,), max_len=512,
                           cache_mode="paged", page_size=BS)
    worker = RolloutWorker(eng, env, tok,
                           RolloutConfig(max_turns=2, max_new_tokens=16,
                                         group_size=4, mode="continuous"))
    trajs = worker.rollout(tasks, jax.random.PRNGKey(7))
    assert len(trajs) == len(ref) == 8
    for a, b in zip(trajs, ref):
        assert a.tokens() == b.tokens()
        assert a.loss_mask() == b.loss_mask()
        np.testing.assert_allclose(a.meta["logprobs"], b.meta["logprobs"],
                                   atol=1e-5)
        assert a.stop_reason == b.stop_reason
    stats = worker.last_stats
    assert stats["prefix_hit_rate"] > 0.0    # 3 of every 4 prompts shared
    assert stats["shared_blocks"] >= 1       # peak refcount>1 blocks
    assert stats["cow_count"] >= 0 and stats["prefix_evictions"] == 0


def test_radix_index_unit():
    """RadixPrefixIndex in isolation: chunked insert/lookup alignment,
    first-writer-wins on re-insert, peek never bumping LRU, and leaf-first
    LRU eviction honoring refcounts."""
    idx = RadixPrefixIndex(4)
    ref = np.zeros(16, np.int32)
    toks = list(range(12))                   # 3 full blocks
    assert idx.insert(toks, [5, 6, 7]) == 3
    assert idx.lookup(toks, 3) == [5, 6, 7]
    assert idx.lookup(toks[:8], 2) == [5, 6]
    assert idx.lookup(toks, 1) == [5]        # cap respected
    # diverging chain shares the first block only
    other = toks[:4] + [99, 98, 97, 96]
    assert idx.insert(other, [5, 9]) == 1    # block 5 kept (first writer)
    assert idx.lookup(other, 2) == [5, 9]
    assert 9 in idx and 8 not in idx
    idx.check(ref)
    # peek is non-mutating
    h, l = idx.hit_blocks, idx.lookup_blocks
    assert idx.peek(toks, 3) == [5, 6, 7]
    assert (idx.hit_blocks, idx.lookup_blocks) == (h, l)
    # eviction: leaf 7 is refcount-pinned, which also shields its ancestors
    # 6 and 5 (non-leaves); only leaf 9 is reclaimable
    ref[7] = 1
    assert idx.evict(10, ref) == [9]
    ref[7] = 0
    assert idx.evict(10, ref) == [7, 6, 5]   # chain drains tail to head
    assert len(idx) == 0
