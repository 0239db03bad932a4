"""Fast paged decode (ISSUE 7 acceptance): Pallas kernel in the decode hot
path (vs the gather fallback oracle), int8 KV block pools (vs the fp oracle,
documented tolerance), chunked prefill parity, and swap-don't-kill
preemption (cache pressure costs latency, never data)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.rollout import RolloutConfig, RolloutWorker
from repro.data.tokenizer import default_tokenizer
from repro.models import Model
from repro.serving.engine import GenerationEngine
from repro.tools.envs import Env
from repro.tools.manager import ToolManager
from repro.tools.registry import ToolCall, ToolRegistry, ToolSpec


@pytest.fixture(scope="module")
def gqa_setup():
    cfg = get_config("tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = default_tokenizer(cfg.vocab_size)
    return cfg, model, params, tok


def _run(eng, tok, ctx, seed=5, budget=12):
    """start -> generate -> extend -> generate: prefill + decode surface."""
    rk = jax.random.split(jax.random.PRNGKey(seed), len(ctx))
    s = eng.start([list(c) for c in ctx])
    r1 = eng.generate(s, budget, row_keys=rk)
    eng.extend(s, [tok.encode(" more")] + [[]] * (len(ctx) - 1))
    r2 = eng.generate(s, 8, row_keys=rk)
    return (r1, r2), s


# ------------------------------------------------------- kernel in the loop
def test_engine_kernel_matches_contiguous(gqa_setup):
    """Decode routed through the Pallas paged-attention kernel (interpret
    mode on CPU) must stay token- and logprob-identical to the contiguous
    oracle — the acceptance bar for putting the kernel in the hot path."""
    cfg, model, params, tok = gqa_setup
    kw = dict(pad_id=tok.pad_id, stop_ids=(tok.eos_id,), max_len=96,
              temperature=1.0)
    contiguous = GenerationEngine(model, params, **kw)
    kernel = GenerationEngine(model, params, cache_mode="paged",
                              page_size=16, paged_kernel=True, **kw)
    assert kernel._use_paged_kernel
    ctx = [tok.encode("kernel parity a"), tok.encode("b"),
           tok.encode("row three !")]
    rc, sc = _run(contiguous, tok, ctx)
    rk_, sk = _run(kernel, tok, ctx)
    for a, b in zip(rc, rk_):
        assert a.token_lists() == b.token_lists()
        for ra, rb in zip(a.logprob_lists(), b.logprob_lists()):
            np.testing.assert_allclose(ra, rb, atol=1e-5)
    np.testing.assert_array_equal(sc.lengths, sk.lengths)


def test_kernel_auto_detect_off_tpu(gqa_setup, monkeypatch):
    """The backend alone picks the paged decode path.  On the CPU the JAX
    gather is the default and ``paged_kernel=True`` runs the kernel in
    interpret mode.  On a TPU the GQA paged engine always runs the compiled
    kernel: asking for the gather raises, and so does asking for interpret
    mode, which is no longer an option."""
    from repro import kernels
    cfg, model, params, tok = gqa_setup
    kw = dict(pad_id=tok.pad_id, stop_ids=(), max_len=64,
              cache_mode="paged", page_size=16)
    assert kernels.interpret_mode()          # the tests run on the CPU
    assert not GenerationEngine(model, params, **kw)._use_paged_kernel
    assert GenerationEngine(model, params, paged_kernel=True,
                            **kw)._use_paged_kernel
    assert not GenerationEngine(model, params, max_len=64, pad_id=0,
                                stop_ids=())._use_paged_kernel

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    assert GenerationEngine(model, params, **kw)._use_paged_kernel
    with pytest.raises(ValueError, match="paged_kernel=False"):
        GenerationEngine(model, params, paged_kernel=False, **kw)
    with pytest.raises(TypeError, match="paged_interpret"):
        GenerationEngine(model, params, paged_interpret=True, **kw)
    mla = Model(get_config("deepseek-v2-236b").reduced())
    eng = GenerationEngine(mla, mla.init(jax.random.PRNGKey(0)), **kw)
    assert not eng._use_paged_kernel         # MLA's gather is its only path


# ------------------------------------------------------------ int8 KV pools
def test_int8_roundtrip_error_bound():
    """Symmetric absmax int8: per-element round-trip error is bounded by
    scale/2 (the quantization-step radius), the bound the serving-level
    tolerance is derived from."""
    from repro.models.attention import _quantize_int8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 16, 2, 32)) * 3.0, jnp.float32)
    q, scale = _quantize_int8(x)
    assert q.dtype == jnp.int8
    deq = q.astype(jnp.float32) * scale[..., None]
    err = np.abs(np.asarray(deq - x))
    bound = np.asarray(scale)[..., None] / 2 + 1e-6
    assert np.all(err <= bound)


def test_int8_requires_paged_cache(gqa_setup):
    cfg, model, params, tok = gqa_setup
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        GenerationEngine(model, params, pad_id=tok.pad_id, stop_ids=(),
                         max_len=64, kv_cache_dtype="int8")


@pytest.mark.parametrize("use_kernel", [False, True])
def test_engine_int8_close_to_fp_oracle(gqa_setup, use_kernel):
    """int8 KV pools (gather and kernel paths) vs the fp paged oracle: the
    decode distributions must stay within the documented serving tolerance
    and produce a finite, complete generation."""
    cfg, model, params, tok = gqa_setup
    kw = dict(pad_id=tok.pad_id, stop_ids=(tok.eos_id,), max_len=96,
              temperature=1.0, cache_mode="paged", page_size=16)
    fp = GenerationEngine(model, params, **kw)
    i8 = GenerationEngine(model, params, kv_cache_dtype="int8",
                          paged_kernel=use_kernel, **kw)
    ctx = [tok.encode("int8 pools a"), tok.encode("longer row number two")]
    rk = jax.random.split(jax.random.PRNGKey(11), len(ctx))
    sf = fp.start([list(c) for c in ctx])
    si = i8.start([list(c) for c in ctx])
    assert si.cache is not None
    lf = np.asarray(jax.nn.log_softmax(sf.last_logits, axis=-1))
    li = np.asarray(jax.nn.log_softmax(si.last_logits, axis=-1))
    assert np.all(np.isfinite(li))
    err = np.max(np.abs(lf - li))
    assert 0.0 < err < 0.25, f"int8 prefill logprob drift {err:.4f}"
    ri = i8.generate(si, 12, row_keys=rk)
    assert all(len(t) > 0 for t in ri.token_lists())
    assert np.all(np.isfinite(np.concatenate(ri.logprob_lists())))


def test_int8_pool_halves_cache_bytes(gqa_setup):
    """The point of int8 pools: the K/V block pools occupy half the bytes of
    the fp32 pools (scales are a per-slot rounding error on top)."""
    cfg, model, params, tok = gqa_setup
    kw = dict(pad_id=tok.pad_id, stop_ids=(), max_len=64,
              cache_mode="paged", page_size=16)
    sf = GenerationEngine(model, params, **kw).start([[2, 3, 4]])
    si = GenerationEngine(model, params, kv_cache_dtype="int8",
                          **kw).start([[2, 3, 4]])

    def pool_bytes(cache, want):
        tot = 0
        for leaf in jax.tree_util.tree_leaves_with_path(cache):
            path, arr = leaf
            name = str(path[-1])
            if any(k in name for k in ("'k'", "'v'", "ckv", "krope")) \
                    and "scale" not in name and hasattr(arr, "dtype"):
                assert arr.dtype == want, (name, arr.dtype)
                tot += arr.size * arr.dtype.itemsize
        return tot

    fp_bytes = pool_bytes(sf.cache, jnp.float32)
    i8_bytes = pool_bytes(si.cache, jnp.int8)
    assert fp_bytes > 0 and i8_bytes * 4 == fp_bytes


# --------------------------------------------------------- chunked prefill
@pytest.mark.parametrize("cache_mode", ["contiguous", "paged"])
def test_chunked_prefill_parity(gqa_setup, cache_mode):
    """A long prompt streamed through fixed-width prefill chunks must leave
    the session in the same state as one monolithic prefill: identical
    last_logits (to fp tolerance) and token-identical decode after it."""
    cfg, model, params, tok = gqa_setup
    kw = dict(pad_id=tok.pad_id, stop_ids=(tok.eos_id,), max_len=256,
              temperature=1.0, cache_mode=cache_mode, page_size=16)
    mono = GenerationEngine(model, params, **kw)
    chunked = GenerationEngine(model, params, prefill_chunk=32, **kw)
    assert chunked.prefill_chunk == 32
    long_prompt = tok.encode("a long prompt " * 14)     # > 2 chunks
    assert len(long_prompt) > 64
    ctx = [long_prompt, tok.encode("short row")]
    rk = jax.random.split(jax.random.PRNGKey(4), len(ctx))
    sm = mono.start([list(c) for c in ctx])
    sc = chunked.start([list(c) for c in ctx])
    np.testing.assert_array_equal(sm.lengths, sc.lengths)
    np.testing.assert_allclose(np.asarray(sm.last_logits),
                               np.asarray(sc.last_logits), atol=1e-4)
    rm = mono.generate(sm, 12, row_keys=rk)
    rc = chunked.generate(sc, 12, row_keys=rk)
    assert rm.token_lists() == rc.token_lists()
    for ra, rb in zip(rm.logprob_lists(), rc.logprob_lists()):
        np.testing.assert_allclose(ra, rb, atol=1e-5)


# --------------------------------------------------- swap-don't-kill wedge
class _OneCallManager(ToolManager):
    """Deterministic tool-intent policy for the random-weights tiny model:
    EVERY model turn parses as one ``blob`` call, so with max_tool_calls=1
    each trajectory is prompt -> turn -> big observation -> turn ->
    retire('tool_budget') regardless of the sampled bytes."""

    def get_prompt(self, q):
        return f"question: {q} "

    def parse_response(self, text):
        return [ToolCall(name="blob", arguments={}, call_id=0)], None

    def format_observation(self, results):
        return "".join(r.content for r in results)


def test_preemption_swaps_instead_of_killing(gqa_setup):
    """Acceptance: under block-pool pressure hard enough to wedge the
    scheduler (every occupied row parked on an observation the pool cannot
    absorb), the victim row is swapped to the host and later re-admitted —
    it finishes with exactly the tokens it would have produced unpressured,
    and nothing is retired as a pressure 'max_len' eviction.

    The 140-char observations need ~9 blocks each on a 13-block pool that
    also holds two ~33-token rows: neither parked row can absorb, nothing
    is in flight, and the wedge-breaker must swap (not kill) a victim."""
    cfg, model, params, tok = gqa_setup
    reg = ToolRegistry()
    reg.register(ToolSpec(name="blob", fn=lambda: "x" * 140, parameters={}))
    env = Env(reg, _OneCallManager(reg), max_tool_calls=1)
    tasks = [("alpha", "a"), ("beta", "b")]

    ref_eng = GenerationEngine(model, params, pad_id=tok.pad_id,
                               stop_ids=(tok.eos_id,), max_len=256)
    ref = RolloutWorker(ref_eng, env, tok,
                        RolloutConfig(max_turns=3, max_new_tokens=16,
                                      group_size=2, mode="reference")
                        ).rollout(tasks, jax.random.PRNGKey(7))

    eng = GenerationEngine(model, params, pad_id=tok.pad_id,
                           stop_ids=(tok.eos_id,), max_len=256,
                           cache_mode="paged", page_size=16, num_blocks=13)
    worker = RolloutWorker(eng, env, tok,
                           RolloutConfig(max_turns=3, max_new_tokens=16,
                                         group_size=2, mode="continuous",
                                         n_slots=2))
    trajs = worker.rollout(tasks, jax.random.PRNGKey(7))
    assert len(trajs) == 4
    stats = worker.last_stats
    assert stats["preemptions"] >= 1          # pressure actually bit
    assert stats["swap_out"] >= 1
    assert stats["swap_in"] >= 1              # and every victim came back
    assert stats["swap_in"] == stats["swap_out"]
    assert stats["evictions"] == 0            # nothing was killed for blocks
    # prefix sharing stayed live under swap pressure: group members (and
    # swap-in re-prefills) served their prompts from shared blocks, and the
    # allocator invariant check in the scheduler's finally block passed
    assert stats["prefix_hit_rate"] > 0.0
    assert stats["cow_count"] >= 0 and stats["prefix_evictions"] >= 0
    for a, b in zip(trajs, ref):
        assert a.tokens() == b.tokens()
        assert a.stop_reason == b.stop_reason == "tool_budget"
        np.testing.assert_allclose(a.meta["logprobs"], b.meta["logprobs"],
                                   atol=1e-5)
