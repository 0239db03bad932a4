"""The rollout entry under a window: ``RolloutWorker.rollout_stream`` ->
``ContinuousScheduler.stream`` on a paged ``GenerationEngine`` (the Pallas
kernel in decode on the chip), tools through ``AsyncToolExecutor``.

The benchmark wraps the engine, the executor and the tokenizer in thin
proxies that time and count each call and put a host span around it; the
scheduler itself is untouched.  Random weights never write a tool call, so
a scripted tool manager turns each finished turn into the call or the
answer the traffic drew for that trajectory and turn.

The window is made of whole scheduler rounds: it opens at the start of the
first round after every slot has retired at least once (a steady stream)
and closes at the start of the first round at or past ``seconds``.  Then
the stream is stopped, the program's state freed, and a sample of the
trajectories the window finished goes through the plain reference.
"""
from __future__ import annotations

import asyncio
import gc
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import flops as F
import harness as H
import program as P
import traffic as TR
import weights as W
from harness import Span, log

from repro import obs
from repro.core import RolloutConfig, RolloutWorker
from repro.core.async_engine import AsyncToolExecutor
from repro.data.tokenizer import default_tokenizer
from repro.serving.engine import GenerationEngine
from repro.tools.envs import Env
from repro.tools.manager import Qwen3ToolManager
from repro.tools.registry import ToolCall, ToolRegistry, ToolResult

BUCKET = 32      # the engine pads prefill and decode-buffer widths to this


class WindowClosed(Exception):
    """Raised from the engine proxy at the round that ends the window."""


SEARCH_DESCRIPTION = (
    "Search the encyclopedia and return the three passages that best match "
    "the query, each prefixed by its rank.")


class Driver:
    """Bookkeeping of one rollout run: which job holds which row, what each
    turn sampled, the window and its counters."""

    def __init__(self, mix, seconds):
        self.mix, self.seconds = mix, seconds
        self.next_job = 0
        self.row_job: dict = {}
        self.turn_buf: dict = {}
        self.swapped: list = []               # (job, partial turn) FIFO
        self.admit_t: dict = {}
        self.retired: dict = {}               # job -> (time, trajectory)
        self.row_retired = np.zeros((mix["slots"],), np.int64)
        self.state = "warmup"
        self.t_open = self.t_close = 0.0
        self.c = {"rounds": 0, "tokens": 0, "slot_rounds": 0,
                  "active_slot_rounds": 0, "decode_steps": 0,
                  "decode_keys": 0, "prefill_tokens": 0, "prefill_keys": 0,
                  "engine_s": 0.0, "compiles": 0, "cache_loads": 0,
                  "traces": 0}
        self.compiled_names: list = []
        self.span_s: dict = {}
        self.program_open = self.program_close = {}   # registry snapshots
        self.compiles = H.CompileCount()
        self.window: Optional[H.TracedWindow] = None
        self.session = None                   # the stream's decode session

    def set_jobs(self, prompts: list) -> None:
        """The prompt of each job, in the scheduler's job order."""
        self.prompts = prompts
        self.n_jobs = len(prompts)
        self.job_turns = {j: [] for j in range(self.n_jobs)}
        self.job_obs = {j: [] for j in range(self.n_jobs)}
        self.seen_prompts: set = set()

    # ----------------------------------------------------------- window
    def round_start(self) -> None:
        now = time.perf_counter()
        if self.state == "warmup" and (self.row_retired > 0).all():
            self.program_open = obs.get().registry.snapshot()
            self.window.open()
            self.state = "open"
            self.t_open = time.perf_counter()
            self.span_s.clear()
            self._cc0 = self.compiles.snapshot()
        elif self.state == "open" and now - self.t_open >= self.seconds:
            self.t_close = now
            cc = H.CompileCount.since(self._cc0)
            self.c["compiles"] = cc["compiled"]
            self.c["cache_loads"] = cc["cache_hit"]
            self.c["traces"] = cc["trace"]
            self.compiled_names = cc["names"]
            self.c["engine_s"] = sum(self.span_s.get(k, 0.0) for k in (
                "engine.generate", "engine.extend_rows", "engine.reset_rows",
                "engine.start"))
            self.window.close()
            self.program_close = obs.get().registry.snapshot()
            self.state = "closed"
            raise WindowClosed()

    @property
    def in_window(self) -> bool:
        return self.state == "open"

    # ----------------------------------------------------------- rows
    def admit(self, row: int, toks: list) -> None:
        """A prefill into an empty lane: the next queued job (the queue is
        FIFO) or a swapped-out job coming back (also FIFO)."""
        j = self.next_job
        if j < self.n_jobs and list(toks) == self.prompts[j]:
            self.next_job += 1
            self.admit_t[j] = time.perf_counter()
            self.row_job[row] = j
            self.turn_buf[row] = []
            if self.in_window:
                self._count_prefill(toks, shared=True)
            return
        if not self.swapped:
            raise RuntimeError(f"row {row}: a prefill that is neither the "
                               "next prompt nor a swap-in")
        j, partial = self.swapped.pop(0)
        self.row_job[row] = j
        self.turn_buf[row] = partial
        if self.in_window:
            self._count_prefill(toks, shared=False)

    def _count_prefill(self, toks, shared: bool, start: int = 0) -> None:
        # a group's identical prompt is computed once: count the first
        key = tuple(toks)
        if shared:
            if key in self.seen_prompts:
                return
            self.seen_prompts.add(key)
        n = len(toks)
        self.c["prefill_tokens"] += n
        self.c["prefill_keys"] += n * start + n * (n + 1) // 2

    def observe(self, row: int, toks: list, start: int) -> None:
        j = self.row_job[row]
        self.job_obs[j].append(list(toks))
        self.turn_buf[row] = []
        if self.in_window:
            self._count_prefill(toks, shared=False, start=start)

    def reset(self, rows) -> None:
        for r in rows:
            j = self.row_job.pop(int(r), None)
            if j is not None and j not in self.retired:
                self.swapped.append((j, self.turn_buf.get(int(r), [])))
            self.turn_buf[int(r)] = []

    def decoded(self, lengths0, res) -> None:
        counts = np.asarray(res.counts)
        for r in np.nonzero(counts)[0]:
            self.turn_buf[int(r)].extend(
                int(t) for t in res.tokens[r, :counts[r]])
        if self.in_window:
            self.c["rounds"] += 1
            self.c["tokens"] += int(counts.sum())
            self.c["slot_rounds"] += len(counts)
            self.c["active_slot_rounds"] += int((counts > 0).sum())
            self.c["decode_steps"] += int(counts.max(initial=0))
            self.c["decode_keys"] += int(sum(
                F.decode_keys(int(lengths0[r]), int(counts[r]))
                for r in np.nonzero(counts)[0]))

    def identify(self, ids) -> Optional[tuple]:
        """The (job, turn) whose finished turn sampled exactly ``ids``."""
        ids = [int(i) for i in ids]
        for row, buf in self.turn_buf.items():
            if buf == ids and row in self.row_job:
                j = self.row_job[row]
                self.job_turns[j].append(ids)
                self.turn_buf[row] = []
                return j, len(self.job_turns[j]) - 1
        return None

    def on_retire(self, tr) -> None:
        j = int(tr.meta["job_index"])
        self.retired[j] = (time.perf_counter(), tr)
        for row, jj in self.row_job.items():
            if jj == j:
                self.row_retired[row] += 1


class EngineProxy:
    """Forwards every attribute to the engine; times, counts and spans the
    calls the scheduler makes."""

    def __init__(self, engine, drv: Driver):
        object.__setattr__(self, "_e", engine)
        object.__setattr__(self, "_d", drv)

    def __getattr__(self, name):
        return getattr(self._e, name)

    def __setattr__(self, name, value):
        setattr(self._e, name, value)

    def start(self, contexts, *a, **kw):
        with Span("engine.start", self._d.span_s):
            s = self._e.start(contexts, *a, **kw)
        for row, toks in enumerate(contexts):
            self._d.admit(row, toks)
        return s

    def extend_rows(self, session, rows, token_lists):
        lengths = np.asarray(session.lengths).copy()
        with Span("engine.extend_rows", self._d.span_s):
            self._e.extend_rows(session, rows, token_lists)
        for r, toks in zip(rows, token_lists):
            if lengths[int(r)] == 0:
                self._d.admit(int(r), toks)
            else:
                self._d.observe(int(r), toks, int(lengths[int(r)]))

    def reset_rows(self, session, rows):
        with Span("engine.reset_rows", self._d.span_s):
            self._e.reset_rows(session, rows)
        self._d.reset(rows)

    def generate(self, session, *a, **kw):
        self._d.session = session
        self._d.round_start()
        lengths0 = np.asarray(session.lengths).copy()
        with Span("engine.generate", self._d.span_s):
            res = self._e.generate(session, *a, **kw)
        self._d.decoded(lengths0, res)
        return res


class ExecutorProxy:
    """The program's executor, with the drain points spanned."""

    def __init__(self, ex, drv: Driver):
        self._ex, self._d = ex, drv

    def __getattr__(self, name):
        return getattr(self._ex, name)

    def drain_ready(self, *a, **kw):
        with Span("tools.drain", self._d.span_s):
            return self._ex.drain_ready(*a, **kw)

    def wait_ready(self, *a, **kw):
        with Span("tools.drain", self._d.span_s):
            return self._ex.wait_ready(*a, **kw)


class TokenizerProxy:
    """The program's tokenizer; ``decode`` of a finished turn also tells
    the scripted manager whose turn it was."""

    def __init__(self, tok, drv: Driver):
        self._tok, self._d = tok, drv
        self.last_turn = None

    def __getattr__(self, name):
        return getattr(self._tok, name)

    def decode(self, ids):
        self.last_turn = self._d.identify(ids)
        return self._tok.decode(ids)


class ScriptedManager(Qwen3ToolManager):
    """Qwen3's hermes-style manager whose parse follows the traffic's plan:
    the drawn number of ``search`` calls, then an answer."""

    def __init__(self, registry, style: str, plan: dict, tok: TokenizerProxy):
        super().__init__(registry, compact=False)
        self.style, self.plan, self.tok = style, plan, tok

    def get_prompt(self, question: str) -> str:
        if self.style == "plain":
            return question
        return super().get_prompt(question)

    def parse_response(self, text):
        turn = self.tok.last_turn
        if turn is None:
            raise RuntimeError("a finished turn that no row sampled")
        job, k = turn
        if k < self.plan["calls"][job]:
            return [ToolCall("search", {"query": f"{job}:{k}"}, 0)], None
        return [], "done"


def make_registry(mix: dict, plan: dict, seed: int) -> ToolRegistry:
    reg = ToolRegistry()

    async def search(query: str) -> str:
        job, k = (int(x) for x in query.split(":"))
        await asyncio.sleep(plan["latency_s"][job][k])
        return TR.observation_text(seed, job, k, plan["obs_bytes"][job][k])

    if mix["max_tool_calls"]:
        reg.register_fn("search", search, SEARCH_DESCRIPTION,
                        {"query": {"type": "string", "required": True}},
                        timeout_s=mix["tool_latency"]["timeout_s"])
    return reg


def prefill_buckets(mix: dict, prompts: list) -> list:
    """Every prefill width the traffic can reach, short of a swap-in: a
    prompt or its unshared suffix, and observations."""
    longest = max(max(len(p) for p in prompts),
                  mix.get("observation_tokens", [0, 0])[1])
    return list(range(BUCKET, TR.ceil_to(longest, BUCKET) + 1, BUCKET))


def warm_shapes(engine, mix: dict, prompts: list, V: int) -> None:
    """Compile every program the window drives, on a throwaway session of
    the same batch and pool, through the engine's own calls: each prefill
    width; a prompt shared by groups of every size (follower logits); a
    reset of every row count; the decode loop at each width a round's
    budget can take; and the pool's eager updates at every freed-block and
    copied-block count a round can reach.  The session is freed after."""
    B = mix["slots"]
    s = engine.start([[1]] * B)
    rows = list(range(B))
    for i, L in enumerate(prefill_buckets(mix, prompts)):
        engine.reset_rows(s, [0])
        engine.extend_rows(s, [0], [[(i * 7 + t) % 256 for t in range(L)]])
    for f in range(1, B + 1):
        engine.reset_rows(s, rows[:f])
        engine.extend_rows(s, rows[:f], [[f + 2] * 20] * f)
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    widths = {TR.ceil_to(mix["max_new_tokens"], BUCKET)}
    if mix["max_tool_calls"]:
        widths |= {TR.ceil_to(b, BUCKET) for b in (8, mix["max_new_tokens"])}
    for T in sorted(widths):
        engine.generate(s, T, None, temperature=mix["temperature"],
                        row_keys=keys, step_offsets=np.zeros((B,), np.int32),
                        row_budgets=np.ones((B,), np.int32))
    model, policy = engine.model, s.cache_policy
    for n in range(1, B + 1):
        s.cache = model.copy_cache_blocks(s.cache, list(range(n)),
                                          list(range(n, 2 * n)),
                                          policy=policy)
    for b in range(mix["warm_freed_blocks"] + 1):
        s.cache = model.reset_cache_rows(s.cache, [0], engine.max_len,
                                         policy=policy,
                                         freed_blocks=list(range(b)))
    jax.block_until_ready(s.cache)
    del s
    gc.collect()


def build_engine(model, params, tok, mix: dict, kv_dtype: str = "fp"):
    return GenerationEngine(
        model, params, pad_id=tok.pad_id, stop_ids=(tok.eos_id,),
        max_len=mix["max_len"], temperature=mix["temperature"],
        cache_mode="paged", page_size=mix["page"],
        num_blocks=mix["pool_blocks"], kv_cache_dtype=kv_dtype)


def stream_key(seed: int) -> jax.Array:
    return jax.random.fold_in(W.base_key(seed), 7)


def run(cell: H.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, kv_dtype: str = "fp", fault=None,
        reuse: Optional[dict] = None, control: bool = False) -> dict:
    """One run of a rollout cell.  ``fault`` (tests only) breaks the timed
    path underneath, to show that ``correct`` then reads false.
    ``kv_dtype="int8"`` switches on the program's int8 K/V pool, its own
    path one precision below the served bf16; ``control`` also reads the
    reference in fp8 put in the program's place (calibration and tests
    only).  ``reuse`` (calibration only) keeps one engine
    across seeds, its weights swapped in place."""
    cfg, mix = cell.config, cell.traffic
    model = P.model_of(cfg)
    pcfg = model.cfg
    tok0 = default_tokenizer(pcfg.vocab_size)
    plan = TR.rollout_plan(mix, seed)
    drv = Driver(mix, seconds)
    tok = TokenizerProxy(tok0, drv)
    registry = make_registry(mix, plan, seed)
    manager = ScriptedManager(registry, mix["prompt_style"], plan, tok)
    env = Env(registry, manager, max_tool_calls=mix["max_tool_calls"])
    G = mix["group_size"]
    tasks = [(q, "") for q in plan["questions"]]
    drv.set_jobs([tok0.encode(manager.get_prompt(q), add_bos=True)
                  for q, _ in tasks for _ in range(G)])
    drv.window = H.TracedWindow(traced, cell.name, seed)

    dtype = P.dtype_of(cfg["param_dtype"])
    if reuse and "engine" in reuse:
        engine = reuse["engine"]
        engine.params = P.remake_params(cfg, seed, dtype, engine.params)
    else:
        params = P.make_params(model, cfg, seed, dtype)
        engine = build_engine(model, params, tok0, mix, kv_dtype)
        del params
        if fault is not None:
            engine = fault(engine)
        log(f"weights made: {time.perf_counter() - t_start:.1f} s")
        warm_shapes(engine, mix, drv.prompts, pcfg.vocab_size)
        log(f"shapes warmed: {time.perf_counter() - t_start:.1f} s")
        if reuse is not None:
            reuse["engine"] = engine
    proxy = EngineProxy(engine, drv)
    executor = ExecutorProxy(AsyncToolExecutor(registry), drv)
    worker = RolloutWorker(proxy, env, tok, RolloutConfig(
        max_turns=mix["max_turns"], max_new_tokens=mix["max_new_tokens"],
        temperature=mix["temperature"], group_size=G, n_slots=mix["slots"]),
        executor=executor)
    t_stream = time.perf_counter()
    try:
        for tr in worker.rollout_stream(tasks, stream_key(seed)):
            drv.on_retire(tr)
    except WindowClosed:
        pass
    if drv.state != "closed":
        raise RuntimeError("the task queue ran dry before the window closed")
    setup_s = drv.t_open - t_start
    log(f"steady after {drv.t_open - t_stream:.1f} s of stream; window "
        f"{drv.t_close - drv.t_open:.2f} s, {drv.c['rounds']} rounds; "
        f"in the window: {drv.c['compiles']} programs compiled, "
        f"{drv.c['cache_loads']} loaded from the compile cache, "
        f"{drv.c['traces']} traces: {drv.compiled_names}")
    peak_bytes = H.memory_peak_bytes(cell.chips)
    live = live_values(drv, cfg, mix["page"])
    drv.session = None
    del worker, proxy, engine, executor
    gc.collect()

    done = {j: v for j, v in drv.retired.items()
            if drv.t_open <= v[0] <= drv.t_close}
    lat = [t - drv.admit_t[j] for j, (t, _) in done.items()]
    failed = sum(1 for _, tr in done.values() if tr.stop_reason != "answer")
    log(f"trajectories finished in window: {len(done)}, failed {failed}; "
        f"p90 admission-to-retirement {np.percentile(lat or [0], 90):.3f} s")
    t_ref = time.perf_counter()
    numbers, extra = check(cfg, mix, seed, plan, drv, done, live, tok0,
                           manager, control)
    log(f"reference check: {time.perf_counter() - t_ref:.1f} s")
    counts = dict(drv.c, finished=len(done), spans_s=dict(drv.span_s),
                  program_open=drv.program_open,
                  program_close=drv.program_close)
    return {"setup_s": setup_s, "window_s": drv.t_close - drv.t_open,
            "counts": counts, "numbers": numbers, "attempted": len(done),
            "failed": failed, "memory_peak_bytes": peak_bytes,
            "trace_dir": drv.window.dir if traced else None,
            "extra": extra}


# ------------------------------------------------------------- the check
def sample_jobs(done: dict, n: int, seed: int) -> list:
    """``n`` finished trajectories drawn from the seed, the longest
    always among them."""
    jobs = sorted(done)
    longest = max(jobs, key=lambda j: (len(done[j][1]), j))
    rest = [j for j in jobs if j != longest]
    r = TR.rng_for(seed, 3)
    pick = list(r.permutation(rest)[:max(0, n - 1)])
    return [longest] + [int(j) for j in pick]


def live_values(drv: Driver, cfg: dict, page: int) -> list:
    """(row, tokens in cache, float32 vectors (n, ...)) of each row live
    when the window closed: the layer-0 pool vectors that the layout's
    ``cache_vectors`` reads."""
    s = drv.session
    rows = [r for r in sorted(drv.row_job) if int(s.lengths[r]) > 0]
    lengths = [int(s.lengths[r]) for r in rows]
    vecs = H.layout(cfg).cache_vectors(s.cache, rows, lengths, page)
    return list(zip(rows, lengths, vecs))


def expected_obs(seed, plan, tok, manager, j: int, n: int) -> list:
    """The tokens of job ``j``'s first ``n`` observations, as the traffic
    makes them."""
    return [tok.encode(manager.format_observation([ToolResult(
        "search", TR.observation_text(seed, j, k, plan["obs_bytes"][j][k]))]))
        for k in range(n)]


def check(cfg, mix, seed, plan, drv, done, live, tok, manager,
          control: bool = False) -> tuple:
    """The sampled trajectories' served tokens against the plain float32
    reference (``configs/<reference>.py``), run layer by layer on weights
    it draws itself from the seed.

    Sampling with per-trajectory keys is ``argmax(log_softmax(logits / T)
    + gumbel(key))``.  The reference rebuilds each token's key (the
    stream's key split per trajectory, folded with the turn and the step)
    and its noise, and reads ``served_gap``: how far the served token's
    noisy reference logit lies below the noisy reference's best (0 when the
    reference would have served the same token).  ``logprob_gap`` is the
    widest gap between the logprob the program recorded for a served token
    and the reference's.  ``value_cache_gap`` is the widest relative gap
    between a value vector that layer 0 of the paged pool holds for a row
    live at the window's close and the reference's, over every cached
    position and K/V head of those rows: the precision the pool keeps.
    ``context_mismatch`` counts prompts, observations, turns and cache
    lengths that differ from what the traffic fed in."""
    if not done or not live:
        return {"served_gap": float("inf"), "logprob_gap": float("inf"),
                "value_cache_gap": float("inf"),
                "context_mismatch": 1.0}, {}
    ref = H.config_module(cfg, "reference")
    jobs = sample_jobs(done, mix["check_trajectories"], seed)
    n_jobs = drv.n_jobs
    job_keys = jax.random.split(stream_key(seed), n_jobs)
    seqs, targets = [], []          # targets: (row, pos, token, lp, key)
    mismatch = 0
    for row, j in enumerate(jobs):
        tr = done[j][1]
        toks = tr.tokens()
        lps = tr.meta["logprobs"]
        prompt = drv.prompts[j]
        mismatch += int(toks[:len(prompt)] != prompt)
        obs_want = expected_obs(seed, plan, tok, manager, j,
                                len(drv.job_obs[j]))
        mismatch += int(obs_want != drv.job_obs[j])
        # the trajectory must be: prompt, then each sampled turn followed
        # by the observation the tool answered with
        pos = len(prompt)
        for k, turn in enumerate(drv.job_turns[j]):
            tk = jax.random.fold_in(job_keys[j], k)
            mismatch += int(toks[pos:pos + len(turn)] != turn)
            for i, t in enumerate(turn):
                targets.append((row, pos + i, t, lps[pos + i],
                                jax.random.fold_in(tk, i)))
            pos += len(turn)
            if k < len(obs_want):
                o = obs_want[k]
                mismatch += int(toks[pos:pos + len(o)] != o)
                pos += len(o)
        mismatch += int(pos != len(toks))
        seqs.append(toks)
    # one shape for every run of the cell: rows padded to the sample size
    # and to the engine's context, so the reference compiles once
    L = mix["max_len"]
    tokens = np.zeros((mix["check_trajectories"], L), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    rows = np.array([t[0] for t in targets])
    at = np.array([t[1] - 1 for t in targets])
    served = np.array([t[2] for t in targets], np.int32)
    lp_prog = np.array([t[3] for t in targets], np.float32)
    keys = jnp.stack([t[4] for t in targets])
    hidden = reference_hidden(ref, cfg, seed, tokens)[rows, at]
    gap, lp_gap = served_gaps(ref, cfg, seed, hidden, served, lp_prog, keys,
                              mix["temperature"])
    log(f"reference: {len(jobs)} trajectories, {len(targets)} served "
        f"tokens, longest {max(len(s) for s in seqs)} tokens")
    # the rows live at the close: prompt, then each turn and the
    # observation the traffic answered it with, then the turn under way
    ctx = np.zeros((mix["slots"], L), np.int32)
    for i, (row, n, _) in enumerate(live):
        j = drv.row_job[row]
        obs = expected_obs(seed, plan, tok, manager, j, len(drv.job_obs[j]))
        mismatch += int(obs != drv.job_obs[j])
        toks = list(drv.prompts[j])
        for k, turn in enumerate(drv.job_turns[j]):
            toks += turn + (obs[k] if k < len(obs) else [])
        toks += drv.turn_buf.get(row, [])
        mismatch += int(len(toks) != n)
        ctx[i, :min(n, L)] = toks[:min(n, L)]
    v_ref = reference_values(ref, cfg, seed, ctx)
    v_gap = value_gap(live, v_ref)
    log(f"pool values: {len(live)} live rows, "
        f"{sum(n for _, n, _ in live)} cached positions")
    extra = {}
    if control:
        # the reference one precision below the served bf16 (every
        # product's inputs in fp8 e4m3, per-tensor scales), read at the
        # same positions: the gap of the token it puts first under the
        # same noise, and its logprob of that token
        h_ctl = reference_hidden(ref, cfg, seed, tokens,
                                 ref.FP8)[rows, at]
        ctl = dict(zip(("served_gap", "logprob_gap"), served_gaps(
            ref, cfg, seed, hidden, served, lp_prog, keys,
            mix["temperature"], h_ctl=h_ctl)))
        v_ctl = reference_values(ref, cfg, seed, ctx, ref.FP8)
        ctl["value_cache_gap"] = value_gap(
            [(r, n, v_ctl[i, :n]) for i, (r, n, _) in enumerate(live)], v_ref)
        ctl["context_mismatch"] = float(mismatch)
        extra["control_fp8"] = ctl
        log(f"control fp8: {ctl}")
    return {"served_gap": float(gap), "logprob_gap": float(lp_gap),
            "value_cache_gap": v_gap,
            "context_mismatch": float(mismatch)}, extra


def reference_values(ref, cfg, seed, tokens: np.ndarray,
                     dtype=jnp.float32) -> np.ndarray:
    """Layer 0's cached vectors (R, L, ...) of the rows' tokens, as the
    reference's ``layer_values`` gives them, in its ``dtype``
    (``ref.FP8`` for the control)."""
    cast = served_weights(cfg)

    @jax.jit
    def fn(tokens, k):
        x = cast(W.global_tensor(k, cfg, "embed"))[tokens]
        w = jax.tree_util.tree_map(cast, W.layer_weights(k, cfg, 0))
        return jax.lax.map(lambda seq: ref.layer_values(seq, w, cfg, dtype),
                           x)

    return np.asarray(fn(jnp.asarray(tokens), W.base_key(seed)))


def value_gap(live: list, v_ref: np.ndarray) -> float:
    """The widest |v - v_ref| / |v_ref| over the live rows' cached
    positions and heads."""
    worst = 0.0
    for i, (_, n, v) in enumerate(live):
        r = v_ref[i, :n]
        rel = (np.linalg.norm(v - r, axis=-1)
               / np.maximum(np.linalg.norm(r, axis=-1), 1e-12))
        worst = max(worst, float(rel.max()))
    return worst


def served_weights(cfg):
    """How the reference takes the served weights: drawn in float32, cast
    to the served dtype, then computed on in float32."""
    dt = P.dtype_of(cfg["param_dtype"])
    return lambda x: x.astype(dt).astype(jnp.float32)


def reference_hidden(ref, cfg, seed, tokens: np.ndarray,
                     dtype=jnp.float32) -> np.ndarray:
    """Last hidden states (R, L, d) of the rows, one layer at a time (one
    program for each of the layout's groups of like layers), in the
    reference's ``dtype`` (``ref.FP8`` for the control)."""
    key = W.base_key(seed)
    cast = served_weights(cfg)
    emb = jax.jit(lambda k: cast(W.global_tensor(k, cfg, "embed")))(key)
    x = emb[jnp.asarray(tokens)]
    del emb
    for first, n in H.layout(cfg).layer_groups(cfg):
        @jax.jit
        def one_layer(x, k, layer, first=first):
            w = jax.tree_util.tree_map(cast, W.layer_weights(
                k, cfg, layer, like=first))
            return jax.lax.map(lambda seq: ref.layer(seq, w, cfg, dtype), x)

        for layer in range(first, first + n):
            x = one_layer(x, key, jnp.int32(layer))
    return np.asarray(x)


def served_gaps(ref, cfg, seed, h, served, lp_prog, keys, temperature,
                chunk: int = 256, h_ctl=None):
    """The widest served-token gap and logprob gap.  With ``h_ctl`` (a
    control's hidden states at the same positions), the control stands in
    for the program: its first token under the same noise, and its
    logprob of that token, are judged against the reference."""
    key = W.base_key(seed)
    cast = served_weights(cfg)
    fn = jax.jit(lambda k: jax.tree_util.tree_map(
        cast, W.global_weights(k, cfg)))
    g = fn(key)
    out_w = g["embed"] if cfg.get("tie_word_embeddings") else g["unembed"]
    V = cfg["vocab_size"]

    def logp(h, out_w, final_norm, dtype=jnp.float32):
        logits = ref.head(h, final_norm, out_w, cfg, dtype)
        return jax.nn.log_softmax(logits / jnp.maximum(temperature, 1e-6), -1)

    @jax.jit
    def gaps(h, hc, served, lp_prog, keys, out_w, final_norm):
        z = logp(h, out_w, final_norm)
        noise = jax.vmap(lambda k: jax.random.gumbel(k, (V,), jnp.float32))(
            keys)
        if hc is not None:
            zc = logp(hc, out_w, final_norm, ref.FP8)
            served = jnp.argmax(zc + noise, -1)
            lp_prog = jnp.take_along_axis(zc, served[:, None], -1)[:, 0]
        y = z + noise
        mine = jnp.take_along_axis(y, served[:, None], -1)[:, 0]
        zs = jnp.take_along_axis(z, served[:, None], -1)[:, 0]
        return jnp.max(y, -1) - mine, jnp.abs(zs - lp_prog)

    worst, worst_lp = 0.0, 0.0
    n = len(served)
    for i in range(0, n, chunk):
        sl = slice(i, min(n, i + chunk))
        m = sl.stop - sl.start
        pad = chunk - m
        hc = (None if h_ctl is None else
              jnp.pad(jnp.asarray(h_ctl[sl]), ((0, pad), (0, 0))))
        a, b = gaps(jnp.pad(jnp.asarray(h[sl]), ((0, pad), (0, 0))), hc,
                    jnp.pad(jnp.asarray(served[sl]), (0, pad)),
                    jnp.pad(jnp.asarray(lp_prog[sl]), (0, pad)),
                    jnp.pad(keys[sl], ((0, pad), (0, 0))),
                    out_w, g["final_norm"])
        worst = max(worst, float(np.max(np.asarray(a)[:m])))
        worst_lp = max(worst_lp, float(np.max(np.asarray(b)[:m])))
    return worst, worst_lp
