"""Continuous-batching rollout scheduler (paper §2.3.2, taken past Fig. 4).

The turn-synchronous loop (``RolloutWorker.rollout_reference``) couples every
trajectory to the slowest tool call of the batch: Generate for everyone,
barrier on the tool results, prefill everyone, repeat — the GPU idles during
every tool call and finished rows occupy dead slots until the episode ends.
This module decouples the Generate-Parse-Invoke-Update stages *per
trajectory* over a fixed pool of decode-batch slots:

park / retire / refill state machine (one slot = one cache lane)::

      task queue ──┐ refill: reset_rows + prompt prefill
                   ▼
               ┌────────┐  decode turn   ┌───────┐ tool calls   ┌────────┐
       ┌──────▶│ ACTIVE │───────────────▶│ parse │─────────────▶│ PARKED │
       │       └────────┘                └───┬───┘  submit()    └───┬────┘
       │ obs prefill (extend_rows)           │ answer / no_call     │
       └─────────────────────────────────────┼─ / tool_budget       │
                   ▲                         ▼ / max_len/turns      │
                   └──── results land ── [ RETIRE slot ] ◀──────────┘
                        (drain_ready)      │
                                           ▼ yield Trajectory; refill or FREE

* A slot whose row emitted tool calls hands them to the background asyncio
  loop as a future (``executor.submit``) and is **parked**: its session row
  is marked stopped, so the fused decode loop keeps generating for the
  remaining active rows while the I/O is in flight — decode and tool latency
  overlap instead of serializing (the rollout-level version of the paper's
  6.8x decoupling argument).
* When a parked row's results land (``executor.drain_ready`` between decode
  rounds, ``wait_ready`` when nothing is active), the observation is
  tokenized and prefilled back into *that row's* cache lane
  (``engine.extend_rows``) and the slot rejoins the decode batch.
* A row that finishes (``</answer>``, no tool intent, tool budget, context
  or turn limit) is **retired**: its trajectory is yielded and the slot's
  cache lane is cleared (``engine.reset_rows``) and re-primed with the next
  task from the queue, keeping the decode batch full for arbitrarily many
  tasks with a bounded memory footprint.

Decode rounds are decoupled from logical turns.  A *turn* (sample until a
stop id or ``max_new_tokens``) may span several *rounds*: when a fraction of
the slots is parked on tool futures, the per-round token budget shrinks
(``adaptive_budget``) so the scheduler returns to the drain point sooner and
observations land earlier, instead of decoding a full turn's worth for the
few active rows while results queue up.  Mid-turn rows carry their sampled
prefix in the slot's turn buffer and resume on the next round; the engine's
``step_offsets`` keep each row's sampling stream indexed by its position
*within the turn*, so how a turn is sliced into rounds cannot change any
sampled token.

Paged KV cache (``engine.cache_mode="paged"``): admission is gated on
*free-block availability* rather than free-slot count —
``engine.admission_headroom`` reserves worst-case decode growth for every
occupied row, and a queued task enters only if its prompt + one turn fits
beyond that reserve (zero-free-blocks => the task simply waits).  Tool
observations that cannot get blocks stay pending on their parked slot until
a retirement frees some; if the pool wedges (nothing active, nothing
absorbable), the longest pending row is **swapped out, not killed**: its
tokens move to a host-side ``_Swapped`` record, its blocks return to the
pool, and ``refill`` re-admits it later with a re-prefill of the full
context — cache pressure costs latency, never data (vLLM-style
swap-preemption).  In-flight tool futures of a swapped row stay registered
and their results land into the record while it is out.  Only when the
victim is the *sole* occupant — so no other row could ever free blocks for
its return — does the scheduler fall back to the old eviction (retire as
``max_len``).  Mean pool utilization is reported as ``cache_utilization``;
swap traffic as ``preemptions`` / ``swap_out`` / ``swap_in``.

Prefix sharing (``engine.prefix_sharing``): admission is *group-aware* — a
G-way GRPO group refilled in one batch charges its shared full prompt
blocks once (the engine prefills the leader and remaps followers in the
same batched prefill), and a prompt whose prefix is already live in the
engine's radix index is charged only its unshared suffix
(``engine.live_shared_blocks``).  Swap-in re-prefills start from length 0,
so a re-admitted record's full prompt blocks resolve through the radix and
its shared mappings are restored without recompute.  Sharing traffic is
reported as ``prefix_hit_rate`` (prompt tokens served from shared blocks),
``shared_blocks`` (peak blocks mapped by >1 row), ``cow_count`` and
``prefix_evictions``; the allocator's invariant self-check runs at the end
of every stream.

In-flight weight refresh (``engine.publish``/``refresh_weights``): a learner
may publish updated params at any time; the scheduler swaps them in **only
at a round boundary** (top of the decode loop), so a version change can
never land mid-round.  Every sampled token is stamped with the weight
version that produced it (``Trajectory.meta["policy_versions"]``, parallel
to ``meta["logprobs"]``; per-turn summary in ``meta["turn_versions"]``) —
a turn that spans a refresh carries mixed versions, which the GRPO/PPO
losses consume as a per-token staleness signal.  Versions referenced by an
in-flight trajectory stay pinned in the engine's WeightStore until the
trajectory retires.

Determinism: each trajectory owns a PRNG stream (``split(key, n_trajs)``);
its k-th decode turn samples from ``fold_in(traj_key, k)`` folded again per
step inside the engine.  Sampling is therefore independent of which rows
share a decode round — and of how turns are sliced into rounds — so with
instant tools the scheduler reproduces ``rollout_reference`` trajectories
token-for-token (the parity oracle in tests/test_rollout_and_rewards.py).
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import os
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.mdp import Role, Trajectory
from repro.tools.registry import ToolResult

MIN_ROUND_BUDGET = 8        # adaptive floor: never shrink a round below this

# ``REPRO_JAX_PROFILE=<dir>`` wraps the first traced scheduler rounds of the
# process in jax.profiler for device-side correlation with the span
# timeline.  Once per process: profiles are heavyweight and one window is
# what you correlate against.
_JAX_PROFILE_ROUNDS = int(os.environ.get("REPRO_JAX_PROFILE_ROUNDS", "8"))
_jax_profile = {"started": False, "stopped": False}


def _jax_profile_start() -> bool:
    d = os.environ.get("REPRO_JAX_PROFILE")
    if not d or _jax_profile["started"]:
        return False
    jax.profiler.start_trace(d)
    _jax_profile["started"] = True
    return True


def _jax_profile_stop() -> None:
    if _jax_profile["started"] and not _jax_profile["stopped"]:
        _jax_profile["stopped"] = True
        jax.profiler.stop_trace()


def order_by_job_index(trajs: List[Trajectory]) -> List[Trajectory]:
    """Restore task x group order on a completion-ordered trajectory list
    (the contract between ``stream`` and batch consumers): sort by the
    ``job_index`` the scheduler stamped into ``meta``, then strip it."""
    trajs.sort(key=lambda t: t.meta.get("job_index", 0))
    for tr in trajs:
        tr.meta.pop("job_index", None)
    return trajs


# jitted once at module scope: folding the per-trajectory streams with their
# turn indices runs every decode round, and re-tracing a fresh vmap per call
# would dominate the round at small batch sizes
_fold_rows = jax.jit(jax.vmap(jax.random.fold_in))


class _StreamMetrics:
    """One trajectory stream's instruments, on a child registry forwarding
    to the process-wide one under ``rollout/`` — per-stream values stay
    exact (they feed ``last_stats``) while the global registry accumulates
    across streams for ``/api/metrics``."""

    _UTIL_BOUNDS = tuple(i / 20 for i in range(1, 20))   # 0.05 .. 0.95

    def __init__(self, turn_budget: float):
        self.reg = r = obs.MetricsRegistry(parent=obs.get().registry,
                                           parent_prefix="rollout/")
        self.rounds = r.counter("rounds")
        self.tool_wait = r.timer("tool_wait_s")
        self.tool_s = r.counter("tool_latency_s")
        self.tool_timeouts = r.counter("tool_timeouts")
        self.refills = r.counter("refills")
        self.active_slot_rounds = r.counter("active_slot_rounds")
        self.slot_rounds = r.counter("slot_rounds")
        self.model_tokens = r.counter("model_tokens")
        self.min_round_budget = r.gauge("min_round_budget")
        self.min_round_budget.set(float(turn_budget))
        self.adaptive_rounds = r.counter("adaptive_rounds")
        self.admission_deferrals = r.counter("admission_deferrals")
        self.starved_rounds = r.counter("starved_rounds")
        self.evictions = r.counter("evictions")
        self.preemptions = r.counter("preemptions")
        self.swap_out = r.counter("swap_out")
        self.swap_in = r.counter("swap_in")
        self.weight_refreshes = r.counter("weight_refreshes")
        self.executor_degradations = r.counter("executor_degradations")
        self.cache_util = r.histogram("cache_utilization",
                                      bounds=self._UTIL_BOUNDS)
        self.decode_round = r.timer("decode_round_s")
        self.admission_wait = r.timer("admission_wait_s")


class SlotState(enum.Enum):
    FREE = "free"          # no occupant; session row is stopped
    ACTIVE = "active"      # decoding in the fused loop
    PARKED = "parked"      # waiting on an in-flight tool future


@dataclasses.dataclass
class _Job:
    """One trajectory waiting for (or occupying) a slot."""
    index: int                      # position in the returned trajectory list
    traj: Trajectory
    prompt_ids: List[int]
    key: jax.Array                  # per-trajectory PRNG stream
    versions: set = dataclasses.field(default_factory=set)
    #                                 weight versions that sampled any of this
    #                                 trajectory's tokens (pinned until retire)
    enqueued_at: float = 0.0        # tracer time when the job entered the queue
    deferred_at: Optional[float] = None   # first admission deferral (wall)


@dataclasses.dataclass
class _Slot:
    row: int                        # batch row / cache lane this slot owns
    state: SlotState = SlotState.FREE
    job: Optional[_Job] = None
    key: Optional[jax.Array] = None  # occupant's stream (kept after FREE so
    #                                  the stacked row_keys stay well-formed)
    turn_idx: int = 0               # decode turns taken by the occupant
    future: object = None           # executor future while PARKED
    calls: list = dataclasses.field(default_factory=list)
    turn_toks: list = dataclasses.field(default_factory=list)   # mid-turn buf
    turn_lps: list = dataclasses.field(default_factory=list)
    turn_vers: list = dataclasses.field(default_factory=list)   # per-token
    #                                  weight version (parallel to turn_toks)
    pending_obs: Optional[list] = None   # landed obs waiting for cache blocks
    lane_clean: bool = True         # cache lane reset since the last occupant
    admit_t: float = 0.0            # tracer time the occupant took this slot
    park_t: float = 0.0             # tracer time the occupant last parked


@dataclasses.dataclass
class _Swapped:
    """A preempted occupant swapped out to the host: everything needed to
    re-admit it later and resume exactly where it left off.  ``context`` is
    the full token stream that was in the cache lane (prompt + turns +
    mid-turn buffer); swap-in rebuilds the lane by re-prefilling it, so a
    swap costs one extra prefill of the context — latency, not data."""
    job: _Job
    key: jax.Array
    context: List[int]
    turn_idx: int
    turn_toks: list
    turn_lps: list
    turn_vers: list
    calls: list
    future: object = None                # still-in-flight tool future
    pending_obs: Optional[list] = None   # obs that landed while swapped out
    park_t: float = 0.0                  # tracer time the row last parked


class ContinuousScheduler:
    """Drives trajectories through Generate-Parse-Invoke-Update with per-slot
    scheduling.  Requires an executor with the futures API
    (``submit`` / ``drain_ready`` / ``wait_ready`` — AsyncToolExecutor)."""

    def __init__(self, engine, env, tokenizer, config, executor,
                 n_slots: int = 0):
        self.engine = engine
        self.env = env
        self.tok = tokenizer
        self.config = config
        self.executor = executor
        self.n_slots = n_slots or getattr(config, "n_slots", 0)
        self.last_stats: Dict[str, float] = {}
        # Round-sliced turns (adaptive budgets, step_offsets) need the real
        # engine's controls.  The engine declares support via an explicit
        # capability flag — engines/doubles without the attribute are driven
        # turn-per-round (no signature probing: a double may *accept*
        # **kwargs without honouring the round contract).
        self._supports_rounds = bool(getattr(engine, "supports_rounds",
                                             False))
        # Versioned weights (in-flight refresh): the scheduler swaps to the
        # latest published params only between decode rounds and stamps
        # every sampled token with the version that produced it.
        self._versioned = hasattr(engine, "refresh_weights")

    # ------------------------------------------------------------------ API
    def run(self, tasks: Sequence[Tuple[str, object]], key: jax.Array,
            group_size: Optional[int] = None) -> List[Trajectory]:
        """Roll every task out; returns trajectories in task x group order
        (the same order the turn-synchronous reference produces)."""
        return order_by_job_index(
            list(self.stream(tasks, key, group_size=group_size)))

    def stream(self, tasks: Sequence[Tuple[str, object]], key: jax.Array,
               group_size: Optional[int] = None) -> Iterator[Trajectory]:
        """Yield trajectories as they retire (completion order) — the
        trajectory stream the trainer consumes.  Scheduler/occupancy stats
        land in ``self.last_stats`` when the stream is exhausted."""
        gs = self.config.group_size if group_size is None else group_size
        jobs = self._build_jobs(tasks, key, gs)
        n_jobs = len(jobs)
        if n_jobs == 0:
            # even the degenerate stream reports the full key set
            self.last_stats = self._finalize_stats(
                _StreamMetrics(self.config.max_new_tokens), None,
                n_slots=0, n_trajectories=0, wall=0.0)
            return
        queue = collections.deque(jobs)
        B = max(1, min(self.n_slots or n_jobs, n_jobs))
        B = max(1, min(B, self._initial_admissible(jobs[:B])))
        slots = [_Slot(row=i) for i in range(B)]

        first = [queue.popleft() for _ in range(B)]
        by_future: Dict[object, _Slot] = {}
        m = _StreamMetrics(self.config.max_new_tokens)
        trc = obs.get().tracer
        if trc.enabled:
            # stamped BEFORE engine.start so the admission (queued close)
            # happens-before the first prefill span on the trace — the
            # ordering trace_check asserts
            t_q = trc.now()
            for j in jobs:
                j.enqueued_at = t_q
            for slot, job in zip(slots, first):
                slot.admit_t = t_q
                trc.complete("queue", "queued", job.enqueued_at, t_q,
                             job=job.index)
        session = self.engine.start([j.prompt_ids for j in first])
        for slot, job in zip(slots, first):
            slot.job, slot.key, slot.state = job, job.key, SlotState.ACTIVE
            slot.turn_idx = 0
        t_start = time.monotonic()
        retired: List[Trajectory] = []
        to_refill: List[_Slot] = []
        swapped: collections.deque = collections.deque()  # _Swapped records

        def admit_wait(job: _Job) -> None:
            """A job that had been deferred by the admission gate finally
            got in: record how long the pool kept it waiting."""
            if job.deferred_at is not None:
                m.admission_wait.observe(time.monotonic() - job.deferred_at)
                job.deferred_at = None

        def retire(slot: _Slot, reason: str, finished: bool) -> None:
            tr = slot.job.traj
            if slot.turn_toks:          # flush a partial mid-turn buffer
                tr.append(Role.MODEL, slot.turn_toks)
                tr.meta["logprobs"].extend(slot.turn_lps)
                tr.meta["policy_versions"].extend(slot.turn_vers)
                tr.meta["turn_versions"].append(slot.turn_vers[-1])
                m.model_tokens.add(len(slot.turn_toks))
            if self._versioned:         # release this trajectory's pins
                for v in slot.job.versions:
                    self.engine.unpin_version(v)
            tr.stop_reason = reason
            tr.finished = finished
            if trc.enabled:
                # the retire span covers the occupant's whole slot residency
                trc.complete(f"slot{slot.row}", "retire", slot.admit_t,
                             trc.now(), job=slot.job.index, reason=reason,
                             finished=finished)
            retired.append(tr)
            slot.future, slot.calls = None, []
            slot.turn_toks, slot.turn_lps, slot.pending_obs = [], [], None
            slot.turn_vers = []
            slot.job, slot.state = None, SlotState.FREE
            slot.lane_clean = False
            session.stopped[slot.row] = True
            to_refill.append(slot)

        def preempt(slot: _Slot) -> None:
            """Swap an occupied slot out to the host instead of killing it:
            the trajectory's tokens (and any in-flight tool future / landed
            observation) move to a ``_Swapped`` record, the cache lane is
            freed, and ``refill`` re-admits the record once blocks exist.
            An outstanding future stays registered in ``by_future`` mapped
            to the record, so its results land while the row is out."""
            # a landed observation means slot.future is stale (already
            # drained from the executor): carrying it into the record would
            # park the record on a future that can never fire again
            live_future = slot.future if slot.pending_obs is None else None
            rec = _Swapped(
                job=slot.job, key=slot.key,
                context=slot.job.traj.tokens() + list(slot.turn_toks),
                turn_idx=slot.turn_idx,
                turn_toks=slot.turn_toks, turn_lps=slot.turn_lps,
                turn_vers=slot.turn_vers, calls=slot.calls,
                future=live_future, pending_obs=slot.pending_obs,
                park_t=slot.park_t)
            rec.job.deferred_at = time.monotonic()
            if rec.future is not None:
                by_future[rec.future] = rec
            swapped.append(rec)
            if trc.enabled:
                trc.instant(f"slot{slot.row}", "swap_out",
                            job=slot.job.index)
            slot.future, slot.calls = None, []
            slot.turn_toks, slot.turn_lps, slot.turn_vers = [], [], []
            slot.pending_obs = None
            slot.job, slot.state = None, SlotState.FREE
            slot.lane_clean = False
            session.stopped[slot.row] = True
            to_refill.append(slot)
            m.preemptions.add()
            m.swap_out.add()

        def swap_in(slot: _Slot, rec: _Swapped) -> None:
            """Re-admit a swapped-out record into a freed slot: re-prefill
            its full context, then restore exactly the state it was
            preempted in (mid-turn buffer, parked-on-future, or pending
            observation)."""
            slot.job, slot.key = rec.job, rec.key
            slot.turn_idx = rec.turn_idx
            slot.turn_toks, slot.turn_lps = rec.turn_toks, rec.turn_lps
            slot.turn_vers = rec.turn_vers
            slot.calls = rec.calls
            slot.lane_clean = False
            slot.park_t = rec.park_t
            admit_wait(rec.job)
            if trc.enabled:
                slot.admit_t = trc.now()
                trc.instant(f"slot{slot.row}", "swap_in",
                            job=rec.job.index)
            max_len = getattr(self.engine, "max_len", None)
            if (rec.pending_obs is not None and max_len is not None
                    and len(rec.context) + len(rec.pending_obs) > max_len):
                # its observation landed while out and can never fit —
                # same contract as the ``_land`` overflow path
                retire(slot, "max_len", finished=False)
                return
            self._extend_rows(session, [slot.row], [rec.context])
            m.swap_in.add()
            if rec.future is not None:
                slot.future = rec.future
                by_future[rec.future] = slot
                slot.state = SlotState.PARKED
                session.stopped[slot.row] = True
            elif rec.pending_obs is not None:
                slot.pending_obs = rec.pending_obs
                slot.state = SlotState.PARKED
                session.stopped[slot.row] = True
            else:
                slot.state = SlotState.ACTIVE

        def refill() -> int:
            """Hand every just-freed slot the next waiting occupant —
            swapped-out records first (they hold partial trajectories),
            then queued tasks in ONE batched reset + prefill (GRPO group
            members tend to retire together).

            Freed lanes are reset *first* — in paged mode that returns their
            blocks to the pool, and it must happen even with an empty queue
            so a dead lane can never pin blocks a live parked row is waiting
            for.  Swap-ins and queued tasks are then admitted against the
            free-block headroom minus what this very batch has already
            claimed (several admissions must not jointly over-commit the
            pool); whatever doesn't fit waits (zero-free-blocks
            backpressure).  A swap-in must additionally leave room for the
            pending observations of still-parked rows — the blocks whose
            shortage caused the preemption — or it would re-create the very
            wedge it resolved.  If nothing is running at all, one occupant
            is force-admitted regardless so an oversized context surfaces
            as an engine error instead of a silent wedge."""
            if not to_refill:
                return 0
            dirty = [s for s in to_refill if not s.lane_clean]
            if dirty:
                self._reset_rows(session, [s.row for s in dirty])
                for s in dirty:
                    s.lane_clean = True
            if not queue and not swapped:
                return 0
            admitted = 0
            claimed = 0
            seen: set = set()       # prompts admitted in THIS batched refill
            backlog = sum(self._obs_blocks(session, s) for s in slots
                          if s.state is SlotState.PARKED
                          and s.pending_obs is not None)
            while to_refill and swapped:
                need = self._admission_blocks(session, swapped[0].context)
                admit_ok = self._can_admit(session, need + backlog, claimed)
                if not admit_ok:
                    if admitted or any(s.job is not None for s in slots):
                        m.admission_deferrals.add()
                        break
                rec = swapped.popleft()
                slot = to_refill.pop()
                claimed += need
                admitted += 1
                swap_in(slot, rec)
                if not admit_ok:
                    break               # force-admitted exactly one
            rows, prompts = [], []
            while to_refill and queue:
                # group-aware: a G-way group refilled together is charged
                # its shared prompt blocks once (the engine's prefix
                # sharing maps followers onto the leader's blocks in the
                # same batched prefill below)
                need = self._admission_blocks(session, queue[0].prompt_ids,
                                              seen)
                admit_ok = self._can_admit(session, need, claimed)
                if not admit_ok:
                    if rows or admitted \
                            or any(s.job is not None for s in slots):
                        m.admission_deferrals.add()
                        if queue[0].deferred_at is None:
                            queue[0].deferred_at = time.monotonic()
                        break
                slot, job = to_refill.pop(), queue.popleft()
                slot.job, slot.key, slot.state = job, job.key, SlotState.ACTIVE
                slot.turn_idx = 0
                slot.lane_clean = False
                admit_wait(job)
                if trc.enabled:
                    slot.admit_t = trc.now()
                    trc.complete("queue", "queued", job.enqueued_at,
                                 slot.admit_t, job=job.index)
                claimed += need
                seen.add(tuple(job.prompt_ids))
                rows.append(slot.row)
                prompts.append(job.prompt_ids)
                if not admit_ok:
                    break               # force-admitted exactly one
            if rows:
                self._extend_rows(session, rows, prompts)
                m.refills.add(len(rows))
            return admitted + len(rows)

        try:
            yield from self._schedule(session, slots, queue, by_future,
                                      m, trc, retired, retire, refill,
                                      preempt)
        finally:
            # finalize even when the consumer abandons the stream early,
            # and release any still-parked futures from the executor
            if by_future and hasattr(self.executor, "forget"):
                self.executor.forget(by_future)
            if self._versioned:
                # abandoned mid-stream: release weight pins of occupants
                # (and swapped-out records) that never retired, so no
                # version leaks in the store
                for slot in slots:
                    if slot.job is not None and slot.job.versions:
                        for v in slot.job.versions:
                            self.engine.unpin_version(v)
                        slot.job.versions = set()
                for rec in swapped:
                    for v in rec.job.versions:
                        self.engine.unpin_version(v)
                    rec.job.versions = set()
            self.last_stats = self._finalize_stats(
                m, session, n_slots=B, n_trajectories=n_jobs,
                wall=time.monotonic() - t_start)
            trc.export("rollout")
            _jax_profile_stop()
            # Allocator invariant self-check after the churn of a whole
            # stream (retire/refill/swap/preempt): shared blocks must be
            # neither leaked nor double-freed.  Runs on every scheduler
            # test by construction.
            alloc = getattr(session, "allocator", None)
            if alloc is not None and hasattr(alloc, "check"):
                alloc.check()

    def _finalize_stats(self, m: _StreamMetrics, session, n_slots: int,
                        n_trajectories: int, wall: float) -> Dict[str, float]:
        """The ONE place ``last_stats`` is assembled — every exit path
        (normal exhaustion, abandoned stream, error teardown) reports the
        same key set, fed by the stream's metrics registry."""
        out = {
            "wall_s": wall,
            "rounds": m.rounds.value,
            "gen_s": m.decode_round.sum,
            "tool_wait_s": m.tool_wait.sum,
            "refills": m.refills.value,
            "model_tokens": m.model_tokens.value,
            "slot_occupancy": (m.active_slot_rounds.value
                               / max(m.slot_rounds.value, 1.0)),
            "tool_latency_s": m.tool_s.value,
            "tool_timeouts": m.tool_timeouts.value,
            "overlap_factor": m.tool_s.value / max(wall, 1e-9),
            "n_slots": float(n_slots),
            "n_trajectories": float(n_trajectories),
            "min_round_budget": m.min_round_budget.value,
            "adaptive_rounds": m.adaptive_rounds.value,
            "admission_deferrals": m.admission_deferrals.value,
            "admission_wait_p90_s": m.admission_wait.percentile(90),
            "starved_rounds": m.starved_rounds.value,
            "evictions": m.evictions.value,
            "preemptions": m.preemptions.value,
            "swap_out": m.swap_out.value,
            "swap_in": m.swap_in.value,
            "weight_refreshes": m.weight_refreshes.value,
            "executor_degradations": m.executor_degradations.value,
            "decode_round_p50_s": m.decode_round.percentile(50),
            "decode_round_p99_s": m.decode_round.percentile(99),
        }
        if m.cache_util.count:
            out["cache_utilization"] = m.cache_util.mean
            out["cache_utilization_peak"] = m.cache_util.max
        if session is not None and hasattr(self.engine, "prefix_stats"):
            ps = self.engine.prefix_stats(session)
            if ps is not None:
                out["prefix_hit_rate"] = ps["prefix_hit_rate"]
                out["shared_blocks"] = float(ps["shared_blocks_peak"])
                out["cow_count"] = float(ps["cow_count"])
                out["prefix_evictions"] = float(ps["prefix_evictions"])
        return out

    def _schedule(self, session, slots, queue, by_future, m, trc, retired,
                  retire, refill, preempt) -> Iterator[Trajectory]:
        """The park/retire/refill loop proper (see module docstring).  Each
        round is one ``rollout/round`` span (retirees are yielded between
        rounds, outside it), its phases the child spans ``rollout/refill``,
        ``rollout/tools``, ``rollout/absorb``, ``rollout/decode`` and
        ``rollout/parse``."""
        no_progress = 0
        profiling = _jax_profile_start()
        prof_rounds = 0
        while True:
            for tr in retired:
                yield tr
            retired.clear()
            with obs.span("rollout/round"):
                with obs.span("rollout/refill"):
                    progress = refill() > 0
                parked = [s for s in slots if s.state is SlotState.PARKED]
                active = [s for s in slots if s.state is SlotState.ACTIVE]
                if not parked and not active:
                    break
                if parked:
                    if by_future:
                        with obs.span("rollout/tools"):
                            progress |= self._collect_tools(
                                session, by_future, bool(active), retire, m)
                    with obs.span("rollout/absorb"):
                        progress |= self._absorb(session, slots, trc)
                    # absorption revives rows (and retire may refill
                    # slots): re-derive the active set so the parse phase
                    # covers every row the engine will actually decode
                    active = [s for s in slots
                              if s.state is SlotState.ACTIVE]
                    if not active:
                        if not progress and not by_future:
                            # pool wedged: every slot is waiting for blocks
                            # that nothing left alive can free — swap out
                            # the longest
                            self._preempt(session, slots, retire, preempt, m)
                        continue

                ver = self._refresh_weights(m, trc)
                m.rounds.add()
                m.slot_rounds.add(len(slots))
                m.active_slot_rounds.add(len(active))
                row_keys = self._row_keys(slots)
                n_parked = sum(1 for s in slots
                               if s.state is SlotState.PARKED)
                round_budget = self._round_budget(len(active), n_parked)
                gen_kw = self._round_controls(slots, active, round_budget, m)
                with obs.span("rollout/decode", rows=len(active)) as dec:
                    res = self.engine.generate(
                        session, round_budget, None,
                        temperature=self.config.temperature,
                        row_keys=row_keys, **gen_kw)
                m.decode_round.observe(dec.seconds)
                if trc.enabled:
                    for s in active:
                        trc.complete(f"slot{s.row}", "decode_round",
                                     trc.since(dec.t0), trc.since(dec.t1),
                                     turn=s.turn_idx, job=s.job.index)
                if profiling:
                    prof_rounds += 1
                    if prof_rounds >= _JAX_PROFILE_ROUNDS:
                        _jax_profile_stop()
                        profiling = False
                if hasattr(self.engine, "cache_utilization"):
                    util = self.engine.cache_utilization(session)
                    if util is not None:
                        m.cache_util.observe(util)
                with obs.span("rollout/parse"):
                    progress |= self._parse(session, active, res, ver,
                                            by_future, retire, m, trc)

                # Wedge breaker: rounds that move no token, land no future
                # and admit nothing (no tool I/O left in flight) mean every
                # occupied row is starved for blocks that nothing alive can
                # free.  Swap out the longest row (vLLM-preemption analogue).
                if progress or retired or by_future:
                    no_progress = 0
                else:
                    no_progress += 1
                    if no_progress >= 2:
                        self._preempt(session, slots, retire, preempt, m)
                        no_progress = 0

    def _collect_tools(self, session, by_future, any_active: bool, retire,
                       m) -> bool:
        """Overlap point: a non-blocking drain while rows are decoding, a
        blocking wait for the first completion only when nothing can
        decode.  The drain is scoped to our own futures so several
        consumers can share one executor.  Landed results become pending
        observations (or retire their row).  True when anything landed."""
        if any_active:
            ready = self.executor.drain_ready(by_future)
        else:
            t0 = time.monotonic()
            ready = self.executor.wait_ready(futures=by_future)
            m.tool_wait.observe(time.monotonic() - t0)
        landed = False
        for fut in ready:
            target = by_future.pop(fut, None)
            if target is None:
                continue
            landed = True
            if isinstance(target, _Swapped):
                # row is swapped out: stage the observation on the record;
                # swap-in absorbs it (the max_len check runs there, where
                # lengths exist again)
                target.pending_obs = self._obs_ids(target.calls, fut, m)
                target.future = None
                continue
            self._land(session, target, fut, retire, m)
        return landed

    def _absorb(self, session, slots, trc) -> bool:
        """Absorb landed observations whose rows can get cache blocks, in
        one batched prefill; the rest stay pending (paged backpressure) and
        retry once a retirement frees blocks.  ``claimed`` makes the
        per-row checks cumulative: several observations admitted into one
        batched prefill must not jointly over-commit the pool.  True when
        any row was revived."""
        rows, obs_lists = [], []
        claimed = 0
        for slot in slots:
            if slot.state is not SlotState.PARKED \
                    or slot.pending_obs is None:
                continue
            need = self._obs_blocks(session, slot)
            if need > self._free_after(session, claimed):
                continue
            claimed += need
            ids = slot.pending_obs
            tr = slot.job.traj
            tr.append(Role.OBSERVATION, ids)
            tr.meta["logprobs"].extend([0.0] * len(ids))
            tr.meta["policy_versions"].extend(
                [self._active_version()] * len(ids))
            rows.append(slot.row)
            obs_lists.append(ids)
            if trc.enabled:
                # park -> revived: the row's tool-I/O shadow
                trc.complete(f"slot{slot.row}", "tool_wait", slot.park_t,
                             trc.now(), job=slot.job.index,
                             obs_tokens=len(ids))
            slot.pending_obs, slot.future, slot.calls = None, None, []
            slot.state = SlotState.ACTIVE
        if rows:
            self._extend_rows(session, rows, obs_lists)
        return bool(rows)

    def _refresh_weights(self, m, trc) -> int:
        """Round boundary: swap to the latest published weights (if a
        learner staged any since the previous round).  The swap can only
        happen HERE — never inside a round — so every token this round
        samples is attributable to exactly one version.  Returns it."""
        if not self._versioned:
            return 0
        prev_ver = int(self.engine.active_version)
        ver = int(self.engine.refresh_weights())
        if ver != prev_ver:
            m.weight_refreshes.add()
            if trc.enabled:
                trc.instant("sched", "weight_refresh", version=ver)
        return ver

    def _round_controls(self, slots, active, round_budget: int, m) -> dict:
        """The engine's round-slicing controls: each active row's offset
        into its turn's sampling stream and its budget this round."""
        if not self._supports_rounds:
            return {}
        turn_budget = self.config.max_new_tokens
        offsets = np.zeros((len(slots),), np.int32)
        budgets = np.zeros((len(slots),), np.int32)
        for s in active:
            done = len(s.turn_toks)      # tokens already this turn
            offsets[s.row] = done
            budgets[s.row] = max(0, min(round_budget, turn_budget - done))
        if round_budget < turn_budget:
            m.adaptive_rounds.add()
        m.min_round_budget.set_min(float(round_budget))
        return {"step_offsets": offsets, "row_budgets": budgets}

    def _parse(self, session, active, res, ver: int, by_future, retire,
               m, trc) -> bool:
        """Fold each active row's round tokens into its turn; parse every
        finished turn and retire the row, or submit its tool calls and park
        it.  True when any row sampled a token."""
        turn_budget = self.config.max_new_tokens
        stop_set = set(getattr(self.engine, "stop_ids", ()) or ())
        progress = False
        for slot in active:
            n_tok = int(res.counts[slot.row])
            if n_tok == 0 and not slot.turn_toks:
                if np.asarray(session.stopped)[slot.row]:
                    # the engine refused the row: context exhausted
                    retire(slot, "max_len", finished=False)
                else:
                    # paged pool starvation: no blocks for this round — stay
                    # ACTIVE and retry once a retirement frees some
                    m.starved_rounds.add()
                continue
            if n_tok:
                slot.turn_toks.extend(res.tokens[slot.row, :n_tok].tolist())
                slot.turn_lps.extend(
                    float(x) for x in res.logprobs[slot.row, :n_tok])
                slot.turn_vers.extend([ver] * n_tok)
                if self._versioned and ver not in slot.job.versions:
                    # pin the sampling version until this trajectory
                    # retires (its old_logprobs reference these params)
                    self.engine.pin_version(ver)
                    slot.job.versions.add(ver)
                progress = True
            # A logical turn ends on a stop id, the full turn budget, or an
            # exhausted context; otherwise the row stays mid-turn and
            # resumes next round (round-sliced turns).
            turn_done = (not self._supports_rounds
                         or slot.turn_toks[-1] in stop_set
                         or len(slot.turn_toks) >= turn_budget
                         or bool(np.asarray(session.stopped)[slot.row]))
            if not turn_done:
                continue
            row_toks = slot.turn_toks
            tr = slot.job.traj
            tr.append(Role.MODEL, row_toks)
            tr.meta["logprobs"].extend(slot.turn_lps)
            tr.meta["policy_versions"].extend(slot.turn_vers)
            tr.meta["turn_versions"].append(slot.turn_vers[-1])
            m.model_tokens.add(len(row_toks))
            slot.turn_toks, slot.turn_lps = [], []
            slot.turn_vers = []
            slot.turn_idx += 1
            text = self.tok.decode(row_toks)
            calls, answer = self.env.manager.parse_response(text)
            over_budget = (tr.n_tool_calls + len(calls)
                           > self.env.max_tool_calls)
            if answer is not None or not calls or over_budget:
                reason = ("answer" if answer is not None else
                          "no_call" if not calls else "tool_budget")
                retire(slot, reason, finished=answer is not None)
                continue
            tr.n_tool_calls += len(calls)
            if slot.turn_idx >= self.config.max_turns:
                # calls counted but not executed — same contract as the
                # reference loop, which breaks before its Invoke stage
                retire(slot, "max_turns", finished=False)
                continue
            slot.calls = calls
            slot.future = self.executor.submit(calls)
            by_future[slot.future] = slot
            slot.state = SlotState.PARKED
            if trc.enabled:
                slot.park_t = trc.now()
            session.stopped[slot.row] = True
        return progress

    # ------------------------------------------------------------- internals
    def _active_version(self) -> int:
        """Weight version currently serving decode (0 for unversioned
        engine doubles)."""
        return (int(self.engine.active_version) if self._versioned else 0)

    def _build_jobs(self, tasks, key, gs) -> List[_Job]:
        jobs: List[_Job] = []
        n = len(tasks) * gs
        keys = jax.random.split(key, max(n, 1))
        ver = self._active_version()
        for gid, (q, gt) in enumerate(tasks):
            prompt_ids = self.tok.encode(self.env.manager.get_prompt(q),
                                         add_bos=True)
            for _ in range(gs):
                tr = Trajectory(group_id=gid,
                                meta={"question": q, "ground_truth": gt,
                                      "logprobs": [],
                                      "policy_versions": [],
                                      "turn_versions": [],
                                      "job_index": len(jobs)})
                tr.append(Role.PROMPT, prompt_ids)
                tr.meta["logprobs"].extend([0.0] * len(prompt_ids))
                # prompt tokens are not sampled; stamped with the version at
                # job-build time purely to keep the array parallel to
                # ``logprobs`` (they are loss-masked out downstream)
                tr.meta["policy_versions"].extend([ver] * len(prompt_ids))
                jobs.append(_Job(index=len(jobs), traj=tr,
                                 prompt_ids=list(prompt_ids),
                                 key=keys[len(jobs)]))
        return jobs

    def _row_keys(self, slots: List[_Slot]) -> jax.Array:
        """(B, 2) per-row keys: occupant's stream folded with its turn index
        (idle rows carry their last occupant's key — they never sample)."""
        keys = jnp.stack([s.key for s in slots])
        turns = jnp.asarray([s.turn_idx for s in slots], jnp.int32)
        return _fold_rows(keys, turns)

    def _obs_ids(self, calls, fut, m: _StreamMetrics) -> List[int]:
        """Resolve a landed tool future into observation token ids (shared
        by parked slots and swapped-out records)."""
        try:
            results: List[ToolResult] = fut.result()
        # An executor-side failure (not a tool error — those come back as
        # ok=False results) degrades to error observations so the stream
        # finishes; rollout/executor_degradations makes it visible.
        except Exception as e:  # lint: disable=broad-except
            m.executor_degradations.add()
            results = [ToolResult(c.name, f"ERROR: {type(e).__name__}: {e}",
                                  ok=False, call_id=c.call_id)
                       for c in calls]
        m.tool_s.add(sum(r.latency_s for r in results))
        n_to = sum(1 for r in results if getattr(r, "timeout", False))
        if n_to:
            m.tool_timeouts.add(n_to)
        return self.tok.encode(self.env.manager.format_observation(results))

    def _land(self, session, slot: _Slot, fut, retire, m) -> None:
        """A parked row's tool results landed: tokenize the observation and
        stage it on the slot (``pending_obs``) for the caller's batched,
        block-gated prefill — or retire the slot if the context is full."""
        ids = self._obs_ids(slot.calls, fut, m)
        max_len = getattr(self.engine, "max_len", None)
        lengths = np.asarray(session.lengths)
        if max_len is not None and int(lengths[slot.row]) + len(ids) > max_len:
            # observation cannot fit at all — retire instead of overflowing
            # (an observation that fits but leaves no decode room is still
            # prefilled, matching the reference loop; the next round then
            # retires the row with counts==0)
            retire(slot, "max_len", finished=False)
            return
        slot.pending_obs = ids

    def _obs_blocks(self, session, slot: _Slot) -> int:
        """Blocks this pending observation's prefill would claim (0 for
        contiguous engines/doubles)."""
        if not hasattr(self.engine, "blocks_needed"):
            return 0
        target = (int(np.asarray(session.lengths)[slot.row])
                  + len(slot.pending_obs))
        return self.engine.blocks_needed(session, slot.row, target)

    def _free_after(self, session, claimed: int) -> float:
        """Free pool blocks once ``claimed`` (admitted earlier in the same
        batched prefill) are accounted for; unbounded for contiguous."""
        if not hasattr(self.engine, "free_blocks"):
            return float("inf")
        free = self.engine.free_blocks(session)
        return float("inf") if free is None else free - claimed

    def _preempt(self, session, slots, retire, preempt, m) -> None:
        """Break a block-pool wedge by swapping the longest occupied row out
        to the host (swap-don't-kill): its blocks return to the pool and
        ``refill`` re-admits it later via a context re-prefill, so the
        trajectory survives intact.  Only when the victim is the *sole*
        occupant — meaning no other row could ever free the blocks it is
        itself waiting for — does this degrade to the old eviction: retire
        with stop_reason 'max_len', keeping everything sampled so far."""
        lengths = np.asarray(session.lengths)
        occupied = [s for s in slots if s.job is not None]
        if not occupied:
            return
        victim = max(occupied, key=lambda s: int(lengths[s.row]))
        if len(occupied) == 1:
            m.evictions.add()
            retire(victim, "max_len", finished=False)
            return
        preempt(victim)

    def _round_budget(self, n_active: int, n_parked: int) -> int:
        """Per-round decode budget: the full turn budget while nothing is
        parked, shrunk proportionally to the active fraction once slots are
        waiting on tool futures — mostly-parked batches take short decode
        rounds so landed observations are drained (and parked rows revived)
        sooner.  Never changes sampled tokens, only how turns are sliced."""
        budget = self.config.max_new_tokens
        if (not getattr(self.config, "adaptive_budget", True)
                or not self._supports_rounds or n_parked == 0):
            return budget
        frac = n_active / max(n_active + n_parked, 1)
        return max(min(MIN_ROUND_BUDGET, budget),
                   int(np.ceil(budget * frac)))

    def _admission_blocks(self, session, token_ids: Sequence[int],
                          seen=None) -> int:
        """Worst-case block footprint of admitting a task: its context plus
        one full decode turn (0 for contiguous engines/doubles), minus the
        blocks prefix sharing will serve for free.

        Group-aware admission: a prompt identical to one admitted earlier
        in the *same* batched refill (``seen``) shares every full prompt
        block with its leader — and its private tail copy-on-write is
        exactly the tail block the remaining charge still counts — so it is
        charged only ``blocks_for(len + turn) - len // page_size`` unique
        blocks.  Cross-batch, the engine's radix probe
        (``live_shared_blocks``) discounts full prompt blocks already
        mapped by a live row (cached-but-unreferenced chains are NOT
        discounted — mapping them consumes reclaimable capacity the
        headroom math counts as free).
        """
        if not hasattr(self.engine, "blocks_for"):
            return 0
        need = self.engine.blocks_for(len(token_ids)
                                      + self.config.max_new_tokens)
        bs = int(getattr(self.engine, "page_size", 0) or 0)
        if bs and seen is not None and tuple(token_ids) in seen \
                and getattr(self.engine, "prefix_sharing", False):
            return max(0, need - len(token_ids) // bs)
        if session is not None and hasattr(self.engine,
                                           "live_shared_blocks"):
            need -= int(self.engine.live_shared_blocks(session, token_ids))
        return max(0, need)

    def _can_admit(self, session, need: int, claimed: int = 0) -> bool:
        """Free-block admission gate (always true for contiguous caches):
        ``need`` blocks must fit beyond the worst-case growth reserve of the
        rows already running and the ``claimed`` blocks of tasks admitted
        earlier in the same batched refill."""
        if (getattr(session, "allocator", None) is None
                or not hasattr(self.engine, "admission_headroom")):
            return True
        budget = self.config.max_new_tokens
        return (self.engine.admission_headroom(session, budget) - claimed
                >= need)

    def _initial_admissible(self, jobs: List[_Job]) -> int:
        """How many of the first jobs fit the configured block pool at once
        (worst case: prompt + one full turn each; identical prompts —
        GRPO groups — charge their shared full prompt blocks once, since
        the initial ``engine.start`` prefills them all in one sharing
        batch).  Unlimited for contiguous engines or auto-sized pools."""
        total = getattr(self.engine, "total_blocks", None)
        if total is None:
            return len(jobs)
        seen: set = set()
        acc = n = 0
        for job in jobs:
            acc += self._admission_blocks(None, job.prompt_ids, seen)
            if acc > total:
                break
            seen.add(tuple(job.prompt_ids))
            n += 1
        return max(1, n)

    # Engine doubles in tests implement only the coarse session API; fall
    # back to a full-batch extend with empty rows for them.
    def _extend_rows(self, session, rows, token_lists) -> None:
        if hasattr(self.engine, "extend_rows"):
            self.engine.extend_rows(session, rows, token_lists)
            return
        full = [[] for _ in range(session.batch)]
        for r, t in zip(rows, token_lists):
            full[int(r)] = list(t)
        self.engine.extend(session, full)
        for r in rows:
            session.stopped[int(r)] = False

    def _reset_rows(self, session, rows) -> None:
        if hasattr(self.engine, "reset_rows"):
            self.engine.reset_rows(session, rows)
            return
        for r in rows:
            session.lengths[int(r)] = 0
            session.stopped[int(r)] = True
