"""Host spans on the profiler's clock, compile counters, stable device-side
names, and the engine / scheduler / learner instrumentation built on them.
"""
import glob
import json
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


def _profile(path):
    from jax.profiler import ProfileData
    (f,) = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return ProfileData.from_file(f)


def _host_events(pd, *names):
    """name -> [(start_ns, duration_ns, stats)] of the host events named
    (read while the plane and line that own them are alive)."""
    out = {}
    with warnings.catch_warnings():     # nanobind's lazy stats types warn
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns, dict(e.stats)))
    return out


def _profile_start_ns(pd) -> int:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if plane.name == "Task Environment":
                return int(dict(plane.stats)["profile_start_time"])
    raise AssertionError("no Task Environment plane")


# ---------------------------------------------------------------- spans
def test_span_lands_in_profiler_trace_with_nesting_and_metadata(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("engine/extend", tokens=5, rows=2):
            assert obs.current_span() == "engine/extend"
            with obs.span("engine/prefill"):
                assert obs.current_span() == "engine/prefill"
                jnp.ones((8, 8)).sum().block_until_ready()
        assert obs.current_span() is None
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(_profile(tmp_path), "engine/extend", "engine/prefill")
    (outer,), (inner,) = ev["engine/extend"], ev["engine/prefill"]
    assert outer[0] <= inner[0]
    assert inner[0] + inner[1] <= outer[0] + outer[1]
    assert outer[2] == {"tokens": 5, "rows": 2}


def test_tracer_export_maps_onto_profile_clock_within_1ms(tmp_path):
    """One offset maps the SpanTracer's Chrome export onto the xplane: the
    export's ``epoch_unix_ns`` plus its ``ts``, less the profile's
    ``profile_start_time``, is the xplane ``start_ns`` of the same span."""
    prof = tmp_path / "prof"
    with obs.scoped(trace=True, trace_dir=str(tmp_path / "trace")) as o:
        jax.profiler.start_trace(str(prof))
        try:
            time.sleep(0.02)
            with obs.span("engine/prefill", tokens=3, rows=1):
                time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
        export = json.load(open(o.tracer.export("clock")))
    (ev,) = [e for e in export["traceEvents"]
             if e["ph"] == "X" and e["name"] == "prefill"]
    pd = _profile(prof)
    (xev,) = _host_events(pd, "engine/prefill")["engine/prefill"]
    mapped = (export["otherData"]["epoch_unix_ns"] + 1000 * ev["ts"]
              - _profile_start_ns(pd))
    assert abs(mapped - xev[0]) < 1e6
    assert abs(1000 * ev["dur"] - xev[1]) < 1e6


def test_span_records_in_tracer_only_when_tracing_is_on(tmp_path):
    with obs.scoped(trace=False) as o:
        with obs.span("engine/prefill", tokens=4) as sp:
            pass
        assert o.tracer.events() == []
        assert sp.seconds >= 0.0
    with obs.scoped(trace=True, trace_dir=str(tmp_path)) as o:
        with obs.span("engine/prefill", tokens=4):
            pass
        with obs.span("train/step", track="learner", event="learner_update"):
            pass
        evs = [e for e in o.tracer.events() if e["ph"] == "X"]
        tracks = {e["tid"]: e["args"]["name"] for e in o.tracer.events()
                  if e["ph"] == "M"}
        assert [(tracks[e["tid"]], e["name"]) for e in evs] == [
            ("engine", "prefill"), ("learner", "learner_update")]
        assert evs[0]["args"] == {"tokens": 4}


def test_named_program_fixes_the_module_name():
    def some_python_name(x, *, k):
        return x * k

    f = jax.jit(obs.named_program(some_python_name, "decode_loop"),
                static_argnames=("k",))
    assert "module @jit_decode_loop" in f.lower(jnp.ones(3), k=2).as_text()
    assert float(f(jnp.ones(3), k=2)[0]) == 2.0


# ------------------------------------------------------ compile counters
_COMPILE_S = ("compile/trace_s", "compile/lower_s", "compile/backend_s",
              "compile/cache_load_s")


def test_compile_listener_counts_a_fresh_jit_and_credits_the_span(tmp_path):
    n = float(time.time_ns())          # a constant no earlier jit has seen

    @jax.jit
    def inner(x):
        return jnp.sin(x) * n

    def outer(x):                      # traces ``inner`` inside its trace
        return inner(x) + 1.0

    x = jnp.ones((5,))
    with obs.scoped(trace=True, trace_dir=str(tmp_path)) as o:
        t0 = time.monotonic()
        with obs.span("engine/reset"):
            jax.jit(outer)(x).block_until_ready()
        wall = time.monotonic() - t0
        snap = o.registry.snapshot()
        instants = [e for e in o.tracer.events()
                    if e["ph"] == "i" and e["name"] == "compile"]
    assert snap["compile/programs"] >= 1
    assert snap["compile/backend_s"] > 0 and snap["compile/trace_s"] > 0
    total = sum(snap.get(k, 0.0) for k in _COMPILE_S)
    # nested events are credited with their own time only: the counters
    # add up to no more than the wall time they happened in, and all of
    # it is credited to the span that was open
    assert 0 < total <= wall
    assert snap["compile/span_s:engine/reset"] == pytest.approx(total)
    assert {e["args"]["span"] for e in instants} == {"engine/reset"}
    assert sum(e["args"]["seconds"] for e in instants) == pytest.approx(total)
    assert any(e["args"]["fun_name"] == "outer" for e in instants)


def test_compile_listener_nesting_frames():
    """The listener's bookkeeping on a scripted event sequence: a trace
    holding another trace, and a backend compile holding a cache load."""
    from repro.obs import spans as S
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    with obs.scoped() as o:
        S._on_start(trace, 0.0, fun_name="outer")
        S._on_start(trace, 0.0, fun_name="inner")
        S._on_duration(trace, 0.25, fun_name="inner")
        S._on_duration(trace, 1.0, fun_name="outer")
        S._on_start(backend, 0.0, fun_name="jit(outer)")
        S._on_duration(S._CACHE_LOAD_EVENT, 0.5)
        S._on_duration(backend, 2.0, fun_name="jit(outer)")
        snap = o.registry.snapshot()
    assert snap["compile/trace_s"] == pytest.approx(1.0)
    assert snap["compile/backend_s"] == pytest.approx(1.5)
    assert snap["compile/cache_load_s"] == pytest.approx(0.5)
    assert snap["compile/programs"] == 1.0
    assert S._frames() == []


# ------------------------------------------------- stable device names
@pytest.fixture(scope="module")
def tiny_paged():
    from repro.configs import get_config
    from repro.data.tokenizer import default_tokenizer
    from repro.models import Model
    from repro.serving.engine import GenerationEngine
    cfg = get_config("tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = default_tokenizer(cfg.vocab_size)

    def make(**kw):
        return GenerationEngine(model, params, pad_id=tok.pad_id,
                                stop_ids=(tok.eos_id,), max_len=128,
                                cache_mode="paged", page_size=16, **kw)
    return make


def test_engine_programs_carry_stable_module_names(tiny_paged):
    eng = tiny_paged()
    s = eng.start([[5, 6, 7]] * 2)
    B, L = 2, 32
    toks = jnp.zeros((B, L), jnp.int32)
    pre = eng._prefill_jit.lower(eng.params, s.cache, toks, toks,
                                 jnp.zeros((B, L), bool), None)
    assert "module @jit_prefill" in pre.as_text()
    loop = eng._loop_jit.lower(
        eng.params, s.cache, s.last_logits, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool), None, jax.random.split(jax.random.PRNGKey(0),
                                                      B),
        jnp.int32(4), jnp.float32(1.0), jnp.zeros((1,), jnp.int32), None,
        jnp.zeros((B,), jnp.int32), jnp.full((B,), 4, jnp.int32),
        T=32, per_row=True)
    assert "module @jit_decode_loop" in loop.as_text()


def test_paged_kernel_carries_its_name():
    from repro.kernels.paged_attention import paged_attention_fwd
    B, H, Hk, D, bs, N, T = 2, 4, 2, 16, 8, 9, 4
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention_fwd(
        *a, interpret=True))(
        jnp.zeros((B, H, D)), jnp.zeros((N, Hk, bs, D)),
        jnp.zeros((N, Hk, bs, D)), jnp.zeros((B, T), jnp.int32),
        jnp.zeros((B,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert str(call.params["name"]) == "paged_attention"


# --------------------------------------------- engine spans and counters
def test_engine_counts_prefill_and_shared_prefix_tokens(tiny_paged):
    prompt = list(range(3, 43))                     # 40 tokens
    with obs.scoped() as o:
        eng = tiny_paged()
        s = eng.start([prompt] * 4)                 # a group of 4
        snap = o.registry.snapshot()
        assert snap["engine/prefill_tokens"] == 160
        assert snap["engine/shared_prefix_tokens"] == 120   # 3 followers
        # a later identical prompt maps two full radix blocks (32 tokens)
        eng.reset_rows(s, [0])
        eng.extend_rows(s, [0], [prompt])
        snap = o.registry.snapshot()
        assert snap["engine/prefill_tokens"] == 200
        assert snap["engine/shared_prefix_tokens"] == 152
        assert snap["engine/radix_hit_tokens"] == 32
        assert not any(k.startswith("engine/prefill_s") for k in snap)


def test_engine_spans_nest_per_call(tiny_paged, tmp_path):
    with obs.scoped(trace=True, trace_dir=str(tmp_path)) as o:
        eng = tiny_paged()
        s = eng.start([[5, 6, 7, 8]] * 2)
        eng.generate(s, 4, None, row_keys=jax.random.split(
            jax.random.PRNGKey(0), 2))
        eng.reset_rows(s, [1])
        tracks = {e["tid"]: e["args"]["name"] for e in o.tracer.events()
                  if e["ph"] == "M"}
        spans = [e for e in o.tracer.events() if e["ph"] == "X"
                 and tracks[e["tid"]] == "engine"]

    def inside(name, parent):
        (p,) = [e for e in spans if e["name"] == parent]
        kids = [e for e in spans if e["name"] == name
                and p["ts"] <= e["ts"] <= p["ts"] + p["dur"]]
        return len(kids)

    assert inside("extend", "start") == 1
    assert inside("prefill", "extend") == 1
    assert inside("share", "extend") == 2           # plan, then finish
    assert inside("decode_loop", "generate") == 1
    assert inside("fetch", "generate") == 1
    assert inside("cow_copy", "generate") == 1      # the shared tail
    assert inside("free", "reset") == 1


# ------------------------------------------------- scheduler and learner
@pytest.fixture(scope="module")
def tiny_env():
    from repro.configs import get_config
    from repro.data.tokenizer import default_tokenizer
    from repro.models import Model
    from repro.tools.search_env import SearchEnv
    cfg = get_config("tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, default_tokenizer(cfg.vocab_size), \
        SearchEnv(n_entities=30, seed=0)


def test_scheduler_round_spans_and_gen_s_from_the_round_timer(tiny_env,
                                                              tmp_path):
    from repro.core.rollout import RolloutConfig, RolloutWorker
    from repro.serving.engine import GenerationEngine
    model, params, tok, env = tiny_env
    with obs.scoped(trace=True, trace_dir=str(tmp_path)) as o:
        engine = GenerationEngine(model, params, pad_id=tok.pad_id,
                                  stop_ids=(tok.eos_id,), max_len=256)
        worker = RolloutWorker(engine, env, tok, RolloutConfig(
            max_turns=2, max_new_tokens=8, group_size=2, n_slots=2))
        worker.rollout(env.sample_tasks(2, seed=1), jax.random.PRNGKey(0))
        snap = o.registry.snapshot()
    stats = worker.last_stats
    assert "rollout/gen_s" not in snap
    assert stats["gen_s"] == pytest.approx(snap["rollout/decode_round_s/sum"])
    assert stats["gen_s"] > 0
    (path,) = glob.glob(str(tmp_path / "*.trace.json"))
    evs = json.load(open(path))["traceEvents"]
    tracks = {e["tid"]: e["args"]["name"] for e in evs if e["ph"] == "M"}
    rollout = [e for e in evs if e["ph"] == "X"
               and tracks[e["tid"]] == "rollout"]
    names = {e["name"] for e in rollout}
    assert {"round", "refill", "decode", "parse"} <= names
    n_rounds = stats["rounds"]
    assert sum(e["name"] == "decode" for e in rollout) == n_rounds
    # every slot's decode_round is the round's decode span, to the microsecond
    decodes = {(e["ts"], e["dur"]) for e in rollout if e["name"] == "decode"}
    slot_rounds = {(e["ts"], e["dur"]) for e in evs
                   if e["ph"] == "X" and e["name"] == "decode_round"}
    assert slot_rounds <= decodes


def test_learner_step_span_ends_on_ready_metrics(tiny_env, tmp_path,
                                                 monkeypatch):
    from repro.core import trainer as trainer_mod
    from repro.core.grpo import GRPOConfig
    from repro.core.rewards import RewardComposer, RuleReward
    from repro.core.rollout import RolloutConfig
    from repro.optim.adamw import AdamWConfig
    model, params, tok, env = tiny_env
    ready = []
    real = jax.block_until_ready

    def spy(x):
        ready.append((obs.current_span(), x))
        return real(x)

    monkeypatch.setattr(trainer_mod.jax, "block_until_ready", spy)
    with obs.scoped(trace=True, trace_dir=str(tmp_path)) as o:
        tr = trainer_mod.RLTrainer(
            model, params, env, tok,
            RewardComposer([(RuleReward(env), 1.0)]),
            trainer_mod.TrainerConfig(n_tasks_per_iter=2, group_size=2,
                                      max_seq_len=256),
            RolloutConfig(max_turns=2, max_new_tokens=8, group_size=2),
            GRPOConfig(), AdamWConfig())
        out = tr.train_iteration(jax.random.PRNGKey(1))
        snap = o.registry.snapshot()
        tracks = {e["tid"]: e["args"]["name"] for e in o.tracer.events()
                  if e["ph"] == "M"}
        learner = [e["name"] for e in o.tracer.events() if e["ph"] == "X"
                   and tracks[e["tid"]] == "learner"]
    steps = [x for where, x in ready if where == "train/step"]
    assert len(steps) == 1 and "loss" in steps[0]
    assert all(np.isfinite(float(v)) for v in steps[0].values())
    assert snap["train/update_s/count"] == 1.0
    assert learner == ["make_batch", "learner_update"]
    assert np.isfinite(out["loss"])
