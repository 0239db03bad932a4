"""The program's own counters and host spans reach the metric readers:
the registry snapshotted at the window's bounds (``program_delta``), and
the slash-named spans of a profile (``Summary.span_s`` and the idle gaps'
names).

One tiny rollout is traced on the CPU with the program patched the way a
configuration's PR would patch it: ``GenerationEngine.generate`` counts
two ``engine/fixture_widgets`` a call, a counter no benchmark code names,
and runs inside a ``fixture/step`` span, of a namespace no code names (a
counter's namespace has to be one the repository's lint knows, which a
configuration's PR registers with its counters).  A reader under
``fixtures/`` reads both with no benchmark code edited.
The CPU profile has no device plane, so the readers' record takes the
program spans of its host plane; the idle gaps come from the committed
small trace."""
import json
import math
import warnings
from pathlib import Path

import pytest

from tiny import tiny_cell

import harness as H  # noqa: E402  (tiny puts the benchmark on the path)
import rollout  # noqa: E402
import trace_reduce as TR  # noqa: E402

from repro import obs
from repro.serving.engine import GenerationEngine

HERE = Path(__file__).resolve().parent
NEW = ("setup_compile_s", "engine_host_idle_share", "prefix_hit_share")
SEED = 2**31 + 77
WIDGETS = 2                      # counted by each generate call


def per_layer(*names) -> list:
    bench = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    found = {m["name"]: m for m in bench["per_layer"]}
    return [found[n] for n in names]


def small_trace() -> TR.Summary:
    from jax.profiler import ProfileData
    text = (HERE / "fixtures" / "small_trace.textproto").read_text()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return TR._summarize(ProfileData.from_text_proto(text), 1)


def record(counts: dict, trace=None, **kw) -> H.Record:
    fields = dict(cell="qwen3-4b.search-rollout", config={}, traffic={},
                  device={}, peak={}, setup_s=100.0, window_s=50.0,
                  counts=counts, trace=trace)
    fields.update(kw)
    return H.Record(**fields)


HAND = {"rounds": 4,
        "program_open": {"compile/trace_s": 1.5, "compile/lower_s": 2.25,
                         "compile/backend_s": 40.0,
                         "compile/cache_load_s": 6.25,
                         "engine/prefill_tokens": 100.0,
                         "engine/shared_prefix_tokens": 20.0},
        "program_close": {"compile/trace_s": 1.5, "compile/lower_s": 2.25,
                          "compile/backend_s": 40.0,
                          "compile/cache_load_s": 6.25,
                          "engine/prefill_tokens": 400.0,
                          "engine/shared_prefix_tokens": 110.0}}


@pytest.mark.parametrize("name,value", [
    # the four compile counters at the opening
    ("setup_compile_s", 50.0),
    # the small trace's gaps under engine/alloc (5 us) and
    # engine/generate (0.5 us), not engine.generate's, over 10 us
    ("engine_host_idle_share", 55.0),
    # (110 - 20) shared of (400 - 100) fed in the window
    ("prefix_hit_share", 30.0)])
def test_new_readers_on_a_hand_made_record(name, value):
    got = H.read_metrics(per_layer(name), record(HAND, small_trace()))
    assert got[name]["value"] == pytest.approx(value)
    assert math.isfinite(got[name]["value"])


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_without_the_program(name):
    # no snapshots and no trace (a run from before the snapshots, or an
    # untraced one): the metric is left out, never read as 0
    assert H.read_metrics(per_layer(name), record({"rounds": 4})) == {}


def test_program_delta_is_close_less_open():
    rec = record(HAND)
    assert H.program_delta(rec, "engine/prefill_tokens") == 300.0
    assert H.program_delta(rec, "compile/backend_s") == 0.0
    assert H.program_delta(rec, "moe/expert_tokens") == 0.0
    assert H.program_delta(record({}), "engine/prefill_tokens") == 0.0


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A traced tiny rollout with the patched program, and the tokens the
    scheduler handed to ``extend_rows`` inside the window."""
    fed = {"window": 0}
    generate = GenerationEngine.generate
    extend_rows = rollout.EngineProxy.extend_rows

    def counted_generate(self, *a, **kw):
        obs.get().registry.counter("engine/fixture_widgets").add(WIDGETS)
        with obs.span("fixture/step"):
            return generate(self, *a, **kw)

    def spied_extend_rows(self, session, rows, token_lists):
        if self._d.in_window:
            fed["window"] += sum(len(t) for t in token_lists)
        return extend_rows(self, session, rows, token_lists)

    cell = tiny_cell("qwen3-4b.search-rollout")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(H, "OUT_DIR", tmp_path_factory.mktemp("trace"))
        mp.setattr(GenerationEngine, "generate", counted_generate)
        mp.setattr(rollout.EngineProxy, "extend_rows", spied_extend_rows)
        out = rollout.run(cell, SEED, 1.0, True, 0.0)
    from jax.profiler import ProfileData
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        pd = ProfileData.from_file(TR.xplane_file(out["trace_dir"]))
    (w0, w1), spans = TR.host_spans(pd)
    trace = TR.Summary(window_s=(w1 - w0) * 1e-9, busy_s=0.0, op_s={},
                       module_s={}, gaps=[], n_devices=0,
                       span_s=TR.span_seconds(spans, w0, w1))
    rec = record(out["counts"], trace, setup_s=out["setup_s"],
                 window_s=out["window_s"])
    return rec, fed["window"]


def test_program_delta_leaves_out_the_warm_up(traced_run):
    rec, fed = traced_run
    c = rec.counts
    # the engine's own count of prefill tokens moved by exactly what the
    # window handed it, though the warm-up had counted more before
    assert H.program_delta(rec, "engine/prefill_tokens") == fed > 0
    assert c["program_open"]["engine/prefill_tokens"] > 0
    # the program's compile listener agrees with the benchmark's on what
    # the window compiled or loaded
    assert (H.program_delta(rec, "compile/programs")
            == c["compiles"] + c["cache_loads"])
    # one generate call a round in the window; the warm-up's calls and the
    # rounds before the opening are left out
    assert (H.program_delta(rec, "engine/fixture_widgets")
            == WIDGETS * c["rounds"])
    assert c["program_open"]["engine/fixture_widgets"] > 0


def test_a_new_namespace_reaches_a_reader(traced_run):
    rec, _ = traced_run
    span_s = rec.trace.span_s
    assert 0 < span_s["fixture/step"] <= rec.trace.window_s
    # the program's own spans come through by their bare names (their
    # arguments stripped), benchmark spans and Python-tracer events not
    assert {"engine/generate", "engine/extend", "rollout/round"} <= set(span_s)
    assert all(TR.PROGRAM_SPAN.fullmatch(k) for k in span_s)
    reader = H._load(HERE / "fixtures" / "widget_reader.py")
    value = reader.read(rec)
    assert value == pytest.approx(
        1000.0 * span_s["fixture/step"]
        / (WIDGETS * rec.counts["rounds"]))
    assert math.isfinite(value) and value > 0


def test_program_counter_readers_on_the_tiny_run(traced_run):
    rec, _ = traced_run
    got = H.read_metrics(per_layer("setup_compile_s", "prefix_hit_share"),
                         rec)
    assert math.isfinite(got["setup_compile_s"]["value"])
    assert got["setup_compile_s"]["value"] > 0
    # groups of 2 share their prompt: some tokens, never all
    assert 0 < got["prefix_hit_share"]["value"] < 100
