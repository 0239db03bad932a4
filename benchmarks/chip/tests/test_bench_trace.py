"""The trace reduction on a small profile committed as a fixture, against
hand-worked values."""
import sys
import warnings
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import trace_reduce as TR  # noqa: E402


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    text = (HERE / "fixtures" / "small_trace.textproto").read_text()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return TR._summarize(ProfileData.from_text_proto(text), 1)


def test_window_and_busy(summary):
    # window 0 .. 10 us; ops (shifted by the line's 1 us) cover
    # [1, 3] u [2.5, 3.5] u [4, 5] u [10.5, ...) clipped to the window
    assert summary.window_s == pytest.approx(10e-6)
    assert summary.busy_s == pytest.approx((2.5 + 1.0) * 1e-6)
    assert 1 - summary.busy_s / summary.window_s == pytest.approx(0.65)


def test_nested_ops_count_their_own_time():
    # a loop [0, 10] holding two body ops [1, 3] and [4, 5]
    ev = [("%while.1", 0, 10_000), ("%a", 1_000, 3_000), ("%b", 4_000, 5_000)]
    st = TR._self_times(ev)
    assert st == pytest.approx({"%while.1": 7e-6, "%a": 2e-6, "%b": 1e-6})
    assert TR.op_name("%fusion.3 = bf16[8] fusion(%x)") == "%fusion.3"


def test_op_and_program_times(summary):
    assert summary.op_time("paged_attention") == pytest.approx(2e-6)
    assert summary.op_s["fusion.1"] == pytest.approx(2e-6)
    assert "fusion.2" not in summary.op_s          # outside the window
    assert summary.module_time("decode_loop") == pytest.approx(4e-6)
    assert summary.module_time("prefill") == 0.0


def test_idle_gaps_named_by_open_host_span(summary):
    # gaps: [0,1] mid 0.5 (engine.generate opens at 0.5: open, the
    # program's engine/generate at 0.6: not yet) -> engine.generate;
    # [3.5,4] mid 3.75 -> engine/generate, inside engine.generate; [5,10]
    # mid 7.5 -> engine/alloc, inside engine/extend inside
    # engine.extend_rows (the Python-tracer event around 7.5 is no span)
    gaps = dict((round(s * 1e6, 6), n) for n, s in summary.gaps)
    assert gaps == {1.0: "engine.generate", 0.5: "engine/generate",
                    5.0: "engine/alloc"}
    b = TR.breakdown(summary)
    assert b["idle_gaps"][0] == ["engine/alloc", pytest.approx(5e-6)]
    assert [k for k, _ in b["device_ops"]][:1] in (["paged_attention"],
                                                   ["fusion.1"])


def test_program_span_seconds(summary):
    # every slash-namespaced host event, whatever its namespace, clipped
    # to the window and summed over occurrences; engine/alloc's seconds
    # also lie inside engine/extend's; the benchmark's spans and the
    # Python tracer's events are not program spans
    assert summary.span_s == pytest.approx({
        "engine/generate": 3.8e-6, "fixture/probe": 0.2e-6,
        "engine/extend": 3.2e-6, "engine/alloc": 1.5e-6,
        "rollout/round": 0.1e-6})


@pytest.mark.parametrize("point,name", [
    (5, "b"), (15, "a"), (25, "c"), (45, "host"), (30, "c")])
def test_innermost_open_span(point, name):
    # a [0, 30] holding b [2, 10]; c [20, 30] shorter than a; nothing at 45
    spans = [(0, 30, "a"), (2, 10, "b"), (20, 30, "c")]
    assert TR.innermost(spans, [point]) == [name]
