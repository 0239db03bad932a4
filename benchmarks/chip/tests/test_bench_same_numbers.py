"""The tiny cells read the compared numbers bit for bit as they did before
the layout modules, for seeds 0 and 1 (values recorded from the benchmark
as it was then); and every metric reader that the benchmark had before
its readers could see the program's counters and spans reads, on the
same record, what it read then.

A rollout run is made repeatable here: every tool call in flight finishes
before the scheduler drains, and the driver's clock advances by one second
a round, so that the window is 8 whole rounds whatever the host's speed
(the benchmark's host spans read no time).  The learner's numbers come
from its first steps, before its window, which a clock of a quarter
second a reading makes one step long.  The readers' record adds to each
run the committed small trace and a TPU v5e's peaks."""
import concurrent.futures
import types
import warnings
from pathlib import Path

import pytest

from tiny import tiny_cell

import harness as H  # noqa: E402  (tiny puts the benchmark on the path)
import learner  # noqa: E402
import peaks  # noqa: E402
import rollout  # noqa: E402
import trace_reduce  # noqa: E402

ROUNDS = 8

RECORDED = {
    ("qwen3-4b.search-rollout", 0): {
        "served_gap": 0.0, "logprob_gap": 0.005183219909667969,
        "value_cache_gap": 0.004057593643665314, "context_mismatch": 0.0},
    ("qwen3-4b.search-rollout", 1): {
        "served_gap": 0.0, "logprob_gap": 0.002788543701171875,
        "value_cache_gap": 0.004005535040050745, "context_mismatch": 0.0},
    ("qwen3-4b-l2.grpo-step", 0): {
        "loss_gap": 0.0006287983383837133, "grad_gap": 0.012807850637623769,
        "change_gap": 0.007889438582437852},
    ("qwen3-4b-l2.grpo-step", 1): {
        "loss_gap": 0.0004224035326235327, "grad_gap": 0.011218219445106615,
        "change_gap": 0.00756904775003452},
}


class SettledExecutor(rollout.AsyncToolExecutor):
    """Every call in flight has finished when the scheduler drains."""

    def drain_ready(self, futures=None):
        with self._inflight_lock:
            sel = [f for f in self._inflight
                   if futures is None or f in futures]
        concurrent.futures.wait(sel)
        return super().drain_ready(futures)

    def wait_ready(self, timeout=None, futures=None):
        return self.drain_ready(futures)


@pytest.fixture
def round_clock(monkeypatch):
    clock = types.SimpleNamespace(t=0.0)
    now = types.SimpleNamespace(perf_counter=lambda: clock.t)
    monkeypatch.setattr(rollout, "time", now)
    monkeypatch.setattr(H, "time", now)
    start = rollout.Driver.round_start

    def round_start(self):
        clock.t += 1.0
        return start(self)

    monkeypatch.setattr(rollout.Driver, "round_start", round_start)
    monkeypatch.setattr(rollout, "AsyncToolExecutor", SettledExecutor)
    steps = types.SimpleNamespace(t=0.0)

    def quarter_seconds():
        steps.t += 0.25
        return steps.t

    monkeypatch.setattr(learner, "time", types.SimpleNamespace(
        perf_counter=quarter_seconds))


_RUNS: dict = {}


def run_once(workload: str, seed: int) -> tuple:
    """The tiny cell and its run's output, once per module for each
    (workload, seed); needs ``round_clock``."""
    if (workload, seed) not in _RUNS:
        cell = tiny_cell(workload)
        if cell.traffic["entry"] == "rollout":
            out = rollout.run(cell, seed, float(ROUNDS), False, 0.0)
        else:
            out = learner.run(cell, seed, 0.5, False, 0.0)
        _RUNS[(workload, seed)] = cell, out
    return _RUNS[(workload, seed)]


@pytest.mark.parametrize("workload,seed", sorted(RECORDED))
def test_numbers_are_as_recorded(workload, seed, round_clock):
    _, out = run_once(workload, seed)
    assert out["numbers"] == RECORDED[(workload, seed)]


# the readers the benchmark had before its readers could see the
# program's counters and spans
READERS = ("decode_ms_per_step", "device_idle_share.learner",
           "device_idle_share.rollout", "learner_mfu", "learner_tokens_per_s",
           "paged_attention_roofline", "peak_hbm_gb.learner",
           "peak_hbm_gb.rollout", "prefill_device_share", "rollout_mfu",
           "rollout_tokens_per_s", "scheduler_host_ms_per_round", "setup_s",
           "slot_occupancy")

# what each reader read on the benchmark as it was then (a reader that
# finds nothing to read is left out)
READ = {
    ("qwen3-4b.search-rollout", 0): {
        "decode_ms_per_step": 3.125000000000001e-05,
        "device_idle_share.rollout": 64.99999999999999,
        "paged_attention_roofline": 2414.636385836385,
        "rollout_mfu": 5.681695837563452e-05, "rollout_tokens_per_s": 61.375,
        "scheduler_host_ms_per_round": 1000.0, "setup_s": 3.0,
        "slot_occupancy": 100.0},
    ("qwen3-4b.search-rollout", 1): {
        "decode_ms_per_step": 3.125000000000001e-05,
        "device_idle_share.rollout": 64.99999999999999,
        "paged_attention_roofline": 2331.3035409035406,
        "rollout_mfu": 5.67866152284264e-05, "rollout_tokens_per_s": 60.25,
        "scheduler_host_ms_per_round": 1000.0, "setup_s": 4.0,
        "slot_occupancy": 100.0},
    ("qwen3-4b-l2.grpo-step", 0): {
        "device_idle_share.learner": 64.99999999999999,
        "learner_mfu": 7.881473299492385e-05,
        "learner_tokens_per_s": 230.66666666666666, "setup_s": 0.5},
    ("qwen3-4b-l2.grpo-step", 1): {
        "device_idle_share.learner": 64.99999999999999,
        "learner_mfu": 9.580377664974619e-05,
        "learner_tokens_per_s": 277.3333333333333, "setup_s": 0.5},
}

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def small_trace():
    from jax.profiler import ProfileData
    text = (Path(__file__).parent / "fixtures" /
            "small_trace.textproto").read_text()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return trace_reduce._summarize(ProfileData.from_text_proto(text), 1)


def reader_values(cell, out) -> dict:
    """Every reader of ``READERS`` on the run's record."""
    rec = H.Record(cell=cell.name, config=cell.config, traffic=cell.traffic,
                   device=DEVICE, peak=peaks.peaks(DEVICE["kind"]),
                   setup_s=out["setup_s"], window_s=out["window_s"],
                   counts=out["counts"], trace=small_trace(),
                   memory_peak_bytes=out["memory_peak_bytes"])
    return {k: v["value"] for k, v in H.read_metrics(
        [{"name": n, "unit": "-"} for n in READERS], rec).items()}


@pytest.mark.parametrize("workload,seed", sorted(RECORDED))
def test_readers_read_as_recorded(workload, seed, round_clock):
    assert reader_values(*run_once(workload, seed)) == READ[(workload, seed)]
