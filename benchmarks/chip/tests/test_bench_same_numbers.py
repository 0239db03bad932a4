"""The tiny cells read the compared numbers bit for bit as they did before
the layout modules, for seeds 0 and 1 (values recorded from the benchmark
as it was then).

A rollout run is made repeatable here: every tool call in flight finishes
before the scheduler drains, and the driver's clock advances by one second
a round, so that the window is 8 whole rounds whatever the host's speed.
The learner's numbers come from its first steps, before its window."""
import concurrent.futures
import types

import pytest

from tiny import tiny_cell

import learner  # noqa: E402  (tiny puts the benchmark on the path)
import rollout  # noqa: E402

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
    monkeypatch.setattr(rollout, "time", types.SimpleNamespace(
        perf_counter=lambda: clock.t))
    start = rollout.Driver.round_start

    def round_start(self):
        clock.t += 1.0
        return start(self)

    monkeypatch.setattr(rollout.Driver, "round_start", round_start)
    monkeypatch.setattr(rollout, "AsyncToolExecutor", SettledExecutor)


@pytest.mark.parametrize("workload,seed", sorted(RECORDED))
def test_numbers_are_as_recorded(workload, seed, round_clock):
    cell = tiny_cell(workload)
    if cell.traffic["entry"] == "rollout":
        out = rollout.run(cell, seed, float(ROUNDS), False, 0.0)
    else:
        out = learner.run(cell, seed, 0.5, False, 0.0)
    assert out["numbers"] == RECORDED[(workload, seed)]
