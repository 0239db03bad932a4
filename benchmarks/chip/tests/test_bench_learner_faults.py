"""A learner run at a tiny size on the CPU, past the chip check: sound, it
is correct; with the step broken underneath (its state returned unchanged,
or half of the batch left out with the mean taken over the rest), or with
the program's bf16-parameter path switched on (the control), ``correct``
reads false."""
import pytest

from tiny import run_tiny

CELL = "qwen3-4b-l2.grpo-step"


def test_sound_run_is_correct():
    result = run_tiny(CELL, seed=2**31 + 9)
    assert result["correct"], result["checks"]


def state_unchanged(learner):
    step = learner._train_step

    def stale(params, opt_state, batch):
        _, opt_state, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics

    learner._train_step = stale


def half_batch(learner):
    step = learner._train_step

    def half(params, opt_state, batch):
        n = batch["tokens"].shape[0] // 2
        return step(params, opt_state, {k: v[:n] for k, v in batch.items()})

    learner._train_step = half


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_broken_step_is_not_correct(fault):
    result = run_tiny(CELL, seed=31, fault=fault)
    assert not result["correct"], result["checks"]


def test_control_bf16_params_is_not_correct():
    result = run_tiny(CELL, seed=32, config_over={"param_dtype": "bfloat16"})
    assert not result["correct"], result["checks"]
