"""A rollout run at a tiny size on the CPU, past the chip check: sound, it
is correct, and the control (the reference with every product in fp8 put
in the program's place) fails the same limits, as does the program with
its own int8 K/V pool switched on; with the timed path broken underneath,
``correct`` reads false.  Faults: a decode step that returns its cache
unchanged, and a token altered where it is produced."""
import dataclasses
import time

import numpy as np
import pytest

from tiny import run_tiny, tiny_cell

import harness as H  # noqa: E402  (tiny puts the benchmark on the path)
import rollout  # noqa: E402


def test_sound_run_is_correct_and_the_control_is_not():
    cell = tiny_cell("qwen3-4b.search-rollout")
    out = rollout.run(cell, 2**31 + 5, 2.0, False, time.perf_counter(),
                      control=True)
    _, ok = H.judge(out["numbers"], cell.limits)
    assert ok, out["numbers"]
    assert out["attempted"] > 0 and out["failed"] == 0
    _, ctl_ok = H.judge(out["extra"]["control_fp8"], cell.limits)
    assert not ctl_ok, out["extra"]


def test_int8_kv_pool_is_not_correct():
    cell = tiny_cell("qwen3-4b.search-rollout")
    out = rollout.run(cell, 2**31 + 5, 2.0, False, time.perf_counter(),
                      kv_dtype="int8")
    checks, ok = H.judge(out["numbers"], cell.limits)
    assert not ok, checks


def stale_decode(engine):
    """The decode step returns the cache it was given."""
    model = engine.model
    orig = model.decode_step

    def step(params, tokens, positions, caches, **kw):
        logits, new = orig(params, tokens, positions, caches, **kw)
        return (logits, caches) if tokens.shape[1] == 1 else (logits, new)

    model.decode_step = step
    return engine


class AlteredToken:
    """Each round's first sampled token of every row is replaced."""

    def __init__(self, engine):
        self._e = engine

    def __getattr__(self, name):
        return getattr(self._e, name)

    def __setattr__(self, name, value):
        if name == "_e":
            object.__setattr__(self, name, value)
        else:
            setattr(self._e, name, value)

    def generate(self, session, *a, **kw):
        res = self._e.generate(session, *a, **kw)
        toks = np.array(res.tokens)
        hit = np.asarray(res.counts) > 0
        toks[hit, 0] = (toks[hit, 0] + 1) % self._e.model.cfg.vocab_size
        return dataclasses.replace(res, tokens=toks)


@pytest.mark.parametrize("fault", [stale_decode, AlteredToken],
                         ids=["state_unchanged", "token_altered"])
def test_broken_path_is_not_correct(fault):
    result = run_tiny("qwen3-4b.search-rollout", seed=77, fault=fault)
    assert not result["correct"], result["checks"]
