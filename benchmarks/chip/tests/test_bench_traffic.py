"""The traffic generator: deterministic per seed, within its stated
ranges, the same set of sizes for every seed."""
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import traffic as T  # noqa: E402

CFG = json.loads((HERE / "configs" / "qwen3-4b-l2.json").read_text())
BIG = 2**31 + 12345


def test_rollout_plan_is_deterministic_and_in_range():
    mix = T.load("search-rollout")
    a, b = T.rollout_plan(mix, BIG), T.rollout_plan(mix, BIG)
    assert a == b
    assert a != T.rollout_plan(mix, BIG + 1)
    n_jobs = mix["tasks"] * mix["group_size"]
    assert len(a["calls"]) == n_jobs and len(a["questions"]) == mix["tasks"]
    c_lo, c_hi = mix["calls_per_trajectory"]
    assert set(a["calls"]) == set(range(c_lo, c_hi + 1))
    assert c_hi <= mix["max_tool_calls"] < mix["max_turns"]
    o_lo, o_hi = mix["observation_tokens"]
    for j in range(n_jobs):
        assert len(a["obs_bytes"][j]) == a["calls"][j]
        for n in a["obs_bytes"][j]:
            assert o_lo <= n + 2 <= o_hi     # + the two response tags
        assert all(x > 0 for x in a["latency_s"][j])
    q_lo, q_hi = mix["question_bytes"]
    assert all(q_lo <= len(q) <= q_hi for q in a["questions"])


def test_rollout_sizes_are_the_same_set_for_every_seed():
    mix = T.load("search-rollout")
    a, b = T.rollout_plan(mix, 1), T.rollout_plan(mix, BIG)
    assert sorted(a["calls"]) == sorted(b["calls"])
    flat = lambda p, k: sorted(x for row in p[k] for x in row)  # noqa: E731
    assert flat(a, "obs_bytes") == flat(b, "obs_bytes")
    assert np.allclose(flat(a, "latency_s"), flat(b, "latency_s"))
    # the lognormal's median is the stated one
    assert np.median(flat(a, "latency_s")) == \
        __import__("pytest").approx(mix["tool_latency"]["median_s"], rel=0.02)


def test_observation_text_has_its_length():
    for n in (126, 200, 382):
        t = T.observation_text(BIG, 5, 1, n)
        assert len(t.encode()) == n
    assert T.observation_text(7, 1, 0, 150) == T.observation_text(7, 1, 0,
                                                                  150)


def test_reason_plan_has_no_calls():
    mix = T.load("reason-rollout")
    p = T.rollout_plan(mix, BIG)
    assert set(p["calls"]) == {0}


def test_learner_batches_deterministic_and_in_range():
    mix = T.load("grpo-step")
    a, b = T.learner_batches(mix, CFG, BIG), T.learner_batches(mix, CFG, BIG)
    assert a == b
    lo, hi = mix["length"]
    assert len(a) == mix["batches"]
    for group in a:
        assert len(group) == mix["group_size"]
        n_prompt = group[0]["roles"].index(1)
        p_lo, p_hi = mix["prompt_tokens"]
        assert p_lo <= n_prompt <= p_hi
        adv = np.array([t["adv"] for t in group])
        assert abs(adv.mean()) < 1e-5
        for t in group:
            assert lo <= len(t["tokens"]) <= min(hi, mix["max_seq_len"])
            assert t["tokens"][:n_prompt] == group[0]["tokens"][:n_prompt]
            assert all(0 <= x < CFG["vocab_size"] for x in t["tokens"])
            roles = np.array(t["roles"])
            old = np.array(t["old"])
            assert (old[roles != 1] == 0).all() and (old[roles == 1] < 0).all()
            assert set(roles[n_prompt:]) <= {1, 2} and roles[n_prompt] == 1
