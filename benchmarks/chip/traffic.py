"""The one generator of traffic: it reads a mix's data file
(``traffic/<mix>.json``) and draws the cell's inputs from ``--seed``.

Sizes are stratified: a mix draws the same set of lengths, call counts and
latencies for every seed (quantiles of the stated distribution) and the
seed only shuffles which trajectory gets which.  Runs on different seeds
then differ in order, not in the amount of work.

Two kinds of mix, named by ``entry``:

* ``rollout``: tasks for ``RolloutWorker.rollout_stream``.  Each trajectory
  (job ``j`` = task * group_size + member, the scheduler's own numbering)
  gets a number of tool calls and, per call, an observation length and a
  tool latency.
* ``learner``: GRPO batches of trajectories made of a shared prompt span,
  then model spans (in the loss) alternating with observation spans.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

WORDS = ("river", "capital", "founded", "empire", "museum", "harbor",
         "treaty", "mountain", "census", "railway", "dynasty", "festival",
         "library", "province", "glacier", "senate", "bridge", "island",
         "desert", "cathedral", "orchestra", "charter", "frontier", "canal")


def load(mix: str) -> dict:
    return json.loads((HERE / "traffic" / f"{mix}.json").read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def stratified_ints(lo: int, hi: int, n: int, rng) -> np.ndarray:
    """n whole numbers spread evenly over [lo, hi], in a seeded order."""
    vals = np.floor(lo + (hi - lo + 1) * (np.arange(n) + 0.5) / n)
    return rng.permutation(np.minimum(vals, hi).astype(np.int64))


def stratified_lognormal(median: float, sigma: float, n: int, rng):
    """n quantiles of a lognormal(median, sigma), in a seeded order."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return rng.permutation(median * np.exp(sigma * z))


def stratified_normal(n: int, rng) -> np.ndarray:
    nd = statistics.NormalDist()
    return rng.permutation(np.array([nd.inv_cdf((i + 0.5) / n)
                                     for i in range(n)]))


def words(rng, n_bytes: int) -> str:
    """Text of exactly ``n_bytes`` ASCII bytes."""
    out = []
    size = 0
    while size < n_bytes:
        w = WORDS[int(rng.integers(len(WORDS)))]
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes].ljust(n_bytes, ".")


# ------------------------------------------------------------------ rollout
def rollout_plan(mix: dict, seed: int) -> dict:
    """Tasks and per-trajectory tool behaviour of a rollout mix.

    Returns ``questions`` (one per task), and per job ``calls`` (number of
    tool calls before the answer turn), ``obs_bytes`` (per call, the bytes
    of the tool's answer text) and ``latency_s`` (per call)."""
    n_tasks, G = mix["tasks"], mix["group_size"]
    n_jobs = n_tasks * G
    r = rng_for(seed, 1)
    q_lo, q_hi = mix["question_bytes"]
    questions = [words(r, int(n)) for n in
                 stratified_ints(q_lo, q_hi, n_tasks, r)]
    c_lo, c_hi = mix["calls_per_trajectory"]
    calls = stratified_ints(c_lo, c_hi, n_jobs, r)
    n_calls = int(calls.sum())
    o_lo, o_hi = mix.get("observation_tokens", [0, 0])
    # an observation is <tool_response>text</tool_response>: 2 special
    # tokens around one byte-level token per byte of text
    obs = stratified_ints(o_lo - 2, o_hi - 2, max(n_calls, 1), r)
    lat = mix.get("tool_latency", {"median_s": 0.0, "sigma": 0.0})
    lats = stratified_lognormal(lat["median_s"], lat["sigma"],
                                max(n_calls, 1), r)
    obs_bytes, latency = [], []
    k = 0
    for c in calls:
        obs_bytes.append([int(x) for x in obs[k:k + c]])
        latency.append([float(x) for x in lats[k:k + c]])
        k += int(c)
    return {"questions": questions, "calls": [int(c) for c in calls],
            "obs_bytes": obs_bytes, "latency_s": latency}


def observation_text(seed: int, job: int, call: int, n_bytes: int) -> str:
    """Three retrieved passages of ``n_bytes`` bytes in all."""
    r = rng_for(seed, 1000 + job * 16 + call)
    a = n_bytes // 3
    parts = [words(r, a - 4), words(r, a - 4),
             words(r, n_bytes - 2 * a - 4)]
    return "".join(f"[{i + 1}] {p}" for i, p in enumerate(parts))[:n_bytes]


# ------------------------------------------------------------------ learner
def learner_batches(mix: dict, cfg: dict, seed: int) -> list:
    """``mix["batches"]`` GRPO groups.  Each trajectory is a dict of
    ``tokens``, ``roles`` (0 prompt, 1 model, 2 observation), ``old``
    (recorded logprob of each model token, 0 elsewhere) and ``adv``."""
    r = rng_for(seed, 2)
    G, nb = mix["group_size"], mix["batches"]
    V = cfg["vocab_size"]
    lens = stratified_ints(*mix["length"], G * nb, r)
    plen = stratified_ints(*mix["prompt_tokens"], nb, r)
    rewards = stratified_normal(G * nb, r)
    # recorded logprobs about a random policy's over the vocabulary
    lp_off, lp_std = mix["old_logprob_vs_uniform"]
    lp_mean = -math.log(V) + lp_off
    batches = []
    for b in range(nb):
        prompt = r.integers(0, V, int(plen[b])).tolist()
        rw = rewards[b * G:(b + 1) * G]
        adv = (rw - rw.mean()) / (rw.std() + mix["adv_eps"])
        group = []
        for g in range(G):
            n = int(lens[b * G + g])
            toks, roles = list(prompt), [0] * len(prompt)
            model = True
            while len(toks) < n:
                lo, hi = mix["model_span" if model else "observation_span"]
                span = min(int(r.integers(lo, hi + 1)), n - len(toks))
                toks += r.integers(0, V, span).tolist()
                roles += [1 if model else 2] * span
                model = not model
            roles_a = np.asarray(roles)
            old = np.where(roles_a == 1,
                           r.normal(lp_mean, lp_std, len(toks)), 0.0)
            group.append({"tokens": toks, "roles": roles,
                          "old": old.astype(np.float32).tolist(),
                          "adv": float(adv[g])})
        batches.append(group)
    return batches


def padded(group: list, length: int | None = None) -> dict:
    """A group as right-padded arrays: tokens, mask (1 on model tokens),
    old logprobs, advantages."""
    L = length or max(len(t["tokens"]) for t in group)
    B = len(group)
    out = {"tokens": np.zeros((B, L), np.int32),
           "mask": np.zeros((B, L), np.float32),
           "old": np.zeros((B, L), np.float32),
           "adv": np.array([t["adv"] for t in group], np.float32)}
    for i, t in enumerate(group):
        n = len(t["tokens"])
        out["tokens"][i, :n] = t["tokens"]
        out["mask"][i, :n] = np.asarray(t["roles"]) == 1
        out["old"][i, :n] = t["old"]
    return out


def n_tokens(group: list) -> int:
    return int(sum(len(t["tokens"]) for t in group))


def ceil_to(n: int, step: int) -> int:
    return max(step, int(math.ceil(n / step)) * step)
