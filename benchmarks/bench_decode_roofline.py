"""Decode hot path vs the roofline: prefill TFLOP/s and AR-step GB/s.

Four ablation cells over the paged engine on the tiny model —
{kernel, gather} x {fp32, int8} — each measured on the same prompts:

  prefill   wall ms for one ``start`` over B long prompts, scored as
            achieved model TFLOP/s (2 * params * tokens matmul proxy)
            against the chip's bf16 peak (launch/hlo_stats.py ``PEAKS``).
  AR step   wall ms per decode step over ``DECODE_STEPS`` steps, scored
            as achieved GB/s (weights + live KV bytes touched per step —
            decode is memory-bound, so this is the roofline axis that
            matters) against the chip's HBM bandwidth.

The peaks are looked up by the device's ``device_kind``; a TPU with no
entry raises.  On the CPU there is no roofline: the Pallas kernel runs in
interpret mode, kernel-cell timings measure the interpreter, and the
roofline fractions are null.  The JSON records the platform and device kind.
Only the accuracy and capacity ablations mean anything on the CPU.

int8 ablation extras: KV pool capacity ratio (bytes fp / bytes int8) and
max |log-softmax| drift of the prefill logits vs the fp gather oracle.

Writes ``results/BENCH_decode_roofline.json``.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.tokenizer import default_tokenizer
from repro.launch.hlo_stats import peaks
from repro.models import Model
from repro.serving.engine import GenerationEngine

BATCH = 4
PROMPT_TOKENS = 128
DECODE_STEPS = 32
MAX_LEN = 256
PAGE_SIZE = 16

CELLS = (
    ("gather_fp", dict(paged_kernel=False)),
    ("kernel_fp", dict(paged_kernel=True)),
    ("gather_int8", dict(paged_kernel=False, kv_cache_dtype="int8")),
    ("kernel_int8", dict(paged_kernel=True, kv_cache_dtype="int8")),
)


def _tree_bytes(tree, pred=lambda path, arr: True) -> int:
    tot = 0
    for path, arr in jax.tree_util.tree_leaves_with_path(tree):
        if hasattr(arr, "dtype") and pred(path, arr):
            tot += arr.size * arr.dtype.itemsize
    return tot


def _kv_pool_bytes(cache) -> int:
    """Bytes of the K/V block pools themselves (scales excluded)."""
    def is_pool(path, arr):
        name = str(path[-1])
        return (any(k in name for k in ("'k'", "'v'", "ckv", "krope"))
                and "scale" not in name)
    return _tree_bytes(cache, is_pool)


def _measure_cell(model, params, tok, prompts, n_params, peak, **engine_kw):
    eng = GenerationEngine(model, params, pad_id=tok.pad_id,
                           stop_ids=(), max_len=MAX_LEN, temperature=1.0,
                           cache_mode="paged", page_size=PAGE_SIZE,
                           **engine_kw)
    rk = jax.random.split(jax.random.PRNGKey(3), len(prompts))

    # warm the prefill + decode jits on a throwaway session
    s = eng.start([list(p) for p in prompts])
    jax.block_until_ready(s.last_logits)
    r = eng.generate(s, 2, row_keys=rk)
    jax.block_until_ready(r.tokens)

    t0 = time.monotonic()
    session = eng.start([list(p) for p in prompts])
    jax.block_until_ready(session.last_logits)
    prefill_s = time.monotonic() - t0
    prefill_logits = np.asarray(
        jax.nn.log_softmax(session.last_logits, axis=-1))

    t0 = time.monotonic()
    res = eng.generate(session, DECODE_STEPS, row_keys=rk)
    jax.block_until_ready(res.tokens)
    decode_s = time.monotonic() - t0

    total_prompt = sum(len(p) for p in prompts)
    prefill_flops = 2.0 * n_params * total_prompt        # matmul proxy
    # decode is memory-bound: per step the weights stream once and every
    # live KV byte is read by attention
    kv_bytes = _kv_pool_bytes(session.cache)
    live_frac = min(1.0, float(np.sum(session.lengths))
                    / (len(prompts) * MAX_LEN))
    param_bytes = _tree_bytes(params)
    step_bytes = param_bytes + kv_bytes * live_frac
    step_s = decode_s / DECODE_STEPS

    return {
        "prefill_ms": prefill_s * 1e3,
        "prefill_tflops_per_s": prefill_flops / prefill_s / 1e12,
        "prefill_roofline_frac": (prefill_flops / prefill_s
                                  / peak["bf16_flops"] if peak else None),
        "ar_step_ms": step_s * 1e3,
        "ar_step_gb_per_s": step_bytes / step_s / 1e9,
        "ar_step_roofline_frac": (step_bytes / step_s / peak["hbm_bw"]
                                  if peak else None),
        "kv_pool_bytes": kv_bytes,
        "kernel_in_use": bool(eng._use_paged_kernel),
    }, prefill_logits


def run():
    cfg = get_config("tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = default_tokenizer(cfg.vocab_size)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    base = tok.encode("roofline probe prompt " * 12)
    prompts = [list(base[:PROMPT_TOKENS - i]) for i in range(BATCH)]

    dev = jax.devices()[0]
    peak = peaks(dev.device_kind) if dev.platform == "tpu" else None
    out = {
        "backend": dev.platform,
        "roofline_meaningful": peak is not None,
        "hardware": {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices()), "peaks": peak},
        "config": {"batch": BATCH, "prompt_tokens": PROMPT_TOKENS,
                   "decode_steps": DECODE_STEPS, "max_len": MAX_LEN,
                   "page_size": PAGE_SIZE, "model": "tiny",
                   "n_params": int(n_params)},
        "cells": {},
    }
    logits = {}
    for name, kw in CELLS:
        if peak is not None and kw.get("paged_kernel") is False:
            continue                # on TPU the gather is not a serving path
        out["cells"][name], logits[name] = _measure_cell(
            model, params, tok, prompts, n_params, peak, **kw)

    # the fp gather is the oracle on the CPU; on TPU, where the gather is not
    # a serving path, the fp kernel is
    oracle_name = "gather_fp" if "gather_fp" in logits else "kernel_fp"
    for name in logits:
        if name != oracle_name:
            out["cells"][name]["prefill_logit_maxdiff_vs_fp_oracle"] = float(
                np.max(np.abs(logits[name] - logits[oracle_name])))

    cells = out["cells"]
    out["ablations"] = {
        "int8_kv_capacity_ratio": (cells["kernel_fp"]["kv_pool_bytes"]
                                   / cells["kernel_int8"]["kv_pool_bytes"]),
        "int8_logit_maxdiff":
            cells["kernel_int8"]["prefill_logit_maxdiff_vs_fp_oracle"],
    }
    if "gather_fp" in cells:
        out["ablations"]["kernel_vs_gather_ar_step_ratio"] = (
            cells["gather_fp"]["ar_step_ms"]
            / cells["kernel_fp"]["ar_step_ms"])
        out["ablations"]["kernel_fp_logit_maxdiff"] = (
            cells["kernel_fp"]["prefill_logit_maxdiff_vs_fp_oracle"])
    return out


def main():
    r = run()
    os.makedirs("results", exist_ok=True)
    with open("results/BENCH_decode_roofline.json", "w") as f:
        json.dump(r, f, indent=2)
    rows = []
    tag = "" if r["roofline_meaningful"] else " (interpret/CPU: ratios only)"
    for name, m in r["cells"].items():
        print(f"bench_decode_roofline,{name},prefill={m['prefill_ms']:.1f}ms"
              f"@{m['prefill_tflops_per_s']:.3f}TF/s,"
              f"ar_step={m['ar_step_ms']:.2f}ms"
              f"@{m['ar_step_gb_per_s']:.2f}GB/s,"
              f"kernel={m['kernel_in_use']}{tag}")
        rows.append((f"decode_roofline_{name}", m["ar_step_ms"] * 1e3,
                     f"gbps={m['ar_step_gb_per_s']:.2f}"))
    a = r["ablations"]
    print("bench_decode_roofline,ablations," + ",".join(
        f"{k}={v:.3g}" for k, v in a.items()))
    rows.append(("decode_roofline_int8_capacity", 0.0,
                 f"{a['int8_kv_capacity_ratio']:.1f}x_kv_on_same_hbm"))
    return rows


if __name__ == "__main__":
    main()
