import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the sweep compiles on 512 forced host devices; pinning the CPU keeps it off
# any accelerator the host has (a chip belongs to one process at a time)
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run (brief: MULTI-POD DRY-RUN).

For every (arch x input-shape x mesh) combination: build abstract params +
input ShapeDtypeStructs (no allocation), jit the appropriate step function
with explicit in/out shardings, .lower().compile(), and record
memory_analysis / cost_analysis / collective schedule into
results/dryrun/<arch>_<shape>_<mesh>[_<variant>].json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.core.grpo import GRPOConfig, make_grpo_train_step
from repro.distributed.sharding import ShardingRules, use_sharding_rules
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (batch_shardings, cache_shardings,
                                opt_state_shardings, replicated)
from repro.models import Model
from repro.optim.adamw import AdamWConfig
from repro.models.params import tree_map_specs

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")
RESULTS_DIR = os.path.abspath(os.path.join(os.getcwd(), "results", "dryrun"))
# the chip the sweep's meshes stand for (v5e pods); its roofline peaks come
# from hlo_stats.PEAKS
TARGET_KIND = "TPU v5 lite"

# per-(arch,shape) microbatch counts for the gradient-accumulation scan
# (chosen so per-device live activations fit HBM; see EXPERIMENTS.md §Perf)
MICRO_BATCH = {
    "default": 32,
    "mamba2-130m": 256,        # tiny model: bigger microbatches are fine
}


# ----------------------------------------------------------------------------
# named variants for the §Perf hillclimb: each maps to config overrides,
# sharding-rule overrides and/or a microbatch override, applied on top of the
# baseline.  Results land in results/dryrun/*_<variant>.json.
# ----------------------------------------------------------------------------
VARIANTS = {
    "baseline": {},
    # tensor-parallel-only params (no FSDP over data): kills the per-microbatch
    # param all-gathers at the cost of replicated param/opt memory over data
    "tp_only": {"rules": {"embed_p": None}},
    # larger microbatches: fewer accumulation iterations -> fewer param
    # gathers + less per-iter fixed work; more activation memory
    "mb64": {"micro_batch": 64},
    "mb128": {"micro_batch": 128},
    # save matmul outputs instead of full recompute in the remat policy
    "remat_dots": {"cfg": {"remat_policy": "dots"}},
    # bf16 big intermediates in blockwise attention / SSD (halves the
    # bandwidth of the attention/scan working set; accumulation stays f32)
    "bf16_acts": {"cfg": {"accum_dtype": "bfloat16"}},
    # combinations discovered during the hillclimb
    "tp_only_mb64": {"rules": {"embed_p": None}, "micro_batch": 64},
    "bf16_acts_mb64": {"cfg": {"accum_dtype": "bfloat16"}, "micro_batch": 64},
    # sequence-sharded activations over the model axis (prefill): the
    # in/out projections become seq-local; collectives move to the scan/conv
    # boundaries
    "seq_shard": {"rules": {"seq": "model", "ssm_inner": None, "mlp": None,
                            "heads": None, "kv_heads": None}},
    "seq_shard_bf16": {"rules": {"seq": "model", "ssm_inner": None,
                                 "mlp": None, "heads": None,
                                 "kv_heads": None},
                       "cfg": {"accum_dtype": "bfloat16"}},
}


def shape_rules_overrides(shape_name: str, arch: str) -> dict:
    if shape_name == "long_500k":
        # batch=1 cannot shard: spread the ring cache over every axis
        return {"seq": ("pod", "data", "model")}
    if shape_name == "decode_32k":
        # context-parallel decode: the cache seq dim shards over the model
        # axis (kv_heads like 8 cannot split 16 ways; a 32k x large-batch
        # cache replicated over `model` would not fit HBM — §Perf pair 3)
        return {"seq": "model"}
    return {}


# ----------------------------------------------------------------------------
# cost extrapolation (EXPERIMENTS.md §Roofline methodology)
#
# XLA's HloCostAnalysis counts a `while` body ONCE regardless of trip count
# (verified: an 8-trip scan reports 1/8 the unrolled flops).  The deployed
# compile scans over layers (and microbatches), so its cost_analysis numbers
# undercount.  We therefore run small AUX compiles with every loop unrolled
# (cfg.unroll_scans) at depths L in {2,4} (hybrid: groups G in {1,2}) and,
# for training, microbatch counts k in {1,2}, then extrapolate the exactly
# affine cost model  m(L,k) = a + b*L + c*k + d*L*k  to the target (L,k).
# ----------------------------------------------------------------------------
import dataclasses as _dc


def _aux_cfg(cfg, depth_unit: int):
    over = dict(scan_layers=False, unroll_scans=True,
                attn_block_q=2048, attn_block_k=2048)
    if cfg.family == "hybrid":
        over["n_layers"] = cfg.attn_every * depth_unit        # groups
    elif cfg.family == "encdec":
        over["n_layers"] = depth_unit
        over["n_encoder_layers"] = depth_unit
    else:
        over["n_layers"] = depth_unit
    return _dc.replace(cfg, **over)


def _depth_units(cfg):
    """(aux depth units, target depth in the same units)."""
    if cfg.family == "hybrid":
        return (1, 2), cfg.n_layers // cfg.attn_every
    return (2, 4), cfg.n_layers


def _collect_costs(model, shape_name, rules, kind, micro_batch, batch_override):
    """Lower+compile one aux config; return {flops, bytes, coll_bytes, colls}."""
    with use_sharding_rules(rules):
        fn, in_sh, out_sh, args = build_step(
            model, shape_name, rules, "baseline",
            micro_batch_override=micro_batch, batch_override=batch_override)
        compiled = jax.jit(fn, in_shardings=in_sh,
                           out_shardings=out_sh).lower(*args).compile()
    cost = hlo_stats.extract_cost(compiled)
    colls = hlo_stats.collective_bytes(compiled.as_text())
    n_while = hlo_stats.while_trip_counts(compiled.as_text())
    return {
        "flops": cost["flops"],
        "bytes_accessed": cost["bytes_accessed"],
        "coll_bytes": float(sum(v["bytes"] for v in colls.values())),
        "colls": colls,
        "n_while": n_while,
    }


def extrapolate_costs(cfg, shape_name, rules, kind, micro_batch=None) -> dict:
    """Exact-cost extrapolation from unrolled aux compiles."""
    units, target_L = _depth_units(cfg)
    L1, L2 = units
    mb = micro_batch or MICRO_BATCH.get(cfg.arch_id, MICRO_BATCH["default"])
    B_target = INPUT_SHAPES[shape_name]["global_batch"]
    metrics = ("flops", "bytes_accessed", "coll_bytes")

    if kind == "train" and B_target > mb:
        k_target = B_target / mb
        pts = {}
        for L in (L1, L2):
            model_aux = Model(_aux_cfg(cfg, L))
            for k in (1, 2):
                pts[(L, k)] = _collect_costs(model_aux, shape_name, rules,
                                             kind, micro_batch=mb,
                                             batch_override=k * mb)
        out = {}
        for m in metrics:
            m11, m21 = pts[(L1, 1)][m], pts[(L2, 1)][m]
            m12, m22 = pts[(L1, 2)][m], pts[(L2, 2)][m]
            d = (m22 - m21 - m12 + m11) / (L2 - L1)
            c = (m12 - m11) - d * L1
            b = (m21 - m11) / (L2 - L1) - d
            a = m11 - b * L1 - c - d * L1
            out[m] = a + b * target_L + c * k_target + d * target_L * k_target
        out["aux_points"] = {f"L{L}_k{k}": {m: pts[(L, k)][m] for m in metrics}
                             for (L, k) in pts}
        out["n_while_aux"] = max(p["n_while"] for p in pts.values())
        return out

    # depth-only extrapolation (prefill / decode / unaccumulated train)
    pts = {}
    for L in (L1, L2):
        model_aux = Model(_aux_cfg(cfg, L))
        pts[L] = _collect_costs(model_aux, shape_name, rules, kind,
                                micro_batch=0, batch_override=None)
    out = {}
    for m in metrics:
        b = (pts[L2][m] - pts[L1][m]) / (L2 - L1)
        a = pts[L1][m] - b * L1
        out[m] = a + b * target_L
    out["aux_points"] = {f"L{L}": {m: pts[L][m] for m in metrics} for L in pts}
    out["n_while_aux"] = max(p["n_while"] for p in pts.values())
    return out


def _override_batch(specs, B_new: int):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((B_new,) + s.shape[1:], s.dtype), specs)


def build_step(model: Model, shape_name: str, rules: ShardingRules,
               variant: str = "baseline", micro_batch_override=None,
               batch_override=None):
    """Returns (fn, in_shardings, out_shardings, abstract_args)."""
    cfg = model.cfg
    kind = INPUT_SHAPES[shape_name]["kind"]
    specs = model.input_specs(shape_name)
    if batch_override is not None:
        specs = _override_batch(specs, batch_override)
    param_sh = rules.specs_to_shardings(model.specs())
    abstract_params = model.abstract()
    use_flash = variant == "flash"

    if kind == "train":
        if micro_batch_override is not None:
            mb = micro_batch_override
        else:
            mb = MICRO_BATCH.get(cfg.arch_id, MICRO_BATCH["default"])
        grpo_cfg = GRPOConfig(micro_batch=mb, kl_coef=0.001,
                              accum_unroll=cfg.unroll_scans)
        opt_cfg = AdamWConfig(lr=1e-5)
        step = make_grpo_train_step(model, opt_cfg, grpo_cfg,
                                    use_flash=use_flash)
        opt_sh = opt_state_shardings(rules, model)
        batch_sh = batch_shardings(rules, specs)
        opt_struct = {
            "m": tree_map_specs(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                model.specs()),
            "v": tree_map_specs(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                model.specs()),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }

        def fn(params, opt_state, batch):
            return step(params, opt_state, batch)

        in_sh = (param_sh, opt_sh, batch_sh)
        out_sh = (param_sh, opt_sh,
                  jax.tree_util.tree_map(lambda _: replicated(rules),
                                         {k: 0 for k in
                                          ("loss", "pg_loss", "kl", "aux",
                                           "ratio_mean", "clip_frac",
                                           "entropy_proxy", "grad_norm",
                                           "lr")}))
        args = (abstract_params, opt_struct, specs)
        return fn, in_sh, out_sh, args

    if kind == "prefill":
        batch_sh = batch_shardings(rules, specs)

        def fn(params, batch):
            # serving prefill: only the final position's logits are needed
            logits, aux, _ = model.apply(params, batch, use_flash=use_flash,
                                         last_token_only=True)
            return logits

        logits_sh = rules.sharding(("batch", "seq", "vocab"),
                                   (1, 1, 1))  # shape-indep pspec
        from jax.sharding import NamedSharding, PartitionSpec as P
        logits_sh = NamedSharding(rules.mesh,
                                  rules.pspec(("batch", None, "vocab"),
                                              (INPUT_SHAPES[shape_name]["global_batch"],
                                               1, cfg.vocab_size)))
        return fn, (param_sh, batch_sh), logits_sh, (abstract_params, specs)

    # ---- decode
    window = model.decode_window(shape_name)
    batch_sh = batch_shardings(rules, specs)
    cache_sh = batch_sh.pop("cache")
    cross_sh = batch_sh.pop("cross_kv", None)

    def fn(params, tokens, positions, cache, cross_kv=None):
        kw = {"cross_kv": cross_kv} if cfg.family == "encdec" else {}
        logits, new_cache = model.decode_step(params, tokens, positions,
                                              cache, window=window, **kw)
        return logits, new_cache

    from jax.sharding import NamedSharding, PartitionSpec as P
    logits_sh = NamedSharding(rules.mesh, rules.pspec(
        ("batch", None, "vocab"),
        (INPUT_SHAPES[shape_name]["global_batch"], 1, cfg.vocab_size)))
    in_sh = [param_sh, batch_sh["tokens"], batch_sh["positions"], cache_sh]
    args = [abstract_params, specs["tokens"], specs["positions"],
            specs["cache"]]
    if cfg.family == "encdec":
        in_sh.append(cross_sh)
        args.append(specs["cross_kv"])
    return fn, tuple(in_sh), (logits_sh, cache_sh), tuple(args)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            variant: str = "baseline", rules_overrides=None) -> dict:
    cfg = get_config(arch)
    vspec = VARIANTS[variant]
    if vspec.get("cfg"):
        cfg = _dc.replace(cfg, **vspec["cfg"])
    model = Model(cfg)
    if not model.supports(shape_name):
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped",
                "reason": f"{arch} does not support {shape_name} "
                          f"(see DESIGN.md §4)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    overrides = shape_rules_overrides(shape_name, arch)
    if vspec.get("rules"):
        overrides.update(vspec["rules"])
    if rules_overrides:
        overrides.update(rules_overrides)
    rules = ShardingRules(mesh, overrides)

    v_mb = vspec.get("micro_batch")
    kind0 = INPUT_SHAPES[shape_name]["kind"]
    t0 = time.monotonic()
    with use_sharding_rules(rules):
        fn, in_sh, out_sh, args = build_step(model, shape_name, rules, variant,
                                             micro_batch_override=v_mb)
        donate = (3,) if kind0 == "decode" else ()
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    mem = hlo_stats.extract_memory(compiled)
    cost = hlo_stats.extract_cost(compiled)
    hlo_text = compiled.as_text()
    colls = hlo_stats.collective_bytes(hlo_text)
    coll_total = sum(v["bytes"] for v in colls.values())

    kind = INPUT_SHAPES[shape_name]["kind"]
    extrap = None
    if not multi_pod:   # the roofline table is single-pod (brief)
        try:
            extrap = extrapolate_costs(cfg, shape_name, rules, kind,
                                       micro_batch=v_mb)
        # Any compile/lowering failure in the roofline extrapolation only
        # costs the sweep that one table; degrade to the raw HLO cost and
        # count it so a broken extrapolator is visible on the dashboards.
        except Exception as e:  # lint: disable=broad-except
            obs.get().registry.counter("dryrun/extrap_errors").add()
            traceback.print_exc()
            extrap = {"error": f"{type(e).__name__}: {e}"}
    if extrap and "flops" in extrap:
        terms = hlo_stats.roofline_terms(
            extrap["flops"], extrap["bytes_accessed"],
            extrap["coll_bytes"], n_chips, device_kind=TARGET_KIND)
    else:
        terms = hlo_stats.roofline_terms(cost["flops"], cost["bytes_accessed"],
                                         coll_total, n_chips,
                                         device_kind=TARGET_KIND)

    B = INPUT_SHAPES[shape_name]["global_batch"]
    S = INPUT_SHAPES[shape_name]["seq_len"]
    n_tokens = B * S if kind != "decode" else B
    n_active = model.n_active_params()
    model_flops_global = 6.0 * n_active * n_tokens * (1 if kind == "train" else 1 / 3)
    # train = fwd+bwd (6ND); prefill/decode = fwd only (2ND)
    model_flops_per_chip = model_flops_global / n_chips

    return {
        "arch": arch,
        "shape": shape_name,
        "kind": kind,
        "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "status": "ok",
        "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
        "n_params": model.n_params(),
        "n_active_params": n_active,
        "memory": mem,
        "cost_raw": cost,
        "cost_extrapolated": extrap,
        "collectives": colls,
        "collective_bytes_total": coll_total,
        "roofline": terms,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flop_ratio": (
            model_flops_per_chip / extrap["flops"]
            if extrap and extrap.get("flops") else
            (model_flops_per_chip / cost["flops"] if cost["flops"] else None)),
        "hbm_gb_per_chip": mem["total_hbm_bytes"] / 1e9,
    }


def result_path(arch, shape, multi_pod, variant):
    mesh = "2x16x16" if multi_pod else "16x16"
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR,
                        f"{arch}_{shape}_{mesh}_{variant}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                path = result_path(arch, shape, mp, args.variant)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {path}")
                    continue
                label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                print(f"[dryrun] {label} ...", flush=True)
                try:
                    res = run_one(arch, shape, mp, args.variant)
                # One (arch, shape, mesh) combination failing must not kill
                # the rest of the sweep: record an error result (it counts
                # toward the exit code) and move on.
                except Exception as e:  # lint: disable=broad-except
                    obs.get().registry.counter("dryrun/run_errors").add()
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "variant": args.variant,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                if res["status"] == "ok":
                    print(f"  ok: compile {res['t_compile_s']}s, "
                          f"hbm/chip {res['hbm_gb_per_chip']:.2f} GB, "
                          f"dominant {res['roofline']['dominant']}", flush=True)
                else:
                    print(f"  {res['status']}: {res.get('reason', res.get('error'))}",
                          flush=True)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
