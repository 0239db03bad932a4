"""Layout of a Qwen2/Qwen3-style dense GQA decoder for the benchmark: the
one place outside the plain reference that reads this family's keys.

A configuration file names it (``"layout": "qwen_dense_layout"``) beside
its reference.  It says what the family-free harness cannot know: the
program's ``ModelConfig``, the tensors each layer draws and their stable
ids, runs of like layers, how the drawn tensors map onto the program's
parameter tree and back, the work a token costs, and which pool vectors
``value_cache_gap`` compares.  ``cfg`` is the configuration file's dict
(Hugging Face key names).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# stable ids: changing one changes every weight drawn from it
GLOBAL_IDS = {"embed": 1, "final_norm": 2, "unembed": 3}
LAYER_IDS = {"ln1": 10, "q": 11, "k": 12, "v": 13, "o": 14, "q_norm": 15,
             "k_norm": 16, "ln2": 17, "gate": 18, "up": 19, "down": 20,
             "q_bias": 21, "k_bias": 22, "v_bias": 23}

# the widths the CPU tests cut the configuration to
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512}


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    Hk = cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or d // H
    return {"d": d, "H": H, "Hk": Hk, "D": D, "ff": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def program_config(cfg: dict):
    """The program's dense GQA decoder; activations in the configuration's
    ``torch_dtype``."""
    from repro.configs.base import ModelConfig   # the program, on demand
    m = dims(cfg)
    return ModelConfig(
        arch_id=cfg["name"], family="dense", n_layers=m["L"],
        d_model=m["d"], vocab_size=m["V"], n_heads=m["H"], n_kv_heads=m["Hk"],
        head_dim=m["D"], qk_norm=bool(cfg.get("use_qk_norm", False)),
        qkv_bias=bool(cfg.get("attention_bias", False)),
        rope_theta=float(cfg["rope_theta"]), d_ff=m["ff"],
        dtype=cfg["torch_dtype"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        norm_eps=float(cfg["rms_norm_eps"]))


# ------------------------------------------------------------- weights
def layer_groups(cfg: dict) -> list:
    """Runs of layers with the same tensors, as (first, count)."""
    return [(0, cfg["num_hidden_layers"])]


def layer_shapes(cfg: dict, layer: int) -> dict:
    """name -> (shape, kind), kind "matrix" (with its fan-in), "norm" or
    "bias"; every layer alike."""
    m = dims(cfg)
    d, H, Hk, D, ff = m["d"], m["H"], m["Hk"], m["D"], m["ff"]
    out = {"ln1": ((d,), "norm"), "ln2": ((d,), "norm"),
           "q": ((d, H, D), ("matrix", d)), "k": ((d, Hk, D), ("matrix", d)),
           "v": ((d, Hk, D), ("matrix", d)),
           "o": ((H, D, d), ("matrix", H * D)),
           "gate": ((d, ff), ("matrix", d)), "up": ((d, ff), ("matrix", d)),
           "down": ((ff, d), ("matrix", ff))}
    if cfg.get("use_qk_norm", False):
        out["q_norm"] = ((D,), "norm")
        out["k_norm"] = ((D,), "norm")
    if cfg.get("attention_bias", False):
        out["q_bias"] = ((H, D), "bias")
        out["k_bias"] = ((Hk, D), "bias")
        out["v_bias"] = ((Hk, D), "bias")
    return out


def global_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    out = {"embed": ((m["V"], m["d"]), "embed"),
           "final_norm": ((m["d"],), "norm")}
    if not cfg.get("tie_word_embeddings", False):
        out["unembed"] = ((m["d"], m["V"]), ("matrix", m["d"]))
    return out


def to_program_tree(canon: dict) -> dict:
    """The program's parameter tree from the drawn tensors: ``canon`` holds
    one stack per layer group (``groups``) and the global tensors."""
    (L,) = canon["groups"]
    attn = {k: L[k] for k in ("q", "k", "v", "o", "q_norm", "k_norm",
                              "q_bias", "k_bias", "v_bias") if k in L}
    emb = {"embed": canon["embed"]}
    if "unembed" in canon:
        emb["unembed"] = canon["unembed"]
    return {"embedding": emb,
            "layers": {"ln1": {"scale": L["ln1"]}, "attn": attn,
                       "ln2": {"scale": L["ln2"]},
                       "mlp": {k: L[k] for k in ("gate", "up", "down")}},
            "final_norm": {"scale": canon["final_norm"]}}


def layer_leaves(tree: dict) -> dict:
    """(name, layer) -> the program's per-layer slice, and (name, None)
    for global tensors: the leaves the learner check compares one by
    one."""
    out = {("embed", None): tree["embedding"]["embed"],
           ("final_norm", None): tree["final_norm"]["scale"]}
    if "unembed" in tree["embedding"]:
        out[("unembed", None)] = tree["embedding"]["unembed"]
    lay = tree["layers"]
    flat = {"ln1": lay["ln1"]["scale"], "ln2": lay["ln2"]["scale"],
            **lay["attn"], **lay["mlp"]}
    for name, arr in flat.items():
        for i in range(arr.shape[0]):
            out[(name, i)] = arr[i]
    return out


# ------------------------------------------------------------- work
def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: every layer's
    projections and MLP, and the output head (the tied embedding counts
    once, as the head; the input lookup is a gather)."""
    m = dims(cfg)
    per_layer = (m["d"] * m["H"] * m["D"] + 2 * m["d"] * m["Hk"] * m["D"]
                 + m["H"] * m["D"] * m["d"] + 3 * m["d"] * m["ff"])
    return m["L"] * per_layer + m["d"] * m["V"]


def attn_flops_per_key(cfg: dict) -> int:
    """Forward FLOPs one query spends on one key across all layers:
    q.k and p.v, 2 FLOPs per multiply-add each, over every query head."""
    m = dims(cfg)
    return 4 * m["H"] * m["D"] * m["L"]


def cache_bytes_per_token(cfg: dict, elem_bytes: int) -> int:
    """Bytes of K and V one cached token holds over all layers."""
    m = dims(cfg)
    return m["L"] * 2 * m["Hk"] * m["D"] * elem_bytes


# ------------------------------------------------------------- the pool
def cache_vectors(cache: dict, rows: list, lengths: list,
                  page: int) -> list:
    """Each row's value vectors (n, kv_heads, D) in float32, as layer 0 of
    the paged pool holds them for its first n = ``lengths[i]`` positions
    (an int8 pool's values times their scales).  The reference's
    ``layer_values`` gives the same vectors."""
    c = cache["stack"]
    pool = np.asarray(jax.device_get(c["v"][0].astype(jnp.float32)))
    if "v_scale" in c:
        pool = pool * np.asarray(jax.device_get(c["v_scale"][0]))[..., None]
    table = np.asarray(jax.device_get(c["table"][0]))
    out = []
    for row, n in zip(rows, lengths):
        v = pool[table[row, :-(-n // page)]].transpose(0, 2, 1, 3)
        out.append(v.reshape(-1, *v.shape[2:])[:n])
    return out
