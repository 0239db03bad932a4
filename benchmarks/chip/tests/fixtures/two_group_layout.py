"""A layout of two groups of like layers, for the tests only: one dense
layer, then two expert layers, laid out as the program's
``Model(ModelConfig(**PROGRAM))`` holds them (``dense_layers``, a list,
beside the ``layers`` stack).  It has tensor shapes and the tree mapping
and nothing else: no work counts, no pool, no configuration file.  Its
widths are fixed here, so it reads no key of the configuration."""
from __future__ import annotations

import jax

d, H, Hk, D, FF, E, F, V = 32, 4, 2, 8, 48, 4, 16, 64

PROGRAM = dict(arch_id="two-group", family="moe", n_layers=3, d_model=d,
               vocab_size=V, n_heads=H, n_kv_heads=Hk, head_dim=D, d_ff=FF,
               n_experts=E, moe_top_k=2, moe_d_ff=F, n_shared_experts=1,
               first_k_dense=1, dtype="float32", tie_embeddings=False)

GLOBAL_IDS = {"embed": 1, "final_norm": 2, "unembed": 3}
LAYER_IDS = {"ln1": 10, "q": 11, "k": 12, "v": 13, "o": 14, "ln2": 17,
             "gate": 18, "up": 19, "down": 20, "router": 30,
             "expert_gate": 31, "expert_up": 32, "expert_down": 33,
             "shared_gate": 34, "shared_up": 35, "shared_down": 36}

ATTN = ("q", "k", "v", "o")
EXPERTS = {"router": "router", "expert_gate": "gate", "expert_up": "up",
           "expert_down": "down", "shared_gate": "shared_gate",
           "shared_up": "shared_up", "shared_down": "shared_down"}


def layer_groups(cfg: dict) -> list:
    return [(0, 1), (1, 2)]


def layer_shapes(cfg: dict, layer: int) -> dict:
    out = {"ln1": ((d,), "norm"), "ln2": ((d,), "norm"),
           "q": ((d, H, D), ("matrix", d)), "k": ((d, Hk, D), ("matrix", d)),
           "v": ((d, Hk, D), ("matrix", d)),
           "o": ((H, D, d), ("matrix", H * D))}
    if layer == 0:
        return dict(out, gate=((d, FF), ("matrix", d)),
                    up=((d, FF), ("matrix", d)),
                    down=((FF, d), ("matrix", FF)))
    return dict(out, router=((d, E), ("matrix", d)),
                expert_gate=((E, d, F), ("matrix", d)),
                expert_up=((E, d, F), ("matrix", d)),
                expert_down=((E, F, d), ("matrix", F)),
                shared_gate=((d, F), ("matrix", d)),
                shared_up=((d, F), ("matrix", d)),
                shared_down=((F, d), ("matrix", F)))


def global_shapes(cfg: dict) -> dict:
    return {"embed": ((V, d), "embed"), "final_norm": ((d,), "norm"),
            "unembed": ((d, V), ("matrix", d))}


def _layer(L: dict, ffn: str, names: dict) -> dict:
    return {"ln1": {"scale": L["ln1"]}, "attn": {k: L[k] for k in ATTN},
            "ln2": {"scale": L["ln2"]},
            ffn: {prog: L[k] for k, prog in names.items()}}


def to_program_tree(canon: dict) -> dict:
    dense, moe = canon["groups"]
    first = _layer(dense, "mlp", {k: k for k in ("gate", "up", "down")})
    return {"embedding": {"embed": canon["embed"],
                          "unembed": canon["unembed"]},
            "dense_layers": [jax.tree_util.tree_map(lambda a: a[i], first)
                             for i in range(dense["ln1"].shape[0])],
            "layers": _layer(moe, "moe", EXPERTS),
            "final_norm": {"scale": canon["final_norm"]}}
