"""Random weights made from ``--seed``, one leaf and one layer at a time.

Every tensor is drawn from its own key: ``fold_in(base, leaf_id)`` for a
global tensor and ``fold_in(fold_in(base, leaf_id), layer)`` for a layer's,
so the program's stacked tree (all layers in one jitted call) and the plain
reference (one layer at a time, after the window) draw identical values
without either handing the other anything.

Tensors are in the plain orientation of the reference (``x @ w``), named
after the Hugging Face modules they stand for.  Matrices are normal with
standard deviation 1/sqrt(fan_in); norm scales are 1 + 0.1 * normal, so a
norm that ignores its scale shows; the embedding is normal(0, 0.02).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flops import dims

# stable ids: changing one changes every weight drawn from it
GLOBAL_IDS = {"embed": 1, "final_norm": 2, "unembed": 3}
LAYER_IDS = {"ln1": 10, "q": 11, "k": 12, "v": 13, "o": 14, "q_norm": 15,
             "k_norm": 16, "ln2": 17, "gate": 18, "up": 19, "down": 20,
             "q_bias": 21, "k_bias": 22, "v_bias": 23}


def base_key(seed: int) -> jax.Array:
    """A key from any whole number: its low and high 32 bits."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind), kind "matrix" (with its fan-in), "norm" or
    "bias"."""
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


def _draw(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return 1.0 + 0.1 * z
    if kind == "bias":
        return 0.02 * z
    if kind == "embed":
        return 0.02 * z
    return z * (1.0 / float(kind[1]) ** 0.5)


def layer_tensor(key, cfg: dict, name: str, layer, dtype=jnp.float32):
    shape, kind = layer_shapes(cfg)[name]
    k = jax.random.fold_in(jax.random.fold_in(key, LAYER_IDS[name]), layer)
    return _draw(k, shape, kind).astype(dtype)


def global_tensor(key, cfg: dict, name: str, dtype=jnp.float32):
    shape, kind = global_shapes(cfg)[name]
    return _draw(jax.random.fold_in(key, GLOBAL_IDS[name]), shape,
                 kind).astype(dtype)


def layer_weights(key, cfg: dict, layer, dtype=jnp.float32) -> dict:
    return {n: layer_tensor(key, cfg, n, layer, dtype)
            for n in layer_shapes(cfg)}


def stacked_weights(key, cfg: dict, dtype) -> dict:
    """Every layer's tensors stacked on a leading axis, and the global
    tensors, in ``dtype``.  Call inside one ``jax.jit`` so that each f32
    draw is cast as it is made."""
    n = cfg["num_hidden_layers"]
    layers = {name: jax.vmap(lambda l, name=name: layer_tensor(
        key, cfg, name, l, dtype))(jnp.arange(n))
        for name in layer_shapes(cfg)}
    glob = {name: global_tensor(key, cfg, name, dtype)
            for name in global_shapes(cfg)}
    return {"layers": layers, **glob}
