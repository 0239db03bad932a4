"""Random weights made from ``--seed``, one leaf and one layer at a time.

Every tensor is drawn from its own key: ``fold_in(base, leaf_id)`` for a
global tensor and ``fold_in(fold_in(base, leaf_id), layer)`` for a layer's,
so the program's stacked tree (all layers in one jitted call) and the plain
reference (one layer at a time, after the window) draw identical values
without either handing the other anything.

Which tensors there are, their shapes, ids and runs of like layers come
from the configuration's layout module (``harness.layout``).  Tensors are
in the plain orientation of the reference (``x @ w``), named after the
Hugging Face modules they stand for.  Matrices are normal with standard
deviation 1/sqrt(fan_in); norm scales are 1 + 0.1 * normal, so a norm
that ignores its scale shows; the embedding is normal(0, 0.02).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import harness as H


def base_key(seed: int) -> jax.Array:
    """A key from any whole number: its low and high 32 bits."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def _draw(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return 1.0 + 0.1 * z
    if kind == "bias":
        return 0.02 * z
    if kind == "embed":
        return 0.02 * z
    return z * (1.0 / float(kind[1]) ** 0.5)


def layer_tensor(key, cfg: dict, name: str, layer, dtype=jnp.float32,
                 like=None):
    """Tensor ``name`` of layer ``layer``.  ``layer`` may be traced where
    ``like``, a layer of the same group given as a number, names the
    shapes."""
    LY = H.layout(cfg)
    shape, kind = LY.layer_shapes(cfg, layer if like is None else like)[name]
    k = jax.random.fold_in(jax.random.fold_in(key, LY.LAYER_IDS[name]), layer)
    return _draw(k, shape, kind).astype(dtype)


def global_tensor(key, cfg: dict, name: str, dtype=jnp.float32):
    LY = H.layout(cfg)
    shape, kind = LY.global_shapes(cfg)[name]
    return _draw(jax.random.fold_in(key, LY.GLOBAL_IDS[name]), shape,
                 kind).astype(dtype)


def global_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    return {n: global_tensor(key, cfg, n, dtype)
            for n in H.layout(cfg).global_shapes(cfg)}


def layer_weights(key, cfg: dict, layer, dtype=jnp.float32,
                  like=None) -> dict:
    shapes = H.layout(cfg).layer_shapes(cfg, layer if like is None else like)
    return {n: layer_tensor(key, cfg, n, layer, dtype, like) for n in shapes}


def stacked_weights(key, cfg: dict, dtype) -> dict:
    """Each group of like layers with its tensors stacked on a leading
    axis (``groups``, in the layout's order), and the global tensors, in
    ``dtype``.  Call inside one ``jax.jit`` so that each f32 draw is cast
    as it is made."""
    LY = H.layout(cfg)
    groups = [{name: jax.vmap(lambda l, name=name, first=first: layer_tensor(
        key, cfg, name, l, dtype, like=first))(jnp.arange(first, first + n))
        for name in LY.layer_shapes(cfg, first)}
        for first, n in LY.layer_groups(cfg)]
    return {"groups": groups, **global_weights(key, cfg, dtype)}
