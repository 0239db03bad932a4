"""The system under test, as the benchmark builds it: the program's model
from a configuration file, and the seeded weights laid out in the program's
own parameter tree by the configuration's layout module; a tree that no
longer matches the program's raises before any run."""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

import harness as H
import weights as W

sys.path.insert(0, str(H.ROOT / "src"))

from repro.models import Model  # noqa: E402


def model_of(cfg: dict) -> Model:
    """The program's model of the configuration, as its layout builds it."""
    return Model(H.layout(cfg).program_config(cfg))


def make_params(model: Model, cfg: dict, seed: int, dtype):
    """The program's parameter tree from ``seed``, made on the device in
    one jitted call, in ``dtype``."""
    tree = H.layout(cfg).to_program_tree
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    fn = jax.jit(lambda k: tree(W.stacked_weights(k, cfg, dtype)))
    got = jax.eval_shape(fn, W.base_key(0))
    if (jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got)
            or [a.shape for a in jax.tree_util.tree_leaves(want)]
            != [a.shape for a in jax.tree_util.tree_leaves(got)]):
        raise RuntimeError("the seeded weights no longer match the program's "
                           "parameter tree")
    return jax.block_until_ready(fn(W.base_key(seed)))


def remake_params(cfg: dict, seed: int, dtype, old):
    """Weights for another seed in the buffers of ``old``, which are
    donated: two 4B-parameter trees never sit on the chip at once."""
    tree = H.layout(cfg).to_program_tree
    fn = jax.jit(lambda _old, k: tree(W.stacked_weights(k, cfg, dtype)),
                 donate_argnums=0)
    return jax.block_until_ready(fn(old, W.base_key(seed)))


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]
