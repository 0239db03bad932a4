"""The system under test, as the benchmark builds it: the program's model
from a configuration file, and the seeded weights laid out in the program's
own parameter tree.  This is the one place that knows that layout; a tree
that no longer matches the program's raises before any run."""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from harness import ROOT
import weights as W

sys.path.insert(0, str(ROOT / "src"))

from repro.configs.base import ModelConfig  # noqa: E402
from repro.models import Model  # noqa: E402


def model_config(cfg: dict) -> ModelConfig:
    """A dense GQA decoder from Hugging Face key names; activations in the
    configuration's ``torch_dtype``."""
    return ModelConfig(
        arch_id=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or (cfg["hidden_size"]
                                         // cfg["num_attention_heads"]),
        qk_norm=bool(cfg.get("use_qk_norm", False)),
        qkv_bias=bool(cfg.get("attention_bias", False)),
        rope_theta=float(cfg["rope_theta"]), d_ff=cfg["intermediate_size"],
        dtype=cfg["torch_dtype"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        norm_eps=float(cfg["rms_norm_eps"]))


def to_program_tree(canon: dict) -> dict:
    L = canon["layers"]
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
    """(canonical name, layer) -> the program's per-layer slice, and
    (name, None) for global tensors: the leaves the learner check
    compares one by one."""
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


def make_params(model: Model, cfg: dict, seed: int, dtype):
    """The program's parameter tree from ``seed``, made on the device in
    one jitted call, in ``dtype``."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    fn = jax.jit(lambda k: to_program_tree(W.stacked_weights(k, cfg, dtype)))
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
    fn = jax.jit(lambda _old, k: to_program_tree(
        W.stacked_weights(k, cfg, dtype)), donate_argnums=0)
    return jax.block_until_ready(fn(old, W.base_key(seed)))


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]
