"""The seeded weights of Qwen3-4B, as the benchmark hands them to the
program, are the same bits as before the layout modules: SHA-256 of the
whole tiny tree for seeds 0 and 1, every leaf's shape and dtype at full
size, and at full size the norm scales and row 0 of ``q`` and ``down`` in
the first and the last layer (the values recorded from the benchmark as it
was before the weights were drawn through the layout)."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

import harness as H  # noqa: E402
import program as P  # noqa: E402
import weights as W  # noqa: E402

TREES = {
    ("qwen3-4b", 0):
        "0a7f1754b6ca780eeb43ba7e9a6aa94ed2e33d17c9b36b52f7af897d2a5a28c5",
    ("qwen3-4b", 1):
        "42d34087b9eb5c9889e37ab53bd82f99021b832956de7c353bf67f2d311f913e",
    ("qwen3-4b-l2", 0):
        "bef2ab24f4f864fac42347d69df10a22c2a00c8b7639a18cedea9e3cb0daaa51",
    ("qwen3-4b-l2", 1):
        "cf74cc3bbc870742ef029e90f8844917c179118d9e7eec16dd196ed0516d53bf",
}

# leaf -> shape, "L" the configuration's layer count
SHAPES = {
    "['embedding']['embed']": (151936, 2560),
    "['final_norm']['scale']": (2560,),
    "['layers']['attn']['k']": ("L", 2560, 8, 128),
    "['layers']['attn']['k_norm']": ("L", 128),
    "['layers']['attn']['o']": ("L", 32, 128, 2560),
    "['layers']['attn']['q']": ("L", 2560, 32, 128),
    "['layers']['attn']['q_norm']": ("L", 128),
    "['layers']['attn']['v']": ("L", 2560, 8, 128),
    "['layers']['ln1']['scale']": ("L", 2560),
    "['layers']['ln2']['scale']": ("L", 2560),
    "['layers']['mlp']['down']": ("L", 9728, 2560),
    "['layers']['mlp']['gate']": ("L", 2560, 9728),
    "['layers']['mlp']['up']": ("L", 2560, 9728),
}

SMALL = {
    "qwen3-4b": {
        "down0":
            "747d6dd32b24d17091d95f00e4d3d3c401a306875f16d1f09b29d3396478d652",
        "final_norm":
            "29cbe1a0130f50691566fb9e8963f3dec7d6443119f1847e1cb9c28e890001cc",
        "k_norm":
            "03e73488db563afbbe1f50f521111c50b8ed53cf5bb7a6fb44fb8bca927e567c",
        "ln1":
            "c84e5e85db461625db4b621e90d5758a2335cc6420b5edd5b17289523c5b5d34",
        "ln2":
            "5277f6d6a2596ee3c04409305f9476edf8ab2342f5e5e6d3e9ce35ec74d8dbe5",
        "q0":
            "e877f5a7bbc4671f0b342ea3ea0b5275e64b07d6fe55d409be9c69f8217aaea3",
        "q_norm":
            "eb3a51c364512d5ba81a7ebfbeb3902725aa415a984a68c4e5acf7375a20cf3a",
    },
    "qwen3-4b-l2": {
        "down0":
            "bfbc3a99e9bad19c9a3e84101b82ce0024141e6267a6c2df53335f0b6e68a601",
        "final_norm":
            "b3c51563fa849867c2cf8e106968675b512a0db1b984331eabd26cbd5502afe5",
        "k_norm":
            "e1997afceaf91db83eeedb2f87e0ef31819924288c3210cdf48df55202616b1b",
        "ln1":
            "4d3d3ed32c60611581c5d1c2c876d6261803b5e02ec5b7b9075be14cf3b3da2c",
        "ln2":
            "12972628bf837d8a677bc7b55f2af5057952519c6eed4e07ba7ee87cf9fd6b22",
        "q0":
            "b068d171774f7834df96f85e3acd7c3ad66b3695ac78d253cebdaaf9729fe4aa",
        "q_norm":
            "8120c1e3d1016d2e205d7e7f82daca1badc9722d750aefaba0a9de7efd98c128",
    },
}


def digest(tree) -> str:
    """SHA-256 over each leaf's path, dtype, shape and bytes, in tree
    order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def full_config(name: str) -> dict:
    import json
    return json.loads((H.CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,seed", sorted(TREES))
def test_tiny_tree_is_the_same_bits(name, seed):
    cfg = tiny.tiny_cell(f"{name}.search-rollout").config
    params = P.make_params(P.model_of(cfg), cfg, seed,
                           P.dtype_of(cfg["param_dtype"]))
    assert digest(params) == TREES[(name, seed)]


@pytest.mark.parametrize("name", ["qwen3-4b", "qwen3-4b-l2"])
def test_full_tree_shapes_and_dtypes(name):
    cfg = full_config(name)
    dtype = P.dtype_of(cfg["param_dtype"])
    tree = H.layout(cfg).to_program_tree
    got = jax.eval_shape(lambda k: tree(W.stacked_weights(k, cfg, dtype)),
                         W.base_key(0))
    leaves = {jax.tree_util.keystr(p): a for p, a in
              jax.tree_util.tree_flatten_with_path(got)[0]}
    n = cfg["num_hidden_layers"]
    assert {k: tuple(n if s == "L" else s for s in v)
            for k, v in SHAPES.items()} == {k: a.shape
                                            for k, a in leaves.items()}
    assert {a.dtype for a in leaves.values()} == {jnp.dtype(dtype)}


@pytest.mark.parametrize("name", ["qwen3-4b", "qwen3-4b-l2"])
def test_full_size_small_leaves_are_the_same_bits(name):
    """Norms from the stacked tree (XLA drops the matrices nobody reads);
    rows of ``q`` and ``down`` drawn as the stack draws them, for the two
    layers alone."""
    cfg = full_config(name)
    dtype = P.dtype_of(cfg["param_dtype"])
    tree, n = H.layout(cfg).to_program_tree, cfg["num_hidden_layers"]
    key = W.base_key(0)

    def norms(k):
        t = tree(W.stacked_weights(k, cfg, dtype))
        lay = t["layers"]
        return {"ln1": lay["ln1"]["scale"], "ln2": lay["ln2"]["scale"],
                "q_norm": lay["attn"]["q_norm"],
                "k_norm": lay["attn"]["k_norm"],
                "final_norm": t["final_norm"]["scale"]}

    got = {k: digest(v) for k, v in jax.jit(norms)(key).items()}
    for name_, leaf in (("q", "q0"), ("down", "down0")):
        rows = jax.jit(lambda k, name_=name_: jax.vmap(
            lambda l: W.layer_tensor(k, cfg, name_, l, dtype, like=0))(
                jnp.array([0, n - 1]))[:, 0])(key)
        got[leaf] = digest(rows)
    assert got == SMALL[name]
