"""Layouts and groups of like layers: a layout of two groups (one dense
layer, then two expert layers; ``fixtures/two_group_layout.py``) stacks
into the program's own parameter tree, a layer drawn alone equals its
slice of the stack, and a configuration that names a missing layout or
reference module is refused when its cell is loaded."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the benchmark on the path)

import harness as H  # noqa: E402
import program as P  # noqa: E402
import weights as W  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
TWO_GROUP = {"name": "two-group", "layout": "two_group_layout"}


@pytest.fixture
def fixture_configs(monkeypatch):
    monkeypatch.setattr(H, "CONFIGS", FIXTURES)


def tiny_dense() -> dict:
    return tiny.tiny_cell("qwen3-4b-l2.grpo-step").config


def test_two_groups_stack_into_the_program_tree(fixture_configs):
    LY = H.layout(TWO_GROUP)
    model = P.Model(ModelConfig(**LY.PROGRAM))
    params = P.make_params(model, TWO_GROUP, 5, jnp.float32)
    assert len(params["dense_layers"]) == 1
    assert params["layers"]["moe"]["gate"].shape[:2] == (2, 4)
    # the program runs on the tree it was given
    logits, _, _ = model.apply(params, {"tokens": jnp.zeros((1, 4),
                                                            jnp.int32)})
    assert logits.shape == (1, 4, 64) and np.isfinite(logits).all()


@pytest.mark.parametrize("which", ["two_group", "qwen_dense"])
def test_a_layer_drawn_alone_equals_its_slice(which, monkeypatch):
    if which == "two_group":
        monkeypatch.setattr(H, "CONFIGS", FIXTURES)
        cfg = TWO_GROUP
    else:
        cfg = tiny_dense()
    key = W.base_key(2**31 + 3)
    canon = jax.jit(lambda k: W.stacked_weights(k, cfg, jnp.float32))(key)
    groups = H.layout(cfg).layer_groups(cfg)
    assert len(canon["groups"]) == len(groups)
    for stack, (first, n) in zip(canon["groups"], groups):
        for layer in range(first, first + n):
            alone = jax.jit(lambda k, layer=layer: W.layer_weights(
                k, cfg, layer))(key)
            assert set(alone) == set(stack)
            for name, arr in alone.items():
                np.testing.assert_array_equal(arr, stack[name][layer - first])


@pytest.mark.parametrize("key", ["layout", "reference"])
def test_a_missing_module_is_refused_at_load(key, tmp_path, monkeypatch):
    bench = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    conf = bench["configs"][0]
    cfg = json.loads((H.ROOT / conf["file"]).read_text())
    cfg[key] = "no_such_module"
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bench["configs"][0] = dict(conf, file="cfg.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(H, "ROOT", tmp_path)
    with pytest.raises(SystemExit, match=f"{conf['name']}.*{key}.*"
                                         "no_such_module"):
        H.load_cell(bench["workloads"][0]["name"])
