"""Compile rehearsals for a described TPU v5e, at Qwen3-4B widths.

The CPU backend runs the Pallas kernels in interpret mode, which accepts
block shapes the chip's compiler refuses.  These tests compile the main-path
kernel, and one paged decode step of the engine with the kernel in it, for a
v5e that is described and not attached, and check that the Mosaic kernel
(``tpu_custom_call``) is in the compiled program.  Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and it keeps it until
it exits.  Keep every such compile in this one file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import Model, PagedCache

ARCH = "qwen3-4b"
B, PAGE, MAX_LEN = 8, 16, 2048
N_BLOCKS = B * MAX_LEN // PAGE              # + 1 trash block in the pool


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # lint: disable=broad-except
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_paged_kernel_compiles_for_v5e(one_chip, kv_dtype):
    """The decode kernel over the engine's pool layout (head-major, trash
    block last), with bf16 or int8 pools and their per-slot scales."""
    from repro.kernels.paged_attention import paged_attention_fwd
    cfg = get_config(ARCH)
    pool = jax.eval_shape(lambda: PagedCache(PAGE, N_BLOCKS, kv_dtype)
                          .init_cache(Model(dataclasses.replace(
                              cfg, n_layers=1)), B, MAX_LEN))["stack"]
    pool = _on(one_chip, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), pool))
    q = jax.ShapeDtypeStruct((B, cfg.n_heads, cfg.head_dim), jnp.bfloat16,
                             sharding=one_chip)
    q_pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def step(q, pool, q_pos):
        return paged_attention_fwd(q, pool["k"], pool["v"], pool["table"],
                                   q_pos, k_scale=pool.get("k_scale"),
                                   v_scale=pool.get("v_scale"),
                                   interpret=False)

    compiled = jax.jit(step).lower(q, pool, q_pos).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's stable name, which profiles read it by
    assert re.search(r"%paged_attention[.\d]* = .*custom-call",
                     compiled.as_text())


def test_engine_paged_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """One paged decode step of the serving engine at Qwen3-4B widths (two
    layers: the layer stack is a scan, so depth adds no new code).  The
    backend rule is steered here, in the test: this process runs on the
    CPU, and the engine must take its TPU path."""
    from repro import kernels
    from repro.serving.engine import GenerationEngine
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    model = Model(dataclasses.replace(get_config(ARCH), n_layers=2))
    params = _on(one_chip, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        model.abstract()))
    eng = GenerationEngine(model, params, pad_id=0, stop_ids=(1,),
                           max_len=MAX_LEN, cache_mode="paged",
                           page_size=PAGE, num_blocks=N_BLOCKS)
    assert eng._use_paged_kernel
    cache = _on(one_chip, model.cache_struct(
        B, MAX_LEN, policy=PagedCache(PAGE, N_BLOCKS)))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    compiled = eng._decode_jit.lower(params, cache, tok, tok, valid,
                                     None).compile()
    assert "tpu_custom_call" in compiled.as_text()
