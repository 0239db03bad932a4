"""Dry-run machinery tests.

The full 512-device runs live in launch/dryrun.py (results under
results/dryrun/).  Here we exercise the same code path on a small forced
device count in a SUBPROCESS (so the pytest process keeps its real single
device), plus unit tests for the HLO collective parser.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.hlo_stats import collective_bytes, roofline_terms

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_collective_parser():
    hlo = textwrap.dedent("""
      ENTRY main {
        %p = bf16[16,128]{1,0} parameter(0)
        %ag = bf16[16,2048]{1,0} all-gather(%p), dimensions={1}
        %ar = f32[16,128]{1,0} all-reduce(%x), to_apply=%sum
        %rs = (f32[8,128]{1,0}, f32[8,128]{1,0}) reduce-scatter(%a, %b), dimensions={0}
        %cp = bf16[4,4]{1,0} collective-permute(%y), source_target_pairs={{0,1}}
        %dot = f32[16,16]{1,0} dot(%p, %p)
      }
    """)
    out = collective_bytes(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 16 * 2048 * 2
    assert out["all-reduce"]["bytes"] == 16 * 128 * 4
    assert out["reduce-scatter"]["bytes"] == 2 * 8 * 128 * 4
    assert out["collective-permute"]["bytes"] == 4 * 4 * 2
    assert out["all-to-all"]["count"] == 0


def test_roofline_terms_dominance():
    t = roofline_terms(flops=197e12, bytes_accessed=819e9, coll_bytes=0,
                       n_chips=1, device_kind="TPU v5 lite")
    # exactly 1s compute, 1s memory, 0 collective
    assert t["t_compute_s"] == pytest.approx(1.0)
    assert t["t_memory_s"] == pytest.approx(1.0)
    t2 = roofline_terms(1e12, 1e9, 1e12, n_chips=256,
                        device_kind="TPU v5 lite")
    assert t2["dominant"] == "collective"


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_has_no_peaks(kind):
    """A device with no published peaks is an error, not v5e by default."""
    from repro.launch.hlo_stats import peaks
    with pytest.raises(KeyError, match="no published peaks"):
        peaks(kind)
    with pytest.raises(KeyError):
        roofline_terms(1.0, 1.0, 0, n_chips=1, device_kind=kind)


@pytest.mark.slow
def test_small_mesh_lower_compile_subprocess():
    """A reduced arch lowers+compiles with the dry-run sharding machinery on
    a 4-device forced-host mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import Model
        from repro.distributed.sharding import ShardingRules, use_sharding_rules
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.specs import batch_shardings

        mesh = make_debug_mesh(model=2, data=2)
        cfg = get_config("qwen3-32b").reduced(d_model=64, n_heads=4,
                                              n_kv_heads=2, head_dim=16,
                                              d_ff=128, vocab_size=256)
        model = Model(cfg)
        rules = ShardingRules(mesh)
        param_sh = rules.specs_to_shardings(model.specs())
        specs = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
        batch_sh = batch_shardings(rules, specs)

        def fwd(params, batch):
            with use_sharding_rules(rules):
                logits, _, _ = model.apply(params, batch)
            return logits

        compiled = jax.jit(fwd, in_shardings=(param_sh, batch_sh)).lower(
            model.abstract(), specs).compile()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        assert ca["flops"] > 0
        print("OK", int(ca["flops"]))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_dryrun_results_if_present():
    """Validate any dry-run artifacts that the sweep has produced so far."""
    d = os.path.join(os.getcwd(), "results", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("no dry-run artifacts yet")
    n_ok = 0
    for name in os.listdir(d):
        try:
            with open(os.path.join(d, name)) as f:
                res = json.load(f)
        except json.JSONDecodeError:
            continue  # being written by a concurrent sweep
        assert res["status"] in ("ok", "skipped", "error")
        if res["status"] == "ok":
            n_ok += 1
            assert res["hbm_gb_per_chip"] > 0
            assert res["roofline"]["dominant"] in ("compute", "memory",
                                                   "collective")
    assert n_ok >= 1
