"""The yardstick's FLOP and byte counts against hand-worked values at
qwen3-4b widths."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops as F  # noqa: E402
import peaks  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return json.loads((HERE / "configs" / "qwen3-4b.json").read_text())


def test_kv_bytes_per_token_is_the_pool_cost(cfg):
    # 36 layers x (K and V) x 8 kv heads x 128 x 2 bytes (bf16)
    assert F.kv_bytes_per_token(cfg) == 36 * 2 * 8 * 128 * 2 == 147_456


def test_matmul_params(cfg):
    per_layer = (2560 * 32 * 128 + 2 * 2560 * 8 * 128 + 32 * 128 * 2560
                 + 3 * 2560 * 9728)
    assert per_layer == 100_925_440
    assert F.matmul_params(cfg) == 36 * per_layer + 2560 * 151_936 \
        == 4_022_272_000


def test_decode_keys_count_live_kv_only():
    # a row at length 100 decoding 3 tokens attends 101, 102, 103 keys
    assert F.decode_keys(100, 3) == 101 + 102 + 103
    assert F.decode_keys(0, 1) == 1
    assert F.decode_keys(500, 0) == 0


def test_paged_attention_least_time_is_bandwidth_bound(cfg):
    p = peaks.peaks("TPU v5 lite")
    keys = 16 * (1000 + 1) * 64      # 16 rows near 1000 tokens, 64 steps
    read = 147_456 * keys / 819e9
    compute = 4 * 32 * 128 * 36 * keys / 197e12
    assert read > compute
    assert F.paged_attention_least_s(cfg, keys, p) == pytest.approx(read)
    # the yardstick does not see the table width or the grid: the same
    # live keys cost the same whatever max_len the engine was built with
    assert F.paged_attention_least_s(cfg, keys, p) == \
        F.paged_attention_least_s(dict(cfg, max_len=32768), keys, p)


def test_rollout_mfu_formula(cfg):
    # one decoded token at context 1000 (attends 1001 keys)
    want = 2 * 4_022_272_000 + 4 * 32 * 128 * 36 * 1001
    assert F.forward_flops(cfg, 1, 1001) == want


def test_learner_mfu_formula(cfg):
    l2 = dict(cfg, num_hidden_layers=2)
    n = 2 * 100_925_440 + 2560 * 151_936
    # one 512-token trajectory: 6 x params x tokens + 3 x causal attention
    keys = 512 * 513 // 2
    assert F.causal_keys([512]) == keys
    assert F.train_flops(l2, 512, keys) == \
        6 * n * 512 + 3 * 4 * 32 * 128 * 2 * keys


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
