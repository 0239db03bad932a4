"""Operations and bytes the algorithm needs, worked out from shapes alone.

Every count here is of the work a token or a decode step requires, never
of how a kernel happens to walk its grid: a kernel that stops visiting dead
table slots then reads as faster against the same yardstick.

``cfg`` is a configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    Hk = cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or d // H
    return {"d": d, "H": H, "Hk": Hk, "D": D, "ff": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: every layer's
    projections and MLP, and the output head (the tied embedding counts
    once, as the head; the input lookup is a gather)."""
    m = dims(cfg)
    per_layer = (m["d"] * m["H"] * m["D"] + 2 * m["d"] * m["Hk"] * m["D"]
                 + m["H"] * m["D"] * m["d"] + 3 * m["d"] * m["ff"])
    return m["L"] * per_layer + m["d"] * m["V"]


def attn_flops_per_key(cfg: dict) -> int:
    """Forward FLOPs one query spends on one key across all layers:
    q.k and p.v, 2 FLOPs per multiply-add each, over every query head."""
    m = dims(cfg)
    return 4 * m["H"] * m["D"] * m["L"]


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one cached token holds over all layers."""
    m = dims(cfg)
    return m["L"] * 2 * m["Hk"] * m["D"] * kv_bytes


def forward_flops(cfg: dict, n_tokens: int, keys_attended: int) -> float:
    """Forward FLOPs of ``n_tokens`` tokens that attend ``keys_attended``
    keys between them (a token at position p attends p + 1)."""
    return (2.0 * matmul_params(cfg) * n_tokens
            + attn_flops_per_key(cfg) * float(keys_attended))


def train_flops(cfg: dict, n_tokens: int, keys_attended: int) -> float:
    """Forward and backward FLOPs of a training step over ``n_tokens``
    tokens: three times the forward pass (6 x params per token), with no
    recomputation counted."""
    return 3.0 * forward_flops(cfg, n_tokens, keys_attended)


def causal_keys(lengths) -> int:
    """Keys attended by full causal sequences of these lengths."""
    return int(sum(n * (n + 1) // 2 for n in lengths))


def decode_keys(start_len: int, steps: int) -> int:
    """Keys one row attends over ``steps`` decode steps that start at
    context length ``start_len``: step i writes position start_len + i and
    attends positions 0 .. start_len + i."""
    return steps * start_len + steps * (steps + 1) // 2


def paged_attention_least_s(cfg: dict, keys: int, peak: dict,
                            kv_bytes: int = 2) -> float:
    """The least time the paged decode kernel needs for ``keys`` live
    (row, step, key) triples: the larger of reading their K and V at the
    HBM bandwidth and their q.k and p.v FLOPs at the bf16 peak."""
    read = kv_bytes_per_token(cfg, kv_bytes) * float(keys) / peak["hbm_bw"]
    compute = attn_flops_per_key(cfg) * float(keys) / peak["bf16_flops"]
    return max(read, compute)
