"""Operations and bytes the algorithm needs, worked out from shapes alone.

Every count here is of the work a token or a decode step requires, never
of how a kernel happens to walk its grid: a kernel that stops visiting dead
table slots then reads as faster against the same yardstick.

``cfg`` is a configuration file's dict (Hugging Face key names); what a
layer of its family costs comes from its layout module
(``harness.layout``).
"""
from __future__ import annotations

import harness as H


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token (the active
    ones, where a layer picks among experts): the layout's count."""
    return H.layout(cfg).matmul_params(cfg)


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """Bytes one cached token holds over all layers, at ``kv_bytes`` an
    element: the layout's count."""
    return H.layout(cfg).cache_bytes_per_token(cfg, kv_bytes)


def forward_flops(cfg: dict, n_tokens: int, keys_attended: int) -> float:
    """Forward FLOPs of ``n_tokens`` tokens that attend ``keys_attended``
    keys between them (a token at position p attends p + 1)."""
    return (2.0 * matmul_params(cfg) * n_tokens
            + H.layout(cfg).attn_flops_per_key(cfg) * float(keys_attended))


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
    compute = (H.layout(cfg).attn_flops_per_key(cfg) * float(keys)
               / peak["bf16_flops"])
    return max(read, compute)
