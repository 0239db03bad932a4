"""HLO statistics for the roofline: collective bytes from the compiled module
text, plus cost_analysis extraction and the three roofline terms.

The roofline divides by the published peaks of one chip, looked up by the
``device_kind`` JAX reports.  A device with no entry is an error, never a
default.
"""
from __future__ import annotations

import re
from typing import Dict

# Published per-chip peaks keyed by ``jax.Device.device_kind``.
# TPU v5e (kind "TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises KeyError for a kind
    with no entry in :data:`PEAKS`."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, dict]:
    """Sum output-shape bytes of every collective op in the HLO text.

    Returns {op_kind: {"count": int, "bytes": int}}.  Bytes are the op's
    result size — a uniform proxy for data moved (methodology note in
    EXPERIMENTS.md §Roofline).
    """
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, rhs = stripped.split("=", 1)
        rhs = rhs.strip()
        m = re.match(r"^(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z0-9-]+)",
                     rhs)
        if not m:
            continue
        result_part, op = m.group(1), m.group(2)
        kind = next((c for c in _COLLECTIVES if op.startswith(c)), None)
        if kind is None:
            continue
        nbytes = sum(_shape_bytes(d, s)
                     for d, s in _SHAPE_RE.findall(result_part))
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    return out


def while_trip_counts(hlo_text: str) -> int:
    return hlo_text.count(" while(")


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: int,
                   n_chips: int, *, device_kind: str) -> dict:
    """The three terms in seconds on one chip of ``device_kind``.

    flops / bytes_accessed are WHOLE-PROGRAM numbers from cost_analysis
    (already per-partition in SPMD: XLA analyses the partitioned module);
    collective bytes are whole-module per-partition too.
    """
    p = peaks(device_kind)
    t_compute = flops / p["bf16_flops"]
    t_memory = bytes_accessed / p["hbm_bw"]
    t_collective = coll_bytes / p["ici_bw"]
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_collective, "collective"))[1]
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
    }


def extract_cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def extract_memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        out[k] = int(getattr(ma, k, 0))
    out["total_hbm_bytes"] = (out["argument_size_in_bytes"]
                              + out["output_size_in_bytes"]
                              + out["temp_size_in_bytes"]
                              - out.get("alias_size_in_bytes", 0))
    return out
