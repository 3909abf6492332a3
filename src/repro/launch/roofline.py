"""Roofline extraction from compiled dry-run artifacts (DESIGN.md §7).

All numbers are per-device (the compiled module IS the per-device program
after SPMD partitioning), so each term is directly a time lower bound, with
the chip's peaks looked up by ``device_kind`` (`launch.mesh.PEAKS`):

  compute    = HLO_FLOPs_per_device / peak bf16 FLOP/s
  memory     = HLO_bytes_per_device / HBM bytes/s
  collective = collective_bytes_per_device / bytes/s per link

collective_bytes is not in cost_analysis(): we parse the post-partitioning
HLO text and sum the result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute / ragged-all-to-all op.
"""
from __future__ import annotations

import re

from .mesh import chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)

_SHAPE_RE = re.compile(r"\b(pred|[sufc]\d+|bf16)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES) + r")\b"
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes per collective kind from compiled HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        if f"{kind}-done" in line:   # async pair: count the -start only
            continue
        # result shape(s) live between '=' and the op name
        seg = line.split(" = ", 1)[1] if " = " in line else line
        seg = seg.split(kind)[0]
        total = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(seg))
        out[kind] += total
        count[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    return out


def roofline(cost: dict, mem: dict, coll: dict, device_kind: str) -> dict:
    """Three-term per-device roofline (seconds) + dominant bottleneck, on the
    peaks of one ``device_kind`` chip (unknown kinds raise)."""
    peaks = chip_peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    bytes_coll = float(coll.get("total", 0))
    terms = {
        "compute_s": flops / peaks.flops_bf16,
        "memory_s": bytes_hbm / peaks.hbm_bw,
        "collective_s": bytes_coll / peaks.ici_bw,
    }
    dominant = max(terms, key=terms.get)
    return {
        **terms,
        "dominant": dominant,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_hbm,
        "collective_bytes_per_dev": bytes_coll,
        "memory_analysis": mem,
    }


def memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    )
    out = {}
    for k in keys:
        out[k] = int(getattr(ma, k, 0) or 0)
    out["total_hbm_bytes"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"] - out["alias_size_in_bytes"]
    )
    return out


def model_flops(cfg, shape_info: dict, kind: str) -> float:
    """MODEL_FLOPS: 6*N_active*tokens (train) or 2*N_active*tokens (serve),
    GLOBAL (multiply ratios accordingly)."""
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = shape_info["batch"] * shape_info["seq"]
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape_info["batch"] * shape_info["seq"]
        return 2.0 * n_active * tokens
    tokens = shape_info["batch"]  # decode: one token per sequence
    return 2.0 * n_active * tokens
