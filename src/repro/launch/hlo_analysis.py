"""Compiled-program cost and memory figures.

``cost_summary`` reads ``compiled.cost_analysis()`` (FLOPs, bytes
accessed) and ``compiled.memory_analysis()`` (argument, output, temporary
and code bytes) into one flat dict.  XLA's cost analysis visits a
``while`` body once, whatever its trip count, so a program that scans
over layers reports one layer's FLOPs and bytes.
"""
from __future__ import annotations

from typing import Dict


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a plain dict (empty when absent)."""
    return dict(compiled.cost_analysis() or {})


def cost_summary(compiled) -> Dict[str, float]:
    ca = cost_analysis_dict(compiled)
    ma = compiled.memory_analysis()
    out = {
        "hlo_flops": float(ca.get("flops", 0.0)),
        "hlo_bytes": float(ca.get("bytes accessed", 0.0)),
        "arg_bytes": float(ma.argument_size_in_bytes),
        "out_bytes": float(ma.output_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "code_bytes": float(ma.generated_code_size_in_bytes),
    }
    out["peak_bytes"] = out["arg_bytes"] + out["out_bytes"] + out["temp_bytes"]
    return out
