"""Post-SPMD HLO analysis: collective-traffic extraction and roofline terms.

``compiled.cost_analysis()`` supplies per-device FLOPs / bytes, but no
collective traffic — we parse the partitioned HLO text and sum operand
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.

Caveat (measured, see EXPERIMENTS.md §Roofline methodology): XLA cost
analysis visits a ``while`` body ONCE, ignoring trip counts.  The roofline
pass therefore re-lowers shallow *unrolled* variants (depth 1 and 2) and
extrapolates ``total = f1 + (n - 1) * (f2 - f1)``; the same correction is
applied to collective bytes parsed here.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"^\s*(?:ROOT\s+)?([%\w.\-]+)\s*=\s*(\([^=]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z0-9\-]+)")
_TUPLE_ELEM_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string, e.g. ``bf16[16,4096]{1,0}`` or a tuple."""
    total = 0
    for dt, dims in _TUPLE_ELEM_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, int]
    count_by_op: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())

    def as_dict(self) -> Dict[str, int]:
        out = {f"{k}_bytes": v for k, v in self.bytes_by_op.items()}
        out.update({f"{k}_count": v for k, v in self.count_by_op.items()})
        out["collective_bytes"] = self.total_bytes
        out["collective_count"] = self.total_count
        return out


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand bytes of every collective in partitioned HLO text.

    Builds a symbol table (instruction name -> result bytes) in one pass,
    then resolves each collective's operand names against it.  ``-start``
    variants (async collectives) are counted; their ``-done`` halves are not.
    """
    shapes: Dict[str, int] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        m = _SHAPE_RE.match(ln)
        if m:
            name = m.group(1).lstrip("%")
            shapes[name] = _shape_bytes(m.group(2))

    bytes_by: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    count_by: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    for ln in lines:
        m = _SHAPE_RE.match(ln)
        if not m:
            continue
        op = m.group(3)
        base = None
        for c in COLLECTIVE_OPS:
            if op == c or op == c + "-start":
                base = c
                break
        if base is None:
            continue
        # operand list: first (...) group after the op name
        rest = ln[m.end():]
        paren = rest.find("(")
        if paren < 0:
            continue
        depth, j = 0, paren
        for j in range(paren, len(rest)):
            if rest[j] == "(":
                depth += 1
            elif rest[j] == ")":
                depth -= 1
                if depth == 0:
                    break
        operand_str = rest[paren + 1 : j]
        total = 0
        for name in re.findall(r"%?([\w.\-]+)", operand_str):
            if name in shapes:
                total += shapes[name]
        bytes_by[base] += total
        count_by[base] += 1
    return CollectiveStats(bytes_by, count_by)


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
