"""Extract roofline inputs from a compiled executable: cost analysis,
memory analysis, and collective traffic parsed from the (SPMD, per-device)
HLO text.

Wire-byte model per collective (ring algorithms, group size n, S = result
bytes of the op as printed in the per-device program):
    all-reduce          2*S*(n-1)/n
    all-gather          S*(n-1)/n            (S is the gathered result)
    reduce-scatter      S*(n-1)              (S is the scattered result)
    all-to-all          S*(n-1)/n
    collective-permute  S
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*(?:\},\{[^}]*)*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota format [G, N/G] => groups of N/G ranks
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("},{")[0]
        return max(1, len([x for x in first.replace("{", "").split(",") if x]))
    return default


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=lambda: defaultdict(int))
    result_bytes: dict = field(default_factory=lambda: defaultdict(float))
    wire_bytes: dict = field(default_factory=lambda: defaultdict(float))

    @property
    def total_wire_bytes(self) -> float:
        return float(sum(self.wire_bytes.values()))

    @property
    def total_result_bytes(self) -> float:
        return float(sum(self.result_bytes.values()))

    def to_dict(self):
        return {"counts": dict(self.counts),
                "result_bytes": dict(self.result_bytes),
                "wire_bytes": dict(self.wire_bytes),
                "total_wire_bytes": self.total_wire_bytes,
                "total_result_bytes": self.total_result_bytes}


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_txt, kind, startdone = m.group(1), m.group(2), m.group(3)
        if startdone == "-done":
            continue                       # counted at -start
        s = _shape_bytes(shape_txt)
        n = _group_size(line, n_devices)
        if n <= 1:
            continue
        if kind == "all-reduce":
            wire = 2.0 * s * (n - 1) / n
        elif kind == "all-gather":
            wire = s * (n - 1) / n
        elif kind == "reduce-scatter":
            wire = float(s) * (n - 1)
        elif kind == "all-to-all":
            wire = s * (n - 1) / n
        else:                              # collective-permute
            wire = float(s)
        stats.counts[kind] += 1
        stats.result_bytes[kind] += s
        stats.wire_bytes[kind] += wire
    return stats


def compiled_metrics(compiled, n_devices: int) -> dict:
    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = parse_collectives(txt, n_devices)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "collectives": coll.to_dict(),
    }
