"""Roofline accounting from compiled artifacts.

Sources (per EXPERIMENTS.md methodology):
  - ``compiled.cost_analysis()``  -> per-device HLO FLOPs and bytes accessed
    (verified: post-SPMD, numbers are per-device).
  - ``compiled.as_text()``        -> collective ops; we sum RESULT-shape
    bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute (convention: result bytes ~ data landing on the
    device; documented here, applied uniformly to baseline & optimized).

IMPORTANT caveat handled by the caller: XLA's HloCostAnalysis counts a
``while`` (lax.scan) body ONCE, so full-step numbers undercount scanned
layer stacks. The dry-run therefore costs each program SEGMENT separately
(embed / one layer per block type / head / optimizer) and scales by the
segment's repeat count ("compositional costing").

Hardware constants: TPU v5e-class chip.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12       # bf16 FLOP/s per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link (~ per-chip usable bandwidth)
DCN_BW = 25e9             # bytes/s per chip across pods (assumed)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[128,1024]{1,0}   or  f32[]
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind (start/done pairs and
    fusion wrappers counted once via the '-start' form preference)."""
    out: Dict[str, int] = {}
    seen_start = set()
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        for kind in _COLLECTIVES:
            # match '<shape> <kind>(' or '<shape> <kind>-start('
            m = re.match(r"^(\(?.*?\)?)\s+" + kind + r"(-start|-done)?\(",
                         rhs)
            if not m:
                continue
            variant = m.group(2) or ""
            if variant == "-done":
                continue  # counted at -start
            shape = m.group(1)
            if variant == "-start" and kind == "all-reduce":
                # all-reduce-start result repeats operand; fine to count
                pass
            out[kind] = out.get(kind, 0) + shape_bytes(shape)
            break
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # per device
    bytes_hbm: float             # per device
    bytes_coll: float            # per device
    coll_breakdown: Dict[str, int]

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_hbm / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.bytes_coll / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def scaled(self, factor: float) -> "RooflineTerms":
        return RooflineTerms(
            self.flops * factor, self.bytes_hbm * factor,
            self.bytes_coll * factor,
            {k: int(v * factor) for k, v in self.coll_breakdown.items()})

    def __add__(self, other: "RooflineTerms") -> "RooflineTerms":
        cb = dict(self.coll_breakdown)
        for k, v in other.coll_breakdown.items():
            cb[k] = cb.get(k, 0) + v
        return RooflineTerms(self.flops + other.flops,
                             self.bytes_hbm + other.bytes_hbm,
                             self.bytes_coll + other.bytes_coll, cb)

    def as_dict(self):
        return {
            "flops_per_dev": self.flops,
            "hbm_bytes_per_dev": self.bytes_hbm,
            "coll_bytes_per_dev": self.bytes_coll,
            "coll_breakdown": self.coll_breakdown,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
        }


ZERO = RooflineTerms(0.0, 0.0, 0.0, {})


def cost_terms(compiled) -> RooflineTerms:
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    cb = collective_bytes(txt)
    return RooflineTerms(
        flops=float(ca.get("flops", 0.0)),
        bytes_hbm=float(ca.get("bytes accessed", 0.0)),
        bytes_coll=float(sum(cb.values())),
        coll_breakdown=cb,
    )


def memory_report(compiled) -> Optional[dict]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        if hasattr(ma, k):
            out[k] = int(getattr(ma, k))
    return out or None
