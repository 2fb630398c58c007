"""Compiled-HLO analysis: collective-bytes extraction and the three-term
roofline (compute / memory / collective) for TPU v5e targets.

collective_bytes is not in cost_analysis(); we parse the compiled module
text and sum the output-operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op (tuple outputs
included).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link (approx; per spec)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.   %x = f32[64,512]{1,0} all-reduce(...)
#        %y = (f32[8,4]{...}, f32[8,4]{...}) all-gather(...)
_OP_LINE = re.compile(
    r"=\s*(\(?[^=]*?)\s*(" + "|".join(_COLLECTIVES)
    + r")(?:-(?:start|done))?\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of collective output bytes per op kind, over the whole module.

    ``-start`` variants counted, ``-done`` skipped (same transfer)."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _OP_LINE.search(line)
        if not m:
            continue
        kind = m.group(2)
        type_str = m.group(1)
        total = sum(_shape_bytes(d, s) for d, s in _SHAPE.findall(type_str))
        out[kind] += total
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    """Three-term roofline for one compiled step on one mesh.

    NOTE on units: XLA SPMD emits one per-device program, and both
    cost_analysis() and the parsed HLO shapes are **per-device** numbers.
    The roofline terms therefore divide by per-chip rates only (this is
    algebraically identical to the spec's global_FLOPs/(chips*peak) form,
    since global = per_device * chips for SPMD); ``model_flops`` is global
    and is normalised by n_chips where compared."""
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device HLO bytes accessed
    coll_bytes: float            # per-device collective payload bytes
    n_chips: int
    model_flops: float = 0.0     # GLOBAL 6*N*D (6*N_active*D for MoE)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (per-device-normalised) — remat and
        redundancy waste detector (< 1 means HLO does extra work)."""
        if not self.flops:
            return 0.0
        return (self.model_flops / self.n_chips) / self.flops

    @property
    def mfu_bound(self) -> float:
        """Upper bound on MFU implied by the dominant roofline term."""
        if self.t_bound <= 0:
            return 0.0
        per_dev_useful_t = (self.model_flops / self.n_chips) / PEAK_FLOPS
        return per_dev_useful_t / self.t_bound

    def row(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu_bound,
        }


def roofline_from_compiled(compiled, n_chips: int,
                           model_flops: float = 0.0,
                           hlo_text: Optional[str] = None) -> Roofline:
    ca = compiled.cost_analysis() or {}
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(txt)
    return Roofline(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=float(coll["total"]),
        n_chips=n_chips,
        model_flops=model_flops,
    )
