"""Roofline terms from a compiled dry-run artifact.

    compute   = HLO_FLOPs(per device) / peak_FLOP/s
    memory    = HLO_bytes(per device) / HBM_bw
    collective = collective_bytes(per device) / link_bw

The peaks are those of the target chip (``hw.peaks(device_kind)``).

FLOPs/bytes come from ``compiled.cost_analysis()`` (the post-SPMD
per-device module, so no ×chips needed).  Collective bytes are parsed
from the optimized HLO text: the summed result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (cost_analysis does not expose them).
MODEL_FLOPS uses 6·N·D (train) or 2·N·D (inference), N = active params.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional, Tuple

import numpy as np

from . import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# one result shape, possibly inside a tuple: bf16[4,512,128]{2,1,0}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes per collective kind over the optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["total"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls or ls.startswith("//"):
            continue
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.*)$", ls)
        if m is None:
            continue
        rhs = m.group(1)
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done(" in rhs:
            continue  # counted at -start
        # result type is everything before the op name
        head = rhs.split(f" {kind}", 1)[0]
        nbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
        out[kind] += nbytes
        out["total"] += nbytes
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collectives: Dict[str, int]
    model_flops_global: float
    n_active_params: int
    device_kind: str
    peak_memory_per_device: Optional[float] = None
    scopes_flops: Dict[str, float] = dataclasses.field(default_factory=dict)
    scopes_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    scopes_coll: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def peaks(self) -> hw.ChipPeaks:
        return hw.peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peaks.bf16_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def useful_flops_ratio(self, n_chips: int) -> float:
        hlo_global = self.flops_per_device * n_chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    def mfu_bound(self, n_chips: int) -> float:
        """Model-FLOPs utilization if the dominant term were the wall
        clock: MODEL_FLOPS / (t_bound · chips · peak)."""
        denom = self.t_bound * n_chips * self.peaks.bf16_flops
        return self.model_flops_global / denom if denom else 0.0

    def to_dict(self, n_chips: int) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "device_kind": self.device_kind,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collectives": self.collectives,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops_global": self.model_flops_global,
            "useful_flops_ratio": self.useful_flops_ratio(n_chips),
            "mfu_bound": self.mfu_bound(n_chips),
            "peak_memory_per_device": self.peak_memory_per_device,
            "n_chips": n_chips,
            "scopes_flops": self.scopes_flops,
            "scopes_bytes": self.scopes_bytes,
            "scopes_coll": self.scopes_coll,
        }


def model_flops(cfg, shape, n_active: int) -> float:
    """6·N·D for training, 2·N·D for inference forward passes."""
    if shape.kind == "train":
        D = shape.global_batch * shape.seq_len
        return 6.0 * n_active * D
    if shape.kind == "prefill":
        D = shape.global_batch * shape.seq_len
        return 2.0 * n_active * D
    D = shape.global_batch * 1
    return 2.0 * n_active * D


def analyze(compiled, cfg, shape, mesh_name: str, n_chips: int,
            n_active: int, *, device_kind: str) -> Roofline:
    """Roofline terms via the trip-count-aware HLO walker (see
    ``hlo_walk``; raw ``cost_analysis`` counts while bodies once), over
    the peaks of ``device_kind`` (the chip the program targets)."""
    hw.peaks(device_kind)
    from . import hlo_walk
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    costs = hlo_walk.walk(hlo)
    flops = float(costs.flops)
    nbytes = float(costs.bytes)
    coll = {k: int(v) for k, v in costs.coll.items()}
    for k in _COLLECTIVES:
        coll.setdefault(k, 0)
    coll.setdefault("total", 0)
    peak_mem = None
    try:
        ma = compiled.memory_analysis()
        peak_mem = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    roof = Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name,
        flops_per_device=flops, bytes_per_device=nbytes,
        collective_bytes_per_device=float(coll["total"]),
        collectives={k: int(v) for k, v in coll.items()},
        model_flops_global=model_flops(cfg, shape, n_active),
        n_active_params=n_active, device_kind=device_kind,
        peak_memory_per_device=peak_mem)
    roof.scopes_flops = dict(hlo_walk.top_scopes(costs.by_scope_flops))
    roof.scopes_bytes = dict(hlo_walk.top_scopes(costs.by_scope_bytes))
    roof.scopes_coll = dict(hlo_walk.top_scopes(costs.by_scope_coll))
    return roof
