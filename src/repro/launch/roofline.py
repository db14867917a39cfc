"""Roofline terms from a compiled dry-run artifact (see EXPERIMENTS.md §Roofline).

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

FLOPs / bytes / collective bytes come from launch/hlo_analysis.py (per-device,
while-loop aware). Hardware peaks: common/hw.py, looked up by device kind —
the caller names the chip; there is no default.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.common.hw import HwSpec
from repro.launch.hlo_analysis import HloCost, analyze_hlo


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device quantities
    flops: float
    hbm_bytes: float
    coll_bytes: float
    collectives: Dict[str, Dict[str, float]]
    # seconds
    compute_s: float
    memory_s: float
    collective_s: float
    # accounting
    model_flops: float  # 6·N_active·D (global)
    useful_ratio: float  # MODEL_FLOPS / (flops × chips)
    # memory_analysis
    bytes_per_device: Optional[float] = None
    argument_bytes: Optional[float] = None

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """No-overlap upper bound (sum) — we report terms separately too."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def row(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_dev": self.flops,
            "hbm_bytes_per_dev": self.hbm_bytes,
            "coll_bytes_per_dev": self.coll_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "bytes_per_device": self.bytes_per_device,
            "collectives": self.collectives,
        }


def roofline_from_compiled(
    compiled,
    arch: str,
    shape_name: str,
    mesh_name: str,
    chips: int,
    model_flops: float,
    *,
    hw: HwSpec,
    hlo_cost: Optional[HloCost] = None,
) -> Roofline:
    cost = hlo_cost or analyze_hlo(compiled.as_text(), total_devices=chips)
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        pass
    bytes_per_dev = None
    arg_bytes = None
    if ma is not None:
        arg_bytes = float(ma.argument_size_in_bytes)
        bytes_per_dev = float(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
        )
    flops_global = cost.flops * chips
    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        flops=cost.flops,
        hbm_bytes=cost.hbm_bytes,
        coll_bytes=cost.coll_bytes,
        collectives={
            k: {"count": c.count, "ici_bytes": c.bytes, "shard_bytes": c.raw_bytes}
            for k, c in cost.collectives.items()
        },
        compute_s=cost.flops / hw.peak_bf16_flops,
        memory_s=cost.hbm_bytes / hw.hbm_bandwidth,
        collective_s=cost.coll_bytes / hw.ici_link_bandwidth,
        model_flops=model_flops,
        useful_ratio=model_flops / flops_global if flops_global else 0.0,
        bytes_per_device=bytes_per_dev,
        argument_bytes=arg_bytes,
    )


def format_row(r: Roofline) -> str:
    gb = (r.bytes_per_device or 0) / 2**30
    return (
        f"{r.arch:24s} {r.shape:12s} {r.mesh:9s} "
        f"cmp {r.compute_s*1e3:9.3f}ms mem {r.memory_s*1e3:9.3f}ms "
        f"coll {r.collective_s*1e3:9.3f}ms -> {r.dominant:10s} "
        f"useful {r.useful_ratio:6.1%} {gb:6.2f}GiB/dev"
    )
