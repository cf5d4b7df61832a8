"""Three-term roofline model from compiled dry-run artifacts.

TPU v5e-class constants (per chip):
    197 TFLOP/s bf16 | 819 GB/s HBM | ~50 GB/s/link ICI

Terms (seconds, per step, per chip — HLO under SPMD is the per-device
program, so cost_analysis numbers are already per-chip):
    compute    = HLO_FLOPs / peak_flops
    memory     = HLO_bytes / hbm_bw
    collective = collective_operand_bytes / ici_bw
"""
from __future__ import annotations

from dataclasses import dataclass, asdict
from typing import Dict, Optional

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link


@dataclass
class Roofline:
    name: str
    chips: int
    hlo_flops: float          # per-chip program flops
    hlo_bytes: float          # per-chip bytes accessed
    coll_bytes: float         # per-chip collective operand bytes
    model_flops: float        # 6ND-style useful flops (GLOBAL)
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bound: str = ""
    useful_ratio: float = 0.0  # model_flops / (hlo_flops * chips)
    step_s: float = 0.0        # max of the three terms
    roofline_frac: float = 0.0  # useful compute time / bound term

    def finalize(self) -> "Roofline":
        self.compute_s = self.hlo_flops / PEAK_FLOPS
        self.memory_s = self.hlo_bytes / HBM_BW
        self.collective_s = self.coll_bytes / ICI_BW
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bound = max(terms, key=terms.get)
        self.step_s = terms[self.bound]
        total_hlo = self.hlo_flops * self.chips
        self.useful_ratio = (self.model_flops / total_hlo) if total_hlo \
            else 0.0
        ideal = self.model_flops / (self.chips * PEAK_FLOPS)
        self.roofline_frac = ideal / self.step_s if self.step_s else 0.0
        return self

    def to_dict(self) -> Dict:
        return asdict(self)


def from_compiled(name: str, compiled, mesh, model_flops: float,
                  hlo_text: Optional[str] = None) -> Roofline:
    from repro.analysis.hlo_parse import collective_bytes
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    return Roofline(
        name=name, chips=int(mesh.devices.size), hlo_flops=flops,
        hlo_bytes=byt, coll_bytes=float(coll.get("total", 0)),
        model_flops=model_flops).finalize()


def memory_summary(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for f in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = float(v)
    args = out.get("argument_size_in_bytes", 0.0)
    alias = out.get("alias_size_in_bytes", 0.0)
    temp = out.get("temp_size_in_bytes", 0.0)
    outb = out.get("output_size_in_bytes", 0.0)
    # peak live bytes per device ~ args + temps + (outputs not aliased)
    out["peak_bytes_est"] = args + temp + max(outb - alias, 0.0)
    return out
