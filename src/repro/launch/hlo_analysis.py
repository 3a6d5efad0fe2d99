"""Roofline-term extraction from compiled dry-run artifacts.

``compiled.cost_analysis()`` supplies per-device HLO FLOPs and bytes.
Collective traffic is NOT in cost_analysis, so we parse the partitioned
HLO text and sum per-device wire bytes for every collective op, with ring
accounting:

  all-gather         : result bytes            (each device receives ~R)
  reduce-scatter     : operand bytes           (each device sends ~I)
  all-reduce         : 2 x operand bytes       (ring RS + AG)
  all-to-all         : operand bytes
  collective-permute : operand bytes

Shapes in the partitioned module are already per-shard, so sums are
per-device.  The hardware model is ``CHIP_PEAKS``, keyed by the running
device's ``device_kind``; a device not in it gets no bound.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    flops: float       # bf16 FLOP/s
    hbm_bw: float      # HBM bytes/s
    ici_bw: float      # chip-to-chip interconnect bytes/s
    source: str


# keyed by jax ``Device.device_kind``
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
        source="Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
               "bf16, 819 GB/s HBM, 1,600 Gbit/s ICI per chip"),
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|[sufc]\d+|bf16)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes per collective kind, from partitioned HLO."""
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.lstrip()
        # "%name = TYPE op-name(OPERANDS...)" — find which collective op
        kind = None
        for k in _COLLECTIVES:
            # match ` op-name(` or `op-name-start(` after the "=" result type
            if f" {k}(" in stripped or f" {k}-start(" in stripped:
                kind = k
                break
        if kind is None:
            continue
        shapes = _SHAPE_RE.findall(stripped)
        if not shapes:
            continue
        # first shape token = result; remaining (inside parens) = operands.
        result = _shape_bytes(*shapes[0])
        operands = sum(_shape_bytes(d, s) for d, s in shapes[1:]) or result
        if kind == "all-gather":
            out[kind] += result
        elif kind == "all-reduce":
            out[kind] += 2 * operands
        else:
            out[kind] += operands
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def count_collective_ops(hlo_text: str) -> Dict[str, int]:
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.lstrip()
        for k in _COLLECTIVES:
            if f" {k}(" in s or f" {k}-start(" in s:
                counts[k] += 1
                break
    return counts


@dataclasses.dataclass
class RooflineTerms:
    """Roofline of one program on one chip.  With ``peaks=None`` (a device
    ``CHIP_PEAKS`` does not list) every time is None and ``dominant`` is
    "not measured"."""

    device_flops: float
    device_bytes: float
    device_coll_bytes: float
    peaks: Optional[ChipPeaks]

    @property
    def t_compute(self) -> Optional[float]:
        return None if self.peaks is None else self.device_flops / self.peaks.flops

    @property
    def t_memory(self) -> Optional[float]:
        return None if self.peaks is None else self.device_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> Optional[float]:
        return (None if self.peaks is None
                else self.device_coll_bytes / self.peaks.ici_bw)

    @property
    def dominant(self) -> str:
        if self.peaks is None:
            return "not measured"
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> Optional[float]:
        """Roofline lower bound on step time (perfect overlap)."""
        if self.peaks is None:
            return None
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> Dict[str, float]:
        return {
            "device_flops": self.device_flops,
            "device_bytes": self.device_bytes,
            "device_coll_bytes": self.device_coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "t_bound_s": self.t_bound,
            "dominant": self.dominant,
        }


def analyze_compiled(compiled, lowered=None) -> Dict[str, object]:
    """Pull cost/memory/collective numbers out of a compiled executable.

    FLOPs/bytes/collective bytes come from the static HLO cost model
    (launch/hlo_cost.py) which multiplies while bodies by trip counts;
    ``compiled.cost_analysis()`` is recorded alongside for reference (it
    counts loop bodies once and therefore undercounts scanned stacks).
    """
    from . import hlo_cost
    info: Dict[str, object] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        info["xla_cost_analysis_flops"] = float(ca.get("flops", 0.0))
        info["xla_cost_analysis_bytes"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        info["cost_analysis_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
            v = getattr(ma, field, None)
            if v is not None:
                info[field] = int(v)
    except Exception as e:  # pragma: no cover
        info["memory_analysis_error"] = repr(e)
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text() if lowered is not None else ""
    model = hlo_cost.analyze_hlo_text(text)
    info["flops"] = model["flops"]
    info["bytes_accessed"] = model["bytes"]   # perfect-fusion floor
    info["bytes_xla_convention"] = model["bytes_xla_convention"]
    info["collective_bytes"] = dict(model["collective_bytes"])
    info["collective_bytes"]["total"] = model["collective_bytes_total"]
    info["collective_op_executions"] = model["collective_op_executions"]
    info["collective_ops"] = count_collective_ops(text)  # static op counts
    if "warnings" in model:
        info["hlo_cost_warnings"] = model["warnings"]
    return info


def roofline_from_info(info: Dict[str, object],
                       peaks: Optional[ChipPeaks]) -> RooflineTerms:
    return RooflineTerms(
        device_flops=float(info.get("flops", 0.0)),
        device_bytes=float(info.get("bytes_accessed", 0.0)),
        device_coll_bytes=float(info["collective_bytes"]["total"]),
        peaks=peaks,
    )
