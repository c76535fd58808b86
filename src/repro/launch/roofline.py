"""Roofline-term derivation from compiled dry-run artifacts.

IMPORTANT calibration facts (verified empirically on this jax/XLA):
  * compiled.cost_analysis() reports flops/bytes of the POST-PARTITION
    per-device module — so terms divide by per-chip peaks, NOT by
    (chips x peak).
  * while-loop (lax.scan) bodies are counted ONCE regardless of trip
    count.  LM cells therefore go through launch/calibrate.py: two
    fully-unrolled small-depth compiles (L=2, L=4) give exact per-layer
    flops/bytes/collective-bytes, and the cell total is the affine
    extrapolation  nonscan + L * per_layer.  Decode/GNN/recsys cells
    unroll their layer loops in python — no correction needed.  The
    SSSP cells report PER-ROUND terms (round count is data-dependent).

Terms per (arch x shape x mesh), seconds per step on the chip whose
``device_kind`` keys :data:`PEAKS` (the dry-run targets a TPU v5e):

  compute    = flops_per_chip / peak FLOP/s   (bf16 MXU peak)
  memory     = bytes_per_chip / peak HBM bytes/s
  collective = coll_bytes_per_chip / ICI bytes/s per link

collective_bytes sums the OUTPUT shapes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute in the post-SPMD HLO
(conservative: wire traffic for an all-gather is output*(k-1)/k).
"""
from __future__ import annotations

import dataclasses
import re

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s ICI per chip (4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": dict(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
DRYRUN_KIND = "TPU v5 lite"   # the chip the dry-run cells compile for


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind (the CPU
    included) raises — a device number needs a device with a source."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[\d,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective in (post-SPMD) HLO text."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str = m.group(1) or m.group(2)
        op = m.group(3)
        out[op] += _shape_bytes(shape_str)
        out["count"] += 1
    out["total"] = sum(out[k] for k in
                       ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    return out


def cost_dict(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return cost


@dataclasses.dataclass
class RooflineTerms:
    """All *_per_chip quantities are for ONE device's program."""
    flops: float                 # per-chip, trip-count corrected
    bytes_accessed: float        # per-chip
    collective_bytes: float      # per-chip
    n_chips: int
    model_flops: float = 0.0     # analytic global 6ND-style
    raw_flops: float = 0.0       # uncorrected cost_analysis value
    correction: str = "none"

    @property
    def t_compute(self) -> float:
        return self.flops / peaks(DRYRUN_KIND)["flops"]

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / peaks(DRYRUN_KIND)["hbm_bw"]

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / peaks(DRYRUN_KIND)["ici_bw"]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / total HLO FLOPs — remat/redundancy waste."""
        tot = self.flops * self.n_chips
        return self.model_flops / tot if tot else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOPs MFU at the bound: what fraction of fleet peak the
        model's 6ND work achieves if the step runs at t_bound."""
        if not self.t_bound:
            return 0.0
        peak = peaks(DRYRUN_KIND)["flops"]
        return (self.model_flops / (self.n_chips * peak)) / self.t_bound

    def to_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops,
            "bytes_per_chip": self.bytes_accessed,
            "collective_bytes_per_chip": self.collective_bytes,
            "chips": self.n_chips, "model_flops": self.model_flops,
            "raw_flops": self.raw_flops, "correction": self.correction,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "t_bound_s": self.t_bound,
            "bottleneck": self.bottleneck,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def terms_from_compiled(compiled, n_chips: int, model_flops: float = 0.0,
                        hlo_text: str | None = None,
                        calibration: dict | None = None) -> RooflineTerms:
    """calibration (from launch/calibrate.py): exact per-layer deltas
    {flops,bytes,coll} plus nonscan base — overrides the raw counts."""
    cost = cost_dict(compiled)
    raw_flops = float(cost.get("flops", 0.0))
    byt = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collective_bytes(text)
    if calibration is not None:
        return RooflineTerms(
            flops=calibration["flops"], bytes_accessed=calibration["bytes"],
            collective_bytes=calibration["coll"], n_chips=n_chips,
            model_flops=model_flops, raw_flops=raw_flops,
            correction="two-point-unrolled")
    return RooflineTerms(
        flops=raw_flops, bytes_accessed=byt,
        collective_bytes=coll["total"], n_chips=n_chips,
        model_flops=model_flops, raw_flops=raw_flops)
