"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh):
    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip, seconds)
    memory term     = HLO_bytes / HBM_bw               (per chip, seconds)
    collective term = collective_bytes / link_bw       (per chip, seconds)

``cost_analysis()`` on the SPMD-partitioned executable reports the
*per-device* program, so terms are per-chip directly. collective_bytes is
not in cost_analysis — we parse the optimized HLO and sum the output-buffer
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (one-pass per step; conservative single-link model).
"""
from __future__ import annotations

import dataclasses
import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# the chip the dry-run roofline terms are priced on
V5E = "TPU v5 lite"

# Published per-chip peaks, keyed by ``jax.devices()[i].device_kind``.
# Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
# system architecture page — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s chip-to-chip interconnect (4 links of 50 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {
        "peak_flops_bf16": 197e12,      # FLOP/s
        "hbm_bw": 819e9,                # bytes/s
        "ici_link_bw": 50e9,            # bytes/s per link
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind``; an unknown kind raises
    (there is no default chip)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-buffer bytes per collective kind from optimized HLO."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        # avoid double counting async -start/-done pairs: count -start only
        rhs = line.split("=", 1)[1]
        if f"{kind}-done" in rhs:
            continue
        out[kind] += _shape_bytes(shape_str)
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    collective_bytes_per_chip: float
    peak_mem_per_chip: float
    collectives: Dict[str, int] = field(default_factory=dict)
    model_flops: float = 0.0           # 6·N·D analytic (global)
    note: str = ""

    @property
    def compute_s(self) -> float:
        return self.flops_per_chip / peaks(V5E)["peak_flops_bf16"]

    @property
    def memory_s(self) -> float:
        return self.bytes_per_chip / peaks(V5E)["hbm_bw"]

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_chip / peaks(V5E)["ici_link_bw"]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (global HLO flops) — remat/redundancy waste."""
        total = self.flops_per_chip * self.chips
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(compute_s=self.compute_s, memory_s=self.memory_s,
                 collective_s=self.collective_s, dominant=self.dominant,
                 useful_flops_ratio=self.useful_flops_ratio)
        return d


def model_flops_estimate(cfg, ishape) -> float:
    """MODEL_FLOPS: 6·N·D for training (fwd+bwd), 2·N_active·D decode/prefill.
    N counts active params (MoE) excluding embeddings' lookup."""
    n = cfg.active_param_count()
    if ishape.kind == "train":
        tokens = ishape.global_batch * ishape.seq_len
        return 6.0 * n * tokens
    if ishape.kind == "prefill":
        tokens = ishape.global_batch * ishape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * ishape.global_batch  # decode: one token per request


def analyze(compiled, lowered_text: str, *, arch: str, shape: str,
            mesh_name: str, chips: int, cfg=None, ishape=None,
            note: str = "") -> Roofline:
    # while-aware coster (XLA cost_analysis counts scan bodies once;
    # see launch/hlo_cost.py) — terms from the compiled per-device program
    from repro.launch.hlo_cost import analyze_hlo
    cost = analyze_hlo(lowered_text)
    flops = cost.flops
    byts = cost.traffic
    colls = {k: int(v) for k, v in cost.per_collective.items()}
    peak = 0.0
    try:
        ma = compiled.memory_analysis()
        peak = float(getattr(ma, "temp_size_in_bytes", 0) +
                     getattr(ma, "argument_size_in_bytes", 0) +
                     getattr(ma, "output_size_in_bytes", 0) -
                     getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    mf = model_flops_estimate(cfg, ishape) if cfg is not None else 0.0
    return Roofline(arch, shape, mesh_name, chips, flops, byts,
                    float(sum(colls.values())), peak, colls, mf, note)


def format_table(rows: List[Roofline]) -> str:
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':10s} "
           f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>10s} "
           f"{'dominant':>10s} {'useful%':>8s} {'mem/chip':>10s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:22s} {r.shape:12s} {r.mesh:10s} "
            f"{r.compute_s:10.3e} {r.memory_s:10.3e} {r.collective_s:10.3e} "
            f"{r.dominant:>10s} {100*r.useful_flops_ratio:8.1f} "
            f"{r.peak_mem_per_chip/2**30:9.2f}G")
    return "\n".join(lines)
