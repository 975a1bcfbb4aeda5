"""Roofline-term derivation from AOT-compiled artifacts.

Three terms per (arch x shape x mesh), in seconds, from the peaks of
the target chip (`benchmarks/peaks.json`, keyed by `device_kind`; the
dry-run targets TPU v5e: 197 TFLOP/s, 819 GB/s, 50 GB/s per link):

    compute    = HLO_FLOPs / (chips * peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips * HBM bytes/s)
    collective = collective_link_bytes / (chips * ICI bytes/s per link)

`cost_analysis()` on an SPMD-partitioned executable reports *per-partition*
numbers, so chips-normalization is already done for compute/memory; we
multiply back where totals are reported (documented per-field below).

collective bytes are parsed from the compiled HLO text: we sum the result
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops, weighting all-reduce 2x (ring all-reduce moves
~2x the payload per device: reduce-scatter + all-gather phases).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
import re
from typing import Any, Dict, Optional

# the one peak table, kept with the benchmarks
PEAKS_FILE = (pathlib.Path(__file__).resolve().parents[3]
              / "benchmarks" / "peaks.json")
# `device_kind` of a TPU v5e chip: the chip the dry-run compiles for
V5E = "TPU v5 lite"


@functools.cache
def _peak_table() -> Dict[str, Any]:
    return json.loads(PEAKS_FILE.read_text())


def device_peaks(kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of `kind` (a `Device.device_kind`).
    Raises KeyError for a kind the table does not list."""
    table = _peak_table()
    if kind.startswith("_") or kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{PEAKS_FILE}")
    return table[kind]


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_WEIGHT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind link bytes (per device) from HLO text."""
    out: Dict[str, float] = {k: 0.0 for k in _WEIGHT}
    out["count"] = 0
    for m in _COLL_RE.finditer(hlo_text):
        tuple_part, dtype, dims, kind = m.groups()
        if tuple_part is not None:
            nbytes = sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(tuple_part))
        else:
            nbytes = _shape_bytes(dtype, dims)
        out[kind] += nbytes * _WEIGHT[kind]
        out["count"] += 1
    out["total"] = sum(v for k, v in out.items()
                       if k in _WEIGHT)
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_count: int
    chips: int
    peak_memory_per_device: Optional[float] = None

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / device_peaks(V5E)["bf16_flops"]

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / device_peaks(V5E)["hbm_bytes_per_s"]

    @property
    def collective_s(self) -> float:
        return (self.collective_bytes_per_device
                / device_peaks(V5E)["ici_link_bytes_per_s"])

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collective_count": self.collective_count,
            "chips": self.chips,
            "peak_memory_per_device": self.peak_memory_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def analyze(compiled, chips: int) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    coll = parse_collective_bytes(compiled.as_text())
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                    + ma.output_size_in_bytes)
    except Exception:
        pass
    return Roofline(
        flops_per_device=flops,
        bytes_per_device=nbytes,
        collective_bytes_per_device=coll["total"],
        collective_count=int(coll["count"]),
        chips=chips,
        peak_memory_per_device=mem,
    )


def model_flops_per_step(cfg, tokens: int, active_params: int) -> float:
    """MODEL_FLOPS = 6 * N(_active) * D tokens (train fwd+bwd);
    2*N*D for inference-only steps."""
    return 6.0 * active_params * tokens


def active_param_count(cfg, params_total: int) -> int:
    """MoE: only top_k(+shared) experts are active per token."""
    if not cfg.moe:
        return params_total
    # expert params: E * (3 * d * f) per layer
    expert = cfg.num_layers * cfg.num_experts * 3 * cfg.d_model * cfg.d_ff
    active_expert = (cfg.num_layers
                     * (cfg.top_k + cfg.num_shared_experts)
                     * 3 * cfg.d_model * cfg.d_ff)
    return params_total - expert + active_expert
