"""Roofline terms from a compiled (dry-run) executable.

  compute    = HLO_FLOPs_total / (chips × peak)
  memory     = HLO_bytes_total / (chips × HBM_bw)
  collective = wire_bytes_per_chip / link_bw

Sources: ``compiled.cost_analysis()`` (flops + bytes of the per-device
partitioned module — multiplied back to totals), and the collective ops parsed
out of ``compiled.as_text()``.  Wire-byte factors per algorithm (ring):
all-reduce 2·(n−1)/n · |shard|, all-gather/reduce-scatter (n−1)/n · |full|,
all-to-all (n−1)/n, collective-permute 1.  MODEL_FLOPS = 6·N·D (2·N·D for a
decode token) gives the useful-fraction ratio.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

#: Per-chip peaks, keyed by ``jax.Device.device_kind``.  Source: Google Cloud
#: TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; an unknown device kind is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None


_COLLECTIVE_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)

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


def collective_bytes_from_hlo(hlo_text: str, *, replica_groups_default: int = 8
                              ) -> Dict[str, float]:
    """Wire bytes per device, by collective kind, with ring-algorithm factors.
    The result-shape bytes are used as |payload| (per-device output)."""
    seen_done = set()
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts: Dict[str, int] = {k: 0 for k in out}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        name, shape_str, kind = m.group(1), m.group(2), m.group(3)
        if name.endswith(".done") or "-done" in hlo_text[m.start():m.end()]:
            pass
        if name in seen_done:
            continue
        seen_done.add(name)
        payload = _shape_bytes(shape_str)
        if payload == 0:
            continue
        # group size from the replica_groups annotation on this line if present
        line_end = hlo_text.find("\n", m.start())
        line = hlo_text[m.start(): line_end if line_end > 0 else None]
        n = replica_groups_default
        gm = re.search(r"replica_groups=\{\{([^}]*)\}", line)
        if gm:
            n = max(2, gm.group(1).count(",") + 1)
        else:
            gm2 = re.search(r"replica_groups=\[\d+,(\d+)\]", line)
            if gm2:
                n = max(2, int(gm2.group(1)))
        if kind == "all-reduce":
            wire = 2.0 * (n - 1) / n * payload
        elif kind in ("all-gather", "reduce-scatter", "all-to-all"):
            wire = (n - 1) / n * payload
        else:  # collective-permute
            wire = float(payload)
        out[kind] += wire
        counts[kind] += 1
    out["_counts"] = counts  # type: ignore
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_total: float
    hlo_bytes_total: float
    collective_bytes_per_chip: float
    collective_breakdown: Dict[str, float]
    model_flops: float
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_fraction(self) -> float:
        return self.model_flops / self.hlo_flops_total if self.hlo_flops_total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """compute-term share of the critical path ≈ achievable MFU bound,
        scaled by useful flops."""
        crit = max(self.compute_s, self.memory_s, self.collective_s)
        if crit <= 0:
            return 0.0
        return (self.model_flops / self.hlo_flops_total) * (self.compute_s / crit)

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant, useful_fraction=self.useful_fraction,
                 roofline_fraction=self.roofline_fraction)
        return d


def roofline_terms(*, arch: str, shape: str, mesh_name: str, chips: int,
                   cost: Dict[str, float], hlo_text: str, model_flops: float,
                   device_kind: str) -> RooflineReport:
    """cost = compiled.cost_analysis() of the PER-DEVICE partitioned module;
    ``device_kind`` names the chip whose peaks bound the terms."""
    peaks = peaks_for(device_kind)
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes_from_hlo(hlo_text)
    counts = coll.pop("_counts", {})
    coll_dev = sum(coll.values())
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops_total=flops_dev * chips,
        hlo_bytes_total=bytes_dev * chips,
        collective_bytes_per_chip=coll_dev,
        collective_breakdown={**coll, "counts": counts},
        model_flops=model_flops,
        compute_s=flops_dev / peaks["flops"],
        memory_s=bytes_dev / peaks["hbm_bw"],
        collective_s=coll_dev / peaks["link_bw"],
    )


def _attention_layer_counts(cfg):
    """(n_full_attn_layers, n_window_layers) for cache-flop accounting."""
    if cfg.family == "ssm":
        return 0, 0
    if cfg.family == "hybrid":
        return len(cfg.hybrid_ids), 0
    if cfg.attn_pattern == "swa":
        return 0, cfg.n_layers
    if cfg.attn_pattern == "local_global":
        g = cfg.local_per_global + 1
        G = cfg.n_layers // g
        return G, cfg.n_layers - G
    n = cfg.n_layers + (cfg.encoder_layers if cfg.family == "encdec" else 0)
    return n, 0


def model_flops_for(cfg, shape) -> float:
    """Useful model FLOPs: 6·N·D (train) / 2·N·D (prefill); decode adds the
    attention-over-cache term 4·B·H·hd·C per layer (2·N·1 alone ignores the
    dominant per-token work at 32k-500k contexts)."""
    n_active = cfg.active_param_count()
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * S * B
    if shape.kind == "prefill":
        return 2.0 * n_active * S * B
    base = 2.0 * n_active * B
    n_full, n_win = _attention_layer_counts(cfg)
    qdim = cfg.n_heads * cfg.head_dim
    attn = 4.0 * B * qdim * (n_full * S + n_win * min(cfg.window or S, S))
    return base + attn
