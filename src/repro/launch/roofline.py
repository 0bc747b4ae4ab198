"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model: the per-chip peaks of the device the program targets, keyed
by ``device_kind`` as JAX reports it (`PEAKS`). A device that is not in the
table is an error, not a default.

Terms (per step, in seconds):
    compute    = HLO_FLOPs_total    / (chips * peak_flops)
    memory     = HLO_bytes_total    / (chips * hbm_bw)
    collective = collective_bytes   / (chips * ici_bw)

`cost_analysis()` on an SPMD-compiled executable reports *per-device*
numbers; we multiply by `chips` to get totals, so the two conventions
cancel and the terms above are just per_device / peak. collective_bytes is
parsed from the post-optimization HLO text (sum of result-shape bytes of
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute).
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops: float   # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # inter-chip bytes/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of inter-chip interconnect over 4 links (50 GB/s each).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """Published per-chip peaks of `device_kind`; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every dtype[shape] literal in `text`."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes from post-optimization HLO."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        # result lines look like: "%name = bf16[..] all-reduce(", or start
        # directly with the shape for top-level instructions
        m = re.search(r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]))\s*([a-z0-9-]+)\(", line)
        if not m:
            continue
        shape_txt, op = m.group(1), m.group(2)
        # normalize op names like all-reduce-start / all-gather-done
        base = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-"):
                base = k
                break
        if base is None:
            continue
        if op.endswith("-done"):
            continue  # avoid double counting start/done pairs
        out[base] += _shape_bytes(shape_txt)
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    device_kind: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    model_flops: float  # 6*N(_active)*D tokens-based estimate

    def __post_init__(self):
        self.peaks = peaks_for(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved if the step ran at the
        bound: useful_FLOPs / (chips * peak * max_term)."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t == 0:
            return 0.0
        return self.model_flops / (self.chips * self.peaks.flops * t)

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(
            t_compute=self.t_compute,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            bottleneck=self.bottleneck,
            useful_flops_ratio=self.useful_flops_ratio,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def model_flops(cfg, seq_len: int, global_batch: int, kind: str) -> float:
    """6·N·D (train) / 2·N·D (forward-only) with N = active params."""
    n = cfg.param_count(active_only=True)
    tokens = seq_len * global_batch if kind != "decode" else global_batch
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens
