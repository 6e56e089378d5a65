"""Roofline-term derivation from AOT-compiled artifacts (EXPERIMENTS.md §Roofline).

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = wire_bytes / (chips * link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()`` (per-device SPMD module).
Collective wire bytes are parsed from the HLO text: every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op contributes
its operand size scaled by the ring-algorithm factor for its replica-group
size N (ag/rs/a2a: (N-1)/N, ar: 2(N-1)/N, cp: 1).

Hardware constants come from :data:`PEAKS`, keyed by the
``jax.devices()[0].device_kind`` string; a kind missing from the table is
an error, never a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

#: Published per-chip peaks, keyed by ``device_kind``: bf16 FLOP/s, HBM
#: bytes/s, and bytes/s per ICI link (the chip's interconnect total over
#: its four links).
PEAKS = {
    "TPU v5 lite": dict(
        flops=197e12, hbm_bw=819e9, link_bw=1600e9 / 8 / 4,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip "
               "interconnect"),
}

#: the chip the AOT dry-runs project onto (a v5e pod slice)
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,\s]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default_n: int) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota [G, N] <= [total]: N participants per group
        return int(m.group(2))
    return default_n


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float            # per device, algorithm-scaled
    raw_bytes: float             # per device, unscaled operand bytes
    counts: dict                 # op -> count

    def as_dict(self):
        return dict(wire_bytes=self.wire_bytes, raw_bytes=self.raw_bytes,
                    counts=self.counts)


def collective_bytes(hlo_text: str, default_group: int) -> CollectiveStats:
    wire = 0.0
    raw = 0.0
    counts: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        type_str, op = m.group(1), m.group(2)
        b = _shape_bytes(type_str)
        if b == 0:
            continue
        n = max(_group_size(line, default_group), 1)
        if op == "all-reduce":
            factor = 2.0 * (n - 1) / n
        elif op in ("all-gather", "reduce-scatter", "all-to-all"):
            factor = (n - 1) / n
        else:                                        # collective-permute
            factor = 1.0
        wire += b * factor
        raw += b
        counts[op] = counts.get(op, 0) + 1
    return CollectiveStats(wire, raw, counts)


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   wire_bytes_per_dev: float,
                   device_kind: str = TARGET_KIND) -> dict:
    pk = peaks(device_kind)
    ct = flops_per_dev / pk["flops"]
    mt = bytes_per_dev / pk["hbm_bw"]
    lt = wire_bytes_per_dev / pk["link_bw"]
    dom = max(("compute", ct), ("memory", mt), ("collective", lt),
              key=lambda kv: kv[1])
    total = max(ct, mt, lt)
    return dict(compute_s=ct, memory_s=mt, collective_s=lt,
                dominant=dom[0],
                roofline_fraction=(ct / total if total > 0 else 0.0))


def model_flops(cfg, seq: int, batch: int, kind: str) -> float:
    """MODEL_FLOPS: 6*N*D train, 2*N*D forward (D = tokens processed)."""
    n = cfg.active_param_count()
    tokens = seq * batch if kind != "decode" else batch
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens
