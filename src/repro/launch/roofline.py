"""Roofline-term derivation from compiled dry-run artifacts.

This container is CPU-only: TPU v5e is the *target*, so wall-clock MFU cannot
be measured.  Instead we derive, per (arch x shape x mesh):

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s          [s]
    memory term     = HLO_bytes_per_chip / HBM_bw               [s]
    collective term = collective_bytes_per_chip / link_bw       [s]

HLO_FLOPs / HLO_bytes / collective bytes come from the trip-count-scaled HLO
cost model (launch/hlo_cost.py) over ``compiled.as_text()`` — XLA's
``cost_analysis()`` counts while-loop bodies once, which would undercount
scanned layer stacks by ~num_layers x (its raw values are kept in
``extra["xla_cost_analysis"]``).  Collective wire bytes apply an algorithmic
factor (ring all-reduce moves ~2x the payload; the others ~1x).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

import numpy as np

from repro.launch import mesh as mesh_lib

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)
# bytes-on-the-wire multiplier per collective algorithm (ring)
_WIRE_FACTOR = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# result shapes may be tuples containing /*index=N*/ comments; capture
# everything between '=' and the op name (operands are %-prefixed, so an op
# name appearing as an operand never matches "<ws>op-name(").
_OP_RE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-kind {count, result_bytes, wire_bytes} + totals, per device."""
    out = {k: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0} for k in COLLECTIVE_KINDS}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        out[kind]["count"] += 1
        out[kind]["result_bytes"] += b
        out[kind]["wire_bytes"] += b * _WIRE_FACTOR[kind]
    total_wire = sum(v["wire_bytes"] for v in out.values())
    total_result = sum(v["result_bytes"] for v in out.values())
    return {"by_kind": out, "wire_bytes": total_wire, "result_bytes": total_result}


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    step_kind: str  # train | prefill | decode | consensus
    flops_per_chip: float
    hbm_bytes_per_chip: float
    coll_wire_bytes_per_chip: float
    coll_breakdown: dict
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_per_chip: float
    useful_flop_ratio: float
    param_bytes_per_chip: float
    arg_bytes: float
    temp_bytes: float
    extra: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def build_report(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    step_kind: str,
    cost: dict,
    memstats,
    hlo_text: str,
    model_flops_total: float,
    param_bytes_total: float,
    extra: Optional[dict] = None,
) -> Roofline:
    # xla's cost_analysis counts while bodies ONCE; use the trip-count-scaled
    # HLO cost model instead (see launch/hlo_cost.py), keeping the raw
    # cost_analysis values in `extra` for reference.
    from repro.launch import hlo_cost as hlo_cost_lib

    hc = hlo_cost_lib.analyze(hlo_text)
    flops = float(hc.flops)
    hbm_bytes = float(hc.bytes_accessed)
    colls = {
        "by_kind": {
            k: {"count": v["count"], "result_bytes": 0, "wire_bytes": v["wire_bytes"]}
            for k, v in hc.coll_by_kind.items()
        },
        "wire_bytes": hc.coll_wire_bytes,
    }
    wire = float(colls["wire_bytes"])
    extra = dict(extra or {})
    extra["xla_cost_analysis"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    extra["loop_multipliers"] = {
        k: v for k, v in sorted(hc.loop_info.items(), key=lambda kv: -kv[1])[:8]
    }

    compute_s = flops / mesh_lib.PEAK_FLOPS_BF16
    memory_s = hbm_bytes / mesh_lib.HBM_BW
    collective_s = wire / mesh_lib.ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)

    model_flops_per_chip = model_flops_total / chips
    useful = model_flops_per_chip / flops if flops else 0.0

    arg_bytes = float(getattr(memstats, "argument_size_in_bytes", 0) or 0)
    temp_bytes = float(getattr(memstats, "temp_size_in_bytes", 0) or 0)

    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        step_kind=step_kind,
        flops_per_chip=flops,
        hbm_bytes_per_chip=hbm_bytes,
        coll_wire_bytes_per_chip=wire,
        coll_breakdown={
            k: v for k, v in colls["by_kind"].items() if v["count"]
        },
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_per_chip=model_flops_per_chip,
        useful_flop_ratio=useful,
        param_bytes_per_chip=param_bytes_total / chips,
        arg_bytes=arg_bytes,
        temp_bytes=temp_bytes,
        extra=extra or {},
    )


def model_flops(cfg, shape_cfg, *, peers: int = 1) -> float:
    """MODEL_FLOPS: 6*N*D train (fwd+bwd), 2*N*D decode/prefill (fwd only);
    N = active params (MoE), D = tokens processed this step (all peers)."""
    n_active = cfg.active_param_count()
    if shape_cfg.kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * n_active * tokens
    if shape_cfg.kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence (global_batch tokens), at least `peers`
    tokens = max(shape_cfg.global_batch, peers)
    return 2.0 * n_active * tokens


def fmt_seconds(s: float) -> str:
    if s == 0:
        return "0"
    if s < 1e-6:
        return f"{s*1e9:.1f}ns"
    if s < 1e-3:
        return f"{s*1e6:.1f}us"
    if s < 1:
        return f"{s*1e3:.2f}ms"
    return f"{s:.2f}s"


def markdown_table(reports: list[Roofline]) -> str:
    hdr = (
        "| arch | shape | mesh | step | compute | memory | collective | dominant "
        "| useful FLOP ratio | params/chip | coll GiB/chip |\n"
        "|---|---|---|---|---|---|---|---|---|---|---|\n"
    )
    rows = []
    for r in reports:
        rows.append(
            f"| {r.arch} | {r.shape} | {r.mesh} | {r.step_kind} "
            f"| {fmt_seconds(r.compute_s)} | {fmt_seconds(r.memory_s)} "
            f"| {fmt_seconds(r.collective_s)} | **{r.dominant}** "
            f"| {r.useful_flop_ratio:.2f} | {r.param_bytes_per_chip/2**30:.2f} GiB "
            f"| {r.coll_wire_bytes_per_chip/2**30:.3f} |"
        )
    return hdr + "\n".join(rows) + "\n"


def save_reports(path: str, reports: list[Roofline]) -> None:
    with open(path, "w") as f:
        json.dump([r.to_dict() for r in reports], f, indent=1)


def load_reports(path: str) -> list[dict]:
    with open(path) as f:
        return json.load(f)
