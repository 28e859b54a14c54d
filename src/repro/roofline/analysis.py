"""Roofline-term extraction from compiled XLA artifacts.

Three terms per (arch x shape x mesh), all in seconds-per-step on the
TARGET hardware (TPU v5e):

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_wire_bytes_per_device / link_bw

``cost_analysis()`` provides per-device FLOPs and bytes (the SPMD
partitioner emits a per-device program).  Collective bytes are NOT in
cost_analysis: we parse the compiled HLO text, find every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, and apply
a ring cost model using each op's replica-group size.
"""

from __future__ import annotations

import dataclasses
import json
import re
from dataclasses import dataclass, field

# --- TPU v5e hardware constants (per chip) ---
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_LINK_BW = 50e9             # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string (handles tuples)."""
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


def _shape_ndim(shape_str: str) -> int:
    nd = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dims = [d for d in m.group(2).split(",") if d]
        nd = max(nd, len(dims))
    return nd


def _is_f32(shape_str: str) -> bool:
    m = _SHAPE_RE.search(shape_str)
    return bool(m) and m.group(1) == "f32"


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].split("{")[-1]
        return max(1, len([x for x in first.split(",") if x.strip()]))
    return 2


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=dict)
    raw_bytes: dict = field(default_factory=dict)       # result-shape bytes
    wire_bytes: dict = field(default_factory=dict)      # ring-model bytes on the wire
    total_wire_bytes: float = 0.0
    act_wire_bytes: float = 0.0      # rank>=3 results: bf16 activations
                                     # promoted to f32 by the host backend

    def add(self, op: str, nbytes: int, gsize: int, mult: float = 1.0,
            ndim: int = 0):
        """nbytes is the RESULT-shape size from the HLO line.

        Ring wire cost per participant:
          all-reduce     result = full tensor      -> 2 (g-1)/g * result
          all-gather     result = gathered (big)   ->   (g-1)/g * result
          reduce-scatter result = scattered (small)->   (g-1)   * result
          all-to-all     result ~ input            ->   (g-1)/g * result
          collective-permute                       ->   result
        """
        self.counts[op] = self.counts.get(op, 0) + mult
        self.raw_bytes[op] = self.raw_bytes.get(op, 0) + nbytes * mult
        if op == "all-reduce":
            wire = 2.0 * (gsize - 1) / gsize * nbytes
        elif op == "reduce-scatter":
            wire = float(gsize - 1) * nbytes
        elif op in ("all-gather", "all-to-all"):
            wire = (gsize - 1) / gsize * nbytes
        else:  # collective-permute: point-to-point
            wire = float(nbytes)
        wire *= mult
        self.wire_bytes[op] = self.wire_bytes.get(op, 0.0) + wire
        self.total_wire_bytes += wire
        if ndim >= 3:
            self.act_wire_bytes += wire

    @property
    def tpu_wire_bytes(self) -> float:
        """TPU-target wire: rank>=3 f32 payloads are bf16 activations
        promoted to f32 by the host backend -> halve that share.
        Integer payloads (graph exchanges) are never promoted."""
        return self.total_wire_bytes - self.act_wire_bytes / 2.0


# header params may be tuples (nested parens): match greedily to '->'
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{")
_WHILE_RE = re.compile(
    r"while\(.*?\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_CALL_RE = re.compile(
    r"(?:calls|to_apply|body|condition|branches|true_computation|"
    r"false_computation|branch_computations)=\{?%?"
    r"([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)\}?")
_CONST_RE = re.compile(r"%?([\w\.\-]+)\s*=\s*s32\[\]\s*constant\((\d+)\)")
_COMPARE_RE = re.compile(r"compare\(%?([\w\.\-]+),\s*%?([\w\.\-]+)\)")


def _split_computations(hlo_text: str):
    """name -> (lines, is_entry)."""
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_HDR_RE.match(line.strip())
        if m and "{" in line:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def _trip_count(cond_lines: list[str]) -> int:
    consts = {}
    for line in cond_lines:
        m = _CONST_RE.search(line)
        if m:
            consts[m.group(1)] = int(m.group(2))
    for line in cond_lines:
        m = _COMPARE_RE.search(line)
        if m:
            for operand in m.groups():
                if operand in consts:
                    return max(1, consts[operand])
    if consts:
        return max(1, max(consts.values()))
    return 1


_BRANCHES_RE = re.compile(
    r"(?:branch_computations|branches)=\{?%?"
    r"([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)\}?")
_TF_RE = re.compile(r"true_computation=%?([\w\.\-]+),\s*"
                    r"false_computation=%?([\w\.\-]+)")
_PLAIN_CALL_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Collective stats with while-trip-count multipliers and
    worst-branch conditionals.

    Computes a per-computation cost bottom-up: a collective inside a
    while body counts trip_count times (nested whiles multiply); a
    conditional contributes its most expensive branch (only one branch
    executes per invocation).  This corrects XLA's body-once text dump
    the same way the jaxpr counter corrects cost_analysis() FLOPs.
    """
    comps, entry = _split_computations(hlo_text)
    stats = CollectiveStats()

    if entry is None:
        for line in hlo_text.splitlines():
            m = _COLL_RE.search(line)
            if m:
                stats.add(m.group("op"), _shape_bytes(m.group("shape")),
                          _group_size(line),
                          ndim=_shape_ndim(m.group("shape"))
                          if _is_f32(m.group("shape")) else 0)
        return stats

    memo: dict[str, dict] = {}

    def merge(into: dict, frm: dict, mult: float = 1.0):
        for op, (cnt, raw, wire, act) in frm.items():
            c0, r0, w0, a0 = into.get(op, (0.0, 0.0, 0.0, 0.0))
            into[op] = (c0 + cnt * mult, r0 + raw * mult, w0 + wire * mult,
                        a0 + act * mult)

    def cost(name: str, stack: tuple) -> dict:
        if name in memo:
            return memo[name]
        if name not in comps or name in stack:
            return {}
        stack = stack + (name,)
        out: dict = {}
        for line in comps[name]:
            cm = _COLL_RE.search(line)
            if cm:
                op = cm.group("op")
                nbytes = _shape_bytes(cm.group("shape"))
                g = _group_size(line)
                tmp = CollectiveStats()
                tmp.add(op, nbytes, g,
                        ndim=_shape_ndim(cm.group("shape"))
                        if _is_f32(cm.group("shape")) else 0)
                merge(out, {op: (tmp.counts[op], tmp.raw_bytes[op],
                                 tmp.wire_bytes[op], tmp.act_wire_bytes)})
                continue
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, []))
                merge(out, cost(body, stack), mult=trips)
                continue
            bm = _TF_RE.search(line) or _BRANCHES_RE.search(line)
            if bm and "conditional(" in line:
                names = [b for g_ in bm.groups() if g_
                         for b in re.split(r",\s*%?", g_)]
                branch_costs = [cost(b, stack) for b in names]
                if branch_costs:
                    worst = max(branch_costs,
                                key=lambda c: sum(v[2] for v in c.values()))
                    merge(out, worst)
                continue
            pm = _PLAIN_CALL_RE.search(line)
            if pm:
                merge(out, cost(pm.group(1), stack))
        memo[name] = out
        return out

    total = cost(entry, ())
    for op, (cnt, raw, wire, act) in total.items():
        stats.counts[op] = cnt
        stats.raw_bytes[op] = raw
        stats.wire_bytes[op] = wire
        stats.total_wire_bytes += wire
        stats.act_wire_bytes += act
    return stats


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    devices: int
    flops_per_device: float
    bytes_per_device: float
    collective_wire_bytes: float
    model_flops_total: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    useful_flops_ratio: float = 0.0
    peak_hbm_bytes: float = 0.0
    collectives: dict = field(default_factory=dict)

    def finalize(self):
        self.compute_s = self.flops_per_device / PEAK_FLOPS_BF16
        self.memory_s = self.bytes_per_device / HBM_BW
        self.collective_s = self.collective_wire_bytes / ICI_LINK_BW
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        total_hlo = self.flops_per_device * self.devices
        self.useful_flops_ratio = (
            self.model_flops_total / total_hlo if total_hlo else 0.0)
        return self

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE); D = tokens.

    Train counts fwd+bwd (the 6N convention); inference programs count
    forward only (2N per token).
    """
    n = cfg.params_active()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n * tokens


def analyze(compiled, *, arch: str, shape_name: str, mesh_name: str,
            devices: int, model_flops_total: float) -> Roofline:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    stats = parse_collectives(compiled.as_text())
    mem = compiled.memory_analysis()
    peak = float(mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
    r = Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, devices=devices,
        flops_per_device=flops, bytes_per_device=nbytes,
        collective_wire_bytes=stats.tpu_wire_bytes,
        model_flops_total=model_flops_total,
        peak_hbm_bytes=peak,
        collectives={
            "counts": stats.counts,
            "raw_bytes": stats.raw_bytes,
            "wire_bytes": stats.wire_bytes,
            "wire_bytes_f32_upper": stats.total_wire_bytes,
            "act_wire_bytes": stats.act_wire_bytes,
        },
    )
    return r.finalize()
