"""Roofline-term derivation from a compiled dry-run artifact.

Per (arch x shape x mesh):
    compute    = HLO_FLOPs / (chips * PEAK_FLOPS)
    memory     = HLO_bytes / (chips * HBM_BW)
    collective = collective_bytes / (chips * link_BW * links)

``compiled.cost_analysis()`` counts a `while` body ONCE, so scan-over-layers
programs undercount by ~num_layers. We therefore parse the optimized HLO into
a computation graph, recover loop trip counts from each while-condition's
`s32[] constant(K)`, propagate a multiplier through `body=` / `calls=` /
`to_apply=` edges, and then:

  * FLOPs  — sum 2 * out_elems * contracted_elems over every `dot`, scaled.
  * bytes  — static traffic model: for every op in a *memory-level*
    computation (ENTRY, while bodies/conds, conditional branches — NOT inside
    fusion bodies) sum output + operand bytes, scaled. Fusions count at their
    call site, so fused elementwise chains count once. This over-approximates
    post-fusion HBM traffic slightly but is consistent across variants, which
    is what the perf hillclimb needs.
  * collective bytes — operand bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, scaled; split ICI vs
    DCI by whether the replica group crosses a 256-chip pod boundary.

Everything is per *program*; divide by chips for per-chip seconds.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

from repro.launch import mesh as meshmod

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
# Ops counted by the static HBM-traffic model. Raw elementwise / broadcast /
# convert ops are EXCLUDED: on TPU they fuse into neighbours (the CPU backend
# leaves them at top level, which would overstate traffic ~10x). `fusion` ops
# count their operands+outputs once, which is exactly the fused-kernel model.
_TRAFFIC_OPS = {
    "fusion", "dot", "convolution", "copy", "transpose", "reduce",
    "reduce-window", "dynamic-slice", "dynamic-update-slice", "slice",
    "concatenate", "pad", "reverse", "gather", "scatter", "sort",
    "select-and-scatter", "all-gather", "all-reduce", "reduce-scatter",
    "all-to-all", "collective-permute",
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPLINE_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_HEAD_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(")


def shape_elems(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        if m.group(1) not in _DTYPE_BYTES:
            continue
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += n
    return total


def shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Op:
    name: str
    out: str       # output type string (may be a tuple)
    opcode: str
    operands: list[str]
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: dict[str, Op]
    is_entry: bool = False


def parse_hlo(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in text.splitlines():
        if not raw.strip():
            continue
        if not raw.startswith(" ") and raw.rstrip().endswith("{"):
            m = _HEAD_RE.match(raw.strip())
            if m:
                cur = Computation(m.group(2), {}, is_entry=bool(m.group(1)))
                comps[cur.name] = cur
            continue
        if raw.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _OPLINE_RE.match(raw)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        # rest = TYPE opcode(args...), attrs...   TYPE may be a (tuple, type)
        # possibly containing /*index=N*/ comments — scan balanced parens.
        if rest.startswith("("):
            depth, j = 1, 1
            while j < len(rest) and depth:
                if rest[j] == "(":
                    depth += 1
                elif rest[j] == ")":
                    depth -= 1
                j += 1
            out_t = rest[:j]
            tail = rest[j:].lstrip()
        else:
            sp = rest.find(" ")
            if sp < 0:
                continue
            out_t = rest[:sp]
            tail = rest[sp + 1 :].lstrip()
        om = re.match(r"([\w\-]+)\(", tail)
        if not om:
            continue
        opcode = om.group(1)
        rest = tail
        # operand list: up to matching close paren
        start = om.end()
        depth = 1
        i = start
        while i < len(rest) and depth:
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
            i += 1
        arg_str = rest[start : i - 1]
        operands = re.findall(r"%([\w\.\-]+)", arg_str)
        cur.ops[name] = Op(name, out_t, opcode, operands, rest)
    return comps


def _trip_count(cond: Computation) -> int:
    best = 0
    for op in cond.ops.values():
        if op.opcode == "constant" and op.out in ("s32[]", "u32[]", "s64[]", "u64[]"):
            m = re.search(r"constant\((\d+)\)", op.line)
            if m:
                best = max(best, int(m.group(1)))
    return max(best, 1)


_CALL_ATTRS = ("calls", "to_apply", "body", "condition")


def _callees(op: Op) -> list[tuple[str, str]]:
    out = []
    for attr in _CALL_ATTRS:
        for m in re.finditer(rf"{attr}=%?([\w\.\-]+)", op.line):
            out.append((attr, m.group(1)))
    m = re.search(r"branch_computations=\{([^}]*)\}", op.line)
    if m:
        for name in re.findall(r"%?([\w\.\-]+)", m.group(1)):
            out.append(("branch", name))
    return out


def analyze_hlo(text: str, attn_score_trailing: int | None = None):
    comps = parse_hlo(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        raise ValueError("no ENTRY computation found")

    # scale multipliers + fused/memory-level marking (fused=False dominates)
    state: dict[str, tuple[int, bool]] = {}

    def visit(cname: str, mult: int, is_fused: bool):
        prev = state.get(cname, (0, True))
        new = (max(prev[0], mult), prev[1] and is_fused)
        if new == prev:
            return
        state[cname] = new
        comp = comps.get(cname)
        if comp is None:
            return
        for op in comp.ops.values():
            callees = _callees(op)
            trip = 1
            if op.opcode == "while":
                cond_name = next((n for a, n in callees if a == "condition"), None)
                if cond_name and cond_name in comps:
                    trip = _trip_count(comps[cond_name])
            for attr, callee in callees:
                if attr == "body":
                    visit(callee, new[0] * trip, is_fused)
                elif attr == "condition":
                    visit(callee, new[0] * (trip + 1), is_fused)
                elif attr in ("calls", "to_apply"):
                    visit(callee, new[0], True)
                else:  # branch
                    visit(callee, new[0], is_fused)

    visit(entry.name, 1, False)
    scale = {k: v[0] for k, v in state.items()}
    fused = {k: v[1] for k, v in state.items()}

    flops = 0.0
    mem_bytes = 0.0
    attn_score_bytes = 0.0  # traffic a flash-attention kernel keeps in VMEM
    top_traffic: list[tuple[float, int, str, str]] = []
    coll_by_kind: dict[str, float] = defaultdict(float)
    coll_lines: list[tuple[str, int, float]] = []  # (line, scale, bytes)

    def is_score_shaped(shape_str: str) -> bool:
        if attn_score_trailing is None:
            return False
        m = _SHAPE_RE.search(shape_str)
        if not m or not m.group(2):
            return False
        dims = [int(x) for x in m.group(2).split(",")]
        return (
            len(dims) >= 4
            and dims[-1] == attn_score_trailing
            and int(np.prod(dims)) >= 1 << 22
        )

    for cname, comp in comps.items():
        mult = scale.get(cname, 0)
        if mult == 0:
            continue
        symtab = {op.name: op.out for op in comp.ops.values()}
        memory_level = not fused.get(cname, True)
        for op in comp.ops.values():
            # FLOPs from dots (counted wherever they live, incl. fusion bodies)
            if op.opcode == "dot":
                out_e = shape_elems(op.out)
                k = 1
                md = re.search(r"lhs_contracting_dims=\{([\d,]+)\}", op.line)
                if md and op.operands:
                    lhs_t = symtab.get(op.operands[0], "")
                    sm = _SHAPE_RE.search(lhs_t)
                    if sm and sm.group(2):
                        dims = [int(x) for x in sm.group(2).split(",")]
                        bdims = re.search(r"lhs_batch_dims=\{([\d,]*)\}", op.line)
                        for ci in md.group(1).split(","):
                            ci = int(ci)
                            if ci < len(dims):
                                k *= dims[ci]
                flops += 2.0 * out_e * k * mult
            elif op.opcode == "convolution":
                # rough: 2 * out_elems * (kernel elems / out-channel)
                out_e = shape_elems(op.out)
                kern = shape_elems(symtab.get(op.operands[1], "")) if len(op.operands) > 1 else 1
                flops += 2.0 * out_e * max(kern, 1) ** 0.5 * mult  # loose lower bound

            # memory traffic at memory level
            if memory_level and op.opcode in _TRAFFIC_OPS:
                b = shape_bytes(op.out)
                score = is_score_shaped(op.out)
                for o in op.operands:
                    ot = symtab.get(o, "")
                    b += shape_bytes(ot)
                    score = score or is_score_shaped(ot)
                # In-place updates: a dynamic-update-slice (or a fusion rooted
                # in one) aliases its big operand on TPU (donation / while
                # carry); real traffic is the written slice, twice (read+write),
                # plus the small operands — not the whole buffer.
                dus_update = None
                if op.opcode == "dynamic-update-slice" and op.operands:
                    dus_update = symtab.get(op.operands[1], "")
                elif op.opcode == "fusion":
                    m = re.search(r"calls=%?([\w\.\-]+)", op.line)
                    callee = comps.get(m.group(1)) if m else None
                    if callee is not None:
                        for cop in callee.ops.values():
                            # dtype converts may wrap the DUS — match on elems
                            if cop.opcode == "dynamic-update-slice" and shape_elems(cop.out) == shape_elems(op.out):
                                csym = {o2.name: o2.out for o2 in callee.ops.values()}
                                dus_update = csym.get(cop.operands[1], "") if len(cop.operands) > 1 else ""
                                break
                if dus_update is not None and shape_elems(dus_update) < shape_elems(op.out):
                    big = shape_bytes(op.out)
                    slice_b = int(big * shape_elems(dus_update) / max(shape_elems(op.out), 1))
                    b = 2 * slice_b + max(b - 2 * big, 0)
                mem_bytes += b * mult
                if score:
                    attn_score_bytes += b * mult
                top_traffic.append((b * mult, mult, op.opcode, op.out[:64]))

            # collectives
            if op.opcode in _COLLECTIVES or any(op.opcode.startswith(c) for c in _COLLECTIVES):
                kind = next(c for c in _COLLECTIVES if op.opcode.startswith(c))
                opb = sum(shape_bytes(symtab.get(o, "")) for o in op.operands)
                if opb == 0:
                    opb = shape_bytes(op.out)
                coll_by_kind[kind] += opb * mult
                coll_lines.append((op.line, mult, opb))

    top_traffic.sort(key=lambda t: -t[0])
    return {
        "flops": flops,
        "mem_bytes": mem_bytes,
        "attn_score_bytes": attn_score_bytes,
        "top_traffic": top_traffic[:20],
        "coll_by_kind": dict(coll_by_kind),
        "coll_lines": coll_lines,
        "n_computations": len(comps),
    }


def _group_crosses_pod(line: str, per_pod: int) -> bool | None:
    """True/False if determinable from replica_groups, else None."""
    m = re.search(r"replica_groups=\{\{([^}]+)\}", line)
    if m:
        ids = [int(x) for x in m.group(1).split(",") if x.strip().isdigit()]
        return len({i // per_pod for i in ids}) > 1
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](T\(([\d,]+)\))?", line)
    if m:
        g, s, src = int(m.group(1)), int(m.group(2)), [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(src)))
        if m.group(5):
            perm = [int(x) for x in m.group(5).split(",")]
            ids = ids.reshape(src).transpose(perm).reshape(-1)
        groups = ids.reshape(g, s)
        return bool((np.ptp(groups // per_pod, axis=1) > 0).any())
    return None


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    ici_bytes: float
    dci_bytes: float


@dataclasses.dataclass
class Roofline:
    chips: int
    hlo_flops: float       # cost_analysis raw (while bodies once)
    scaled_flops: float    # HLO-parsed, while-scaled
    hlo_bytes: float
    scaled_bytes: float
    coll: CollectiveStats
    model_flops: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    useful_ratio: float
    per_device_peak_bytes: int
    attn_score_bytes: float = 0.0
    top_traffic: list = dataclasses.field(default_factory=list)

    def table_row(self) -> dict:
        return {
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops": self.scaled_flops,
            "hlo_bytes": self.scaled_bytes,
            "useful_ratio": self.useful_ratio,
            "coll_ici_bytes": self.coll.ici_bytes,
            "coll_dci_bytes": self.coll.dci_bytes,
            "peak_bytes_per_dev": self.per_device_peak_bytes,
            "attn_score_bytes": self.attn_score_bytes,
        }


def analyze(compiled, mesh, model_flops: float, hlo_text: str | None = None, attn_score_trailing: int | None = None) -> Roofline:
    chips = int(np.prod(list(mesh.shape.values())))
    pod = mesh.shape.get("pod", 1)
    per_pod = chips // pod if pod > 1 else chips
    hlo = hlo_text if hlo_text is not None else compiled.as_text()
    cost = dict(compiled.cost_analysis() or {})
    parsed = analyze_hlo(hlo, attn_score_trailing=attn_score_trailing)

    ici = dci = 0.0
    for line, mult, opb in parsed["coll_lines"]:
        crosses = _group_crosses_pod(line, per_pod) if pod > 1 else False
        if crosses:
            dci += opb * mult
        else:
            ici += opb * mult
    coll = CollectiveStats(parsed["coll_by_kind"], ici, dci)

    scaled_flops = max(parsed["flops"], float(cost.get("flops", 0.0)))
    scaled_bytes = max(parsed["mem_bytes"], float(cost.get("bytes accessed", 0.0)))

    # NOTE: the compiled SPMD module's shapes are PER-DEVICE (post-partition),
    # so parsed FLOPs/bytes are already per-chip — no further division.
    compute_s = scaled_flops / meshmod.PEAK_FLOPS_BF16
    memory_s = scaled_bytes / meshmod.HBM_BW
    ici_s = ici / (meshmod.ICI_BW * meshmod.ICI_LINKS)
    dci_s = dci / meshmod.DCI_BW
    collective_s = ici_s + dci_s

    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    peak = 0
    try:
        ma = compiled.memory_analysis()
        peak = int(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            - getattr(ma, "alias_size_in_bytes", 0)
        )
    except Exception:
        pass

    return Roofline(
        chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)),
        scaled_flops=scaled_flops,
        hlo_bytes=float(cost.get("bytes accessed", 0.0)),
        scaled_bytes=scaled_bytes,
        coll=coll,
        model_flops=model_flops,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        # model_flops is global; parsed flops are per-chip
        useful_ratio=((model_flops / chips) / scaled_flops) if scaled_flops else 0.0,
        per_device_peak_bytes=peak,
        attn_score_bytes=parsed["attn_score_bytes"],
        top_traffic=parsed["top_traffic"],
    )
