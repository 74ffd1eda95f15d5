"""Roofline-term extraction from compiled dry-run artifacts.

XLA's ``cost_analysis()`` on the host backend does NOT multiply
while-loop bodies by their trip count (measured: an 8-step scan of
matmuls reports ~1 matmul of flops), and every layer stack here is a
``lax.scan``.  So this module derives all three roofline terms from the
optimized HLO text itself with a computation-graph walk:

  flops       — every ``dot``/``convolution``, 2·|result|·contraction,
                multiplied through enclosing while trip counts
  HBM bytes   — per *top-level* instruction: result + operand bytes at
                fusion boundaries (internals of a fusion don't touch
                HBM), bookkeeping ops excluded, trip-count aware
  collectives — all-gather/all-reduce/reduce-scatter/all-to-all/
                collective-permute (+ async -start forms): max(result,
                operand) bytes as the per-device wire-bytes proxy,
                trip-count aware

Terms (peaks from ``repro.hw`` for the record's device kind; TPU v5e:
t_comp = flops/197e12, t_mem = bytes/819e9, t_coll = coll_bytes/50e9).
``cost_analysis()`` raw numbers are recorded alongside for reference.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import cached_property

from repro import hw

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLL_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "iota",
}
_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"([\w\-]+)\(")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_COND_BODY_RE = re.compile(r"condition=%([\w.\-]+),\s*body=%([\w.\-]+)")
_BRANCH_RE = re.compile(r"(?:branch_computations|to_apply)=\{?%?([\w.\-,%\s]+)\}?")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CONST_RE = re.compile(r"[su]\d+\[\]\s+constant\((\d+)\)")


def _shapes_in(text: str) -> list[tuple[str, tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES:
            out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _nbytes(shapes) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _paren_span(s: str, start: int) -> str:
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return s[start: i + 1]
    return s[start:]


@dataclass
class Instr:
    name: str
    op: str
    result_shapes: list
    operand_names: list[str]
    line: str


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    symbols: dict = field(default_factory=dict)   # %name -> shapes list


class HloCost:
    """Computation-graph walk over optimized HLO text (see module doc)."""

    def __init__(self, hlo_text: str):
        self.comps: dict[str, Computation] = {}
        self.entry: str | None = None
        self._parse(hlo_text)
        self._memo_flops: dict[str, float] = {}
        self._memo_bytes: dict[str, float] = {}
        self._memo_coll: dict[str, dict[str, float]] = {}

    # -- parsing -----------------------------------------------------------
    def _parse(self, text: str) -> None:
        cur: Computation | None = None
        for raw in text.splitlines():
            if raw and not raw[0].isspace() and "->" in raw and "{" in raw:
                m = _HEADER_RE.match(raw)
                if not m:
                    continue
                cur = Computation(m.group(1))
                self.comps[cur.name] = cur
                if raw.startswith("ENTRY"):
                    self.entry = cur.name
                # header params: "p: f32[8,64], q: s32[]"
                for pname, ptype in re.findall(r"([\w.\-]+):\s*([^,()]+(?:\([^)]*\))?)",
                                               m.group(2)):
                    cur.symbols[pname] = _shapes_in(ptype)
                continue
            if cur is None:
                continue
            mi = _INSTR_RE.match(raw)
            if not mi:
                if raw.startswith("}"):
                    cur = None
                continue
            name, rest = mi.group(1), mi.group(2)
            mo = _OP_RE.search(rest)
            if not mo:
                continue
            op = mo.group(1)
            result_shapes = _shapes_in(rest[: mo.start()])
            args = _paren_span(rest, mo.end() - 1)
            operand_names = re.findall(r"%([\w.\-]+)", args)
            cur.symbols[name] = result_shapes
            cur.instrs.append(Instr(name, op, result_shapes, operand_names, rest))

    # -- trip counts ---------------------------------------------------------
    def _trip_count(self, cond_name: str) -> int:
        comp = self.comps.get(cond_name)
        if not comp:
            return 1
        consts = [int(m) for i in comp.instrs for m in _CONST_RE.findall(i.line)]
        return max(consts) if consts else 1

    # -- flops ----------------------------------------------------------------
    def _dot_flops(self, comp: Computation, ins: Instr) -> float:
        result_elems = 1
        for _, dims in ins.result_shapes:
            for d in dims:
                result_elems *= d
        contraction = 1
        m = _LHS_CDIMS_RE.search(ins.line)
        if m and ins.operand_names:
            lhs = comp.symbols.get(ins.operand_names[0])
            if lhs:
                _, dims = lhs[0]
                for idx in (int(x) for x in m.group(1).split(",") if x):
                    if idx < len(dims):
                        contraction *= dims[idx]
        return 2.0 * result_elems * contraction

    def _conv_flops(self, comp: Computation, ins: Instr) -> float:
        # approx: 2 · |result| · (kernel elems / output features)
        result_elems = 1
        for _, dims in ins.result_shapes:
            for d in dims:
                result_elems *= d
        if len(ins.operand_names) >= 2:
            rhs = comp.symbols.get(ins.operand_names[1])
            if rhs:
                _, kdims = rhs[0]
                kelems = 1
                for d in kdims:
                    kelems *= d
                feat = kdims[-1] if kdims else 1
                return 2.0 * result_elems * max(1, kelems // max(feat, 1))
        return 2.0 * result_elems

    def _callees(self, ins: Instr) -> list[str]:
        out = [m for m in _CALLS_RE.findall(ins.line)]
        mb = _BRANCH_RE.search(ins.line)
        if mb:
            out += re.findall(r"[\w.\-]+", mb.group(1).replace("%", " "))
        return [c for c in out if c in self.comps]

    def flops(self, comp_name: str | None = None) -> float:
        comp_name = comp_name or self.entry
        if comp_name in self._memo_flops:
            return self._memo_flops[comp_name]
        comp = self.comps.get(comp_name)
        if comp is None:
            return 0.0
        total = 0.0
        self._memo_flops[comp_name] = 0.0  # cycle guard
        for ins in comp.instrs:
            if ins.op == "dot":
                total += self._dot_flops(comp, ins)
            elif ins.op == "convolution":
                total += self._conv_flops(comp, ins)
            elif ins.op == "while":
                m = _COND_BODY_RE.search(ins.line)
                if m:
                    total += self._trip_count(m.group(1)) * self.flops(m.group(2))
            else:
                for callee in self._callees(ins):
                    total += self.flops(callee)
        self._memo_flops[comp_name] = total
        return total

    # -- HBM bytes ---------------------------------------------------------------
    _SLICE_OPS = ("dynamic-slice", "slice", "gather")

    def _fusion_operand_bytes(self, callee: str) -> list[float] | None:
        """Per-parameter touched bytes for a fusion computation.

        A loop body reads a dynamic-slice of the stacked layer weights;
        charging the full (L, ...) operand per iteration overcounts HBM
        traffic L×.  If every use of a fusion parameter is a slice-type
        op, charge only the slices' result bytes.
        """
        comp = self.comps.get(callee)
        if comp is None:
            return None
        params = [n for n in comp.symbols if n.startswith("param")]
        params.sort(key=lambda n: (len(n), n))
        out = []
        for pname in params:
            uses = [i for i in comp.instrs if pname in i.operand_names]
            if uses and all(u.op in self._SLICE_OPS for u in uses):
                out.append(float(sum(_nbytes(u.result_shapes) for u in uses)))
            else:
                out.append(float(_nbytes(comp.symbols.get(pname, []))))
        return out

    def _fusion_result_bytes(self, callee: str, default: float) -> float:
        """In-place dynamic-update-slice roots write only the update."""
        comp = self.comps.get(callee)
        if comp is None or not comp.instrs:
            return default
        root = comp.instrs[-1]
        if root.op == "dynamic-update-slice" and len(root.operand_names) >= 2:
            upd = comp.symbols.get(root.operand_names[1])
            if upd:
                return float(_nbytes(upd))
        return default

    def hbm_bytes(self, comp_name: str | None = None) -> float:
        """Fusion-boundary traffic model (slice-aware, trip-count aware)."""
        comp_name = comp_name or self.entry
        if comp_name in self._memo_bytes:
            return self._memo_bytes[comp_name]
        comp = self.comps.get(comp_name)
        if comp is None:
            return 0.0
        total = 0.0
        self._memo_bytes[comp_name] = 0.0
        for ins in comp.instrs:
            if ins.op in _SKIP_OPS:
                continue
            if ins.op == "while":
                m = _COND_BODY_RE.search(ins.line)
                if m:
                    total += self._trip_count(m.group(1)) * self.hbm_bytes(m.group(2))
                continue
            if ins.op in ("call", "conditional"):
                for callee in self._callees(ins):
                    total += self.hbm_bytes(callee)
                continue
            res = float(_nbytes(ins.result_shapes))
            if ins.op == "fusion":
                callees = self._callees(ins)
                per_param = (
                    self._fusion_operand_bytes(callees[0]) if callees else None
                )
                if callees:
                    res = self._fusion_result_bytes(callees[0], res)
                nb = res
                if per_param is not None:
                    data_operands = [
                        o for o in ins.operand_names if comp.symbols.get(o)
                    ]
                    for i, opnd in enumerate(data_operands):
                        if i < len(per_param):
                            nb += per_param[i]
                        else:
                            nb += _nbytes(comp.symbols.get(opnd, []))
                else:
                    nb += sum(
                        _nbytes(comp.symbols.get(o, [])) for o in ins.operand_names
                    )
            elif ins.op in self._SLICE_OPS:
                nb = 2 * res  # read the slice, write the slice
            elif ins.op == "dynamic-update-slice":
                upd = (
                    comp.symbols.get(ins.operand_names[1])
                    if len(ins.operand_names) >= 2 else None
                )
                nb = 2.0 * _nbytes(upd) if upd else res
            else:
                nb = res + sum(
                    _nbytes(comp.symbols.get(o, [])) for o in ins.operand_names
                )
            total += nb
        self._memo_bytes[comp_name] = total
        return total

    # -- collectives ------------------------------------------------------------
    def collectives(self, comp_name: str | None = None) -> dict[str, float]:
        comp_name = comp_name or self.entry
        if comp_name in self._memo_coll:
            return self._memo_coll[comp_name]
        comp = self.comps.get(comp_name)
        if comp is None:
            return {}
        total: dict[str, float] = {}
        self._memo_coll[comp_name] = {}

        def add(kind, nb, mult=1.0):
            total[kind] = total.get(kind, 0.0) + nb * mult

        for ins in comp.instrs:
            base = ins.op[:-6] if ins.op.endswith("-start") else ins.op
            if base in _COLL_KINDS:
                res = _nbytes(ins.result_shapes)
                opnd = sum(
                    _nbytes(comp.symbols.get(o, [])) for o in ins.operand_names
                )
                add(base, max(res, opnd))
                continue
            if ins.op == "while":
                m = _COND_BODY_RE.search(ins.line)
                if m:
                    trip = self._trip_count(m.group(1))
                    for k, v in self.collectives(m.group(2)).items():
                        add(k, v, trip)
                continue
            for callee in self._callees(ins):
                for k, v in self.collectives(callee).items():
                    add(k, v)
        self._memo_coll[comp_name] = total
        return total


# ---------------------------------------------------------------------------
# Roofline record
# ---------------------------------------------------------------------------


@dataclass
class Roofline:
    flops: float                  # per-device flops (trip-count aware)
    hbm_bytes: float              # per-device fusion-boundary bytes
    collective_bytes: float       # per-device wire bytes
    model_flops: float            # 6·N_active·D (whole step, all chips)
    chips: int
    device_kind: str              # repro.hw table key of the target chip

    @property
    def peaks(self) -> hw.Chip:
        return hw.chip(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.peak_flops_bf16

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline bound = max term (perfect overlap assumption)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_compute_ratio(self) -> float:
        total_hlo = self.flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful model flops / (chips · peak · bound time) — the score."""
        t = self.step_time
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * self.peaks.peak_flops_bf16 * t)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.collective_bytes,
            "model_flops": self.model_flops,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "step_time_bound_s": self.step_time,
            "useful_compute_ratio": self.useful_compute_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops_for(cfg, shape, n_active_matmul: int) -> float:
    """6·N·D for train, 2·N·D for fwd-only; D = tokens processed."""
    if shape.kind == "train":
        return 6.0 * n_active_matmul * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active_matmul * shape.tokens
    return 2.0 * n_active_matmul * shape.global_batch


# Back-compat simple line parser (used by tests for cross-validation)
@dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, int] = field(default_factory=dict)
    count_by_kind: dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Flat (trip-count-unaware) collective scan — kept as a lower bound
    and for parser cross-validation in tests."""
    stats = CollectiveStats()
    coll_re = re.compile(r"\b(" + "|".join(_COLL_KINDS) + r")(-start)?\(")
    for line in hlo_text.splitlines():
        m = coll_re.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        kind = m.group(1)
        head, _, tail = line.partition(m.group(0))
        res = _nbytes(_shapes_in(head))
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + res
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats
