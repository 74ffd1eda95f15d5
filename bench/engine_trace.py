"""The serving engine's own names in a JAX profiler trace.

``bench/tracing.py`` reduces a trace to device numbers by the names XLA
gives; this module reads the names the program gives itself
(``repro/spans.py``), from the same ``.xplane.pb`` and on the same clock.

Host spans (``serve.*`` ``TraceAnnotation`` events) are kept by name as
intervals, so device idle can be read inside one ``ServeEngine.run()``
and not inside the profiler's own start and stop.  Leaf ops of the
decode-loop program (``jit_loop``, placed by the ``XLA Modules`` events
that contain them) are summed by the innermost of the program's device
scopes in their name-scope path (``""`` where none).  The path is the
``op_name`` metadata of the op's HLO instruction in the loop's compiled
HLO, which the trace's ``/host:metadata`` plane holds as an ``Hlo
Proto`` stat per program.  The op events themselves carry no scope: on
a TPU v5e their own stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, and the ``tf_op``
stat the trace viewer shows sits on the event's metadata, which
``ProfileData`` does not expose; on the CPU they carry ``hlo_op`` and
``hlo_module``.  Ops of an instruction the HLO does not hold count
under ``"?"``.  Where the program names no spans or scopes, both
readings stay empty and every reading here is None.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from pathlib import Path

from bench.tracing import (MODULES_LINE, OPS_LINE, _host_events, _name_gap,
                           find_xplane, leaves, program_name, union)

try:
    from repro import spans as SPANS     # the program's span and scope names
except ImportError:                      # a program that names none
    SPANS = None

LOOP_PROGRAM = "jit_loop"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


@dataclass
class EngineTrace:
    spans: dict[str, list[tuple[int, int]]] = field(default_factory=dict)
    loop_scopes: dict[str, float] = field(default_factory=dict)  # per device
    busy_iv: list[list[tuple[int, int]]] = field(default_factory=list)

    def span_s(self, span: str) -> float | None:
        """Seconds the ``span`` events cover; None without such a span."""
        length = sum(e - s for s, e in union(self.spans.get(span, [])))
        return length * 1e-9 if length > 0 else None

    def busy_in_s(self, span: str) -> float | None:
        """Seconds inside the ``span`` events in which the device ran an
        op (averaged over devices); None without such a span or device."""
        marks = union(self.spans.get(span, []))
        if not marks or not self.busy_iv:
            return None
        return sum(overlap(iv, marks) for iv in self.busy_iv) * 1e-9 / len(self.busy_iv)

    def idle_share_in(self, span: str) -> float | None:
        """% of the ``span`` events' length in which the device ran no
        op (averaged over devices); None without such a span."""
        length, busy = self.span_s(span), self.busy_in_s(span)
        if length is None or busy is None:
            return None
        return 100.0 * (1.0 - busy / length)

    def span_gaps(self, top: int = 10) -> list[tuple[str, float]]:
        """The first device's longest idle gaps inside ``serve.run``
        (its edges included), each named by the shortest engine span
        that covers its midpoint: the engine step the device waited on."""
        if SPANS is None or not self.busy_iv:
            return []
        named = [(s, e, k) for k, iv in self.spans.items() for s, e in iv]
        holes = []
        for lo, hi in union(self.spans.get(SPANS.RUN, [])):
            inside = [(max(s, lo), min(e, hi)) for s, e in self.busy_iv[0]
                      if e > lo and s < hi]
            edges = [lo] + [x for iv in inside for x in iv] + [hi]
            holes += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        holes.sort(key=lambda h: h[0] - h[1])
        return [(_name_gap(named, lo, hi), (hi - lo) * 1e-9)
                for lo, hi in holes[:top]]

    def scope_share(self, scope: str) -> float | None:
        """``scope``'s % of the decode loop's leaf-op time; None where
        the trace holds no op of that scope."""
        total = sum(self.loop_scopes.values())
        if total <= 0 or scope not in self.loop_scopes:
            return None
        return 100.0 * self.loop_scopes[scope] / total


def overlap(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def scope_of(path: str, scopes) -> str:
    """The innermost of ``scopes`` (each one or more ``/``-joined
    components) in the name-scope ``path``; ``""`` for none."""
    parts = path.split("/")
    best, key = "", (-1, 0)
    for sc in scopes:
        sp = sc.split("/")
        for i in range(len(parts) - len(sp), -1, -1):
            if parts[i:i + len(sp)] == sp:
                if (i + len(sp), len(sp)) > key:
                    best, key = sc, (i + len(sp), len(sp))
                break
    return best


def instr_name(text: str) -> str:
    """``%copy.272 = bf16[8,16]... copy(...)`` -> ``copy.272``; a bare
    instruction name (the CPU's op events) passes unchanged."""
    return text.split(" = ", 1)[0].lstrip("%")


def _loop_scopes(lines, leaf_ops, n: int, hlo_ops: dict[str, str] | None):
    """Seconds by scope of the decode loop's ``leaf_ops`` on one device,
    or {} where no op can be placed in a scope."""
    if SPANS is None or not hlo_ops or MODULES_LINE not in lines:
        return {}
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                  for ev in lines[MODULES_LINE].events
                  if program_name(ev.name) == LOOP_PROGRAM)
    starts = [m[0] for m in mods]
    out: dict[str, float] = {"": 0.0}
    for ev in leaf_ops:
        j = bisect.bisect_right(starts, ev.start_ns) - 1
        if j < 0 or ev.start_ns >= mods[j][1]:
            continue
        path = hlo_ops.get(instr_name(ev.name))
        key = "?" if path is None else scope_of(path, SPANS.DEVICE)
        out[key] = out.get(key, 0.0) + ev.duration_ns * 1e-9 / n
    return out if set(out) - {"", "?"} else {}


def reduce_engine(planes, hlo_ops: dict[str, str] | None = None) -> EngineTrace:
    """Spans, busy intervals and loop scopes of profiler planes
    (``ProfileData.planes`` or look-alikes).  ``hlo_ops`` maps the
    decode loop's HLO instruction names to their ``op_name`` metadata
    (``hlo_op_names``)."""
    planes = list(planes)
    devs = [lines for lines in ({ln.name: ln for ln in pl.lines} for pl in planes)
            if OPS_LINE in lines]
    n = len(devs)
    busy_iv = []
    loop_scopes: dict[str, float] = {}
    for lines in devs:
        evs = list(lines[OPS_LINE].events)
        busy_iv.append(union([(ev.start_ns, ev.start_ns + ev.duration_ns)
                              for ev in evs]))
        for k, v in _loop_scopes(lines, leaves(evs), n, hlo_ops).items():
            loop_scopes[k] = loop_scopes.get(k, 0.0) + v
    spans: dict[str, list[tuple[int, int]]] = {}
    if SPANS is not None:
        for s, e, name in _host_events(planes):
            if name in SPANS.HOST:
                spans.setdefault(name, []).append((s, e))
    return EngineTrace(spans=spans, loop_scopes=loop_scopes, busy_iv=busy_iv)


# -- the loop's HLO, from the trace's metadata plane --------------------------
# Field numbers of tsl/profiler/protobuf/xplane.proto (XSpace.planes 1;
# XPlane.name 2, event_metadata 4, stat_metadata 5; map entries key 1,
# value 2; XEventMetadata.name 2, stats 5; XStat.metadata_id 1,
# bytes_value 6; XStatMetadata.name 2) and xla/service/hlo.proto
# (HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# metadata 7; OpMetadata.op_name 2).

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for everything else."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield key >> 3, v


def _field(buf, num: int):
    return next((v for f, v in _fields(buf) if f == num), None)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace") if v is not None else ""


def hlo_op_names(raw: bytes, program: str = LOOP_PROGRAM) -> dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata (the name-scope
    path) in the compiled programs named ``program`` whose HLO the
    trace's ``/host:metadata`` plane holds; {} where it holds none."""
    out: dict[str, str] = {}
    for f, plane in _fields(raw):
        if f != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        proto_ids = set()
        for g, entry in _fields(plane):
            if g == 5:
                sm = _field(entry, 2)
                if sm is not None and _text(_field(sm, 2)) == HLO_PROTO_STAT:
                    proto_ids.add(_field(entry, 1))
        for g, entry in _fields(plane):
            em = _field(entry, 2) if g == 4 else None
            if em is None or program_name(_text(_field(em, 2))) != program:
                continue
            for h, st in _fields(em):
                if h != 5 or _field(st, 1) not in proto_ids:
                    continue
                module = _field(_field(st, 6) or b"", 1) or b""
                for c, comp in _fields(module):
                    if c != 3:
                        continue
                    for d, ins in _fields(comp):
                        if d == 2:
                            md = _field(ins, 7)
                            out.setdefault(_text(_field(ins, 1)),
                                           _text(_field(md, 2)) if md else "")
    return out


def read_engine_trace(logdir: str | Path) -> EngineTrace:
    from jax.profiler import ProfileData

    path = find_xplane(logdir)
    return reduce_engine(ProfileData.from_file(path).planes,
                         hlo_ops=hlo_op_names(Path(path).read_bytes()))


def summary(run) -> EngineTrace | None:
    """The engine reading of ``run``'s traced call, read from the
    harness's trace directory once and kept in ``run.traced``; None
    for an untraced run or a program that names no spans."""
    if SPANS is None or not run or not run.traced:
        return None
    if "engine" not in run.traced:
        from bench.run import TRACE_DIR

        run.traced["engine"] = read_engine_trace(TRACE_DIR)
    return run.traced["engine"]
