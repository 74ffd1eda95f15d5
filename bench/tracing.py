"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Device planes are those that carry an ``XLA Ops`` line.  Busy time is
the union of that line's op intervals; an XLA program's time is the
sum of its ``XLA Modules`` events (names lose their ``(id)`` suffix).
Ops nest (a ``while`` op spans the ops of its body): only leaf ops are
summed by name, each named by its HLO instruction, opcode and output
shape (and a custom call's target), so a kernel's time is the sum of
the leaf events that carry its name.
Numbers are averaged over the device planes, so ``busy_s`` is per
chip.  Each idle gap of the first device (the stretches before its
first op and after its last one within the harness's ``bench.*``
spans count too) is named after the shortest host event that covers
its midpoint: what the host was doing then.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class TraceSummary:
    devices: int
    busy_s: float                                   # per device
    programs: dict[str, float] = field(default_factory=dict)   # per device
    ops: dict[str, float] = field(default_factory=dict)        # per device
    gaps: list[tuple[str, float]] = field(default_factory=list)

    def program_s(self, pattern: str) -> float | None:
        """Seconds of the programs whose name matches ``pattern``."""
        hits = [v for k, v in self.programs.items() if re.search(pattern, k)]
        return sum(hits) if hits else None

    def op_s(self, pattern: str) -> float | None:
        hits = [v for k, v in self.ops.items() if re.search(pattern, k)]
        return sum(hits) if hits else None


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge (start, end) intervals; returns them sorted, disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_name(text: str) -> str:
    """``%copy.272 = bf16[8,16]{1,0:T(8,128)} copy(...)`` ->
    ``copy.272 copy bf16[8,16]``; other names pass unchanged."""
    if " = " not in text:
        return text
    instr, rest = text.split(" = ", 1)
    op = re.search(r"[)}\]] ([a-z][\w\-]*)\(", rest)
    shape = "tuple" if rest.startswith("(") else re.split(r"[{ ]", rest, 1)[0]
    out = f"{instr.lstrip('%')} {op.group(1) if op else '?'} {shape}"
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{out} {target.group(1)}" if target else out


def leaves(events) -> list:
    """Events that contain no other event of the same line."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    parent = [False] * len(evs)
    stack: list[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].start_ns + evs[stack[-1]].duration_ns <= e.start_ns:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(evs, parent) if not p]


def find_xplane(logdir: str | Path) -> str:
    hits = sorted(glob.glob(str(Path(logdir) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return hits[-1]


def _host_events(planes) -> list[tuple[int, int, str]]:
    out = []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return out


def _name_gap(host, lo: int, hi: int) -> str:
    mid = (lo + hi) // 2
    best = None
    for s, e, name in host:
        if s <= mid <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "no host event"


def reduce_planes(planes, top_gaps: int = 10) -> TraceSummary:
    """Reduce profiler planes (``ProfileData.planes`` or look-alikes)."""
    planes = list(planes)
    devs = []
    for pl in planes:
        lines = {ln.name: ln for ln in pl.lines}
        if OPS_LINE in lines:
            devs.append(lines)
    if not devs:
        raise ValueError("trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    n = len(devs)
    busy = 0.0
    programs: dict[str, float] = {}
    ops: dict[str, float] = {}
    gaps: list[tuple[str, float]] = []
    host = _host_events(planes)
    for i, lines in enumerate(devs):
        evs = list(lines[OPS_LINE].events)
        iv = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in evs]
        for ev in leaves(evs):
            k = op_name(ev.name)
            ops[k] = ops.get(k, 0.0) + ev.duration_ns * 1e-9 / n
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                k = program_name(ev.name)
                programs[k] = programs.get(k, 0.0) + ev.duration_ns * 1e-9 / n
        merged = union(iv)
        busy += sum(e - s for s, e in merged) * 1e-9 / n
        if i == 0:
            holes = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
            marks = [(s, e) for s, e, name in host if name.startswith("bench.")]
            if merged and marks:      # the traced window's two edges
                holes += [(min(s for s, _ in marks), merged[0][0]),
                          (merged[-1][1], max(e for _, e in marks))]
            holes.sort(key=lambda h: h[0] - h[1])
            gaps = [(_name_gap(host, lo, hi), (hi - lo) * 1e-9)
                    for lo, hi in holes[:top_gaps]]
    return TraceSummary(devices=n, busy_s=busy,
                        programs=programs, ops=ops, gaps=gaps)


def read_trace(logdir: str | Path) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(find_xplane(logdir)).planes)
