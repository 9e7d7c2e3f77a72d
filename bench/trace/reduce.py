"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

What the TPU trace holds (read by hand on a v5e): one plane per chip,
``/device:TPU:<n>``, whose ``XLA Ops`` line has one event per executed
HLO instruction, named by the instruction's HLO text (shapes included),
with times in nanoseconds. Control flow nests: a ``while`` event spans
the ops of its body, which are events on the same line. Pallas kernels
are custom-calls with ``custom_call_target="tpu_custom_call"`` and an
empty ``kernel_metadata``; their names do not reach the trace, so a
kernel is found by its operand signature. The host plane ``/host:CPU``
holds the harness's ``jax.profiler.TraceAnnotation`` spans (``prepare``,
``dispatch``, ``wait``) on the same clock, to within about a
millisecond.

Busy time is the union of ``XLA Ops`` intervals. Self time of an op is
its duration less that of the ops nested in it, so a loop is not
counted twice. A collective is an op whose opcode is one of
``COLLECTIVES``; its time is exposed where no other leaf op runs.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import cached_property
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_SPANS = ("prepare", "dispatch", "wait")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_OPCODE = re.compile(r"^%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(")
_SHAPE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u32)"
                    r"\[([\d,]*)\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Interval = Tuple[float, float]


@dataclass
class Op:
    text: str            # the HLO instruction as the trace names it
    start: float         # ns
    end: float
    self_ns: float = 0.0
    leaf: bool = True

    @cached_property
    def opcode(self) -> str:
        m = _OPCODE.match(self.text)
        return m.group(2) if m else ""

    @property
    def is_collective(self) -> bool:
        op = self.opcode
        return any(op == c or op.startswith(c + "-") for c in COLLECTIVES)

    def label(self) -> str:
        """``%name = <result shape> opcode``, layouts dropped."""
        m = _OPCODE.match(self.text)
        head = self.text[:m.end() - 1] if m else self.text
        return _LAYOUT.sub("", head)[:160]


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(_LAYOUT.sub("", text))]


def result_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each result of an HLO instruction."""
    m = _OPCODE.match(text)
    return _shapes(m.group(1)) if m else []


def operand_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each operand of an HLO instruction's call."""
    m = _OPCODE.match(text)
    if not m:
        return []
    depth, i = 1, m.end()
    j = i
    while j < len(text) and depth:
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        j += 1
    return _shapes(text[i:j - 1])


def is_pallas(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """a minus b; both sorted, disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _nest(ops: List[Op]) -> None:
    """Fill self time and leaf flags from the nesting of intervals."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            parent = stack[-1]
            parent.leaf = False
            parent.self_ns -= op.end - op.start
        stack.append(op)


@dataclass
class Trace:
    devices: Dict[str, List[Op]]                        # plane -> ops
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices, host = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                ops = [Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for line in plane.lines if line.name == OPS_LINE
                       for e in line.events]
                _nest(ops)
                devices[plane.name] = ops
            elif plane.name.startswith("/host:"):
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name in HOST_SPANS]
        host.sort(key=lambda h: h[1])
        return cls(devices, host)

    def window(self) -> Interval:
        """From the first host span of the traced steps to the last."""
        if not self.host:
            raise ValueError("the trace holds none of the harness's spans")
        return self.host[0][1], max(h[2] for h in self.host)

    def busy(self, plane: str) -> List[Interval]:
        lo, hi = self.window()
        return clip(union((o.start, o.end) for o in self.devices[plane]),
                    lo, hi)

    def busy_s(self) -> float:
        """Seconds of the window in which an op ran, mean over chips."""
        return (sum(length(self.busy(p)) for p in self.devices)
                / len(self.devices) / 1e9)

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def op_seconds(self, top: int = 10) -> List[Tuple[str, float]]:
        """Self time per op, summed over its executions, mean over chips."""
        tot: Dict[str, float] = {}
        lo, hi = self.window()
        for ops in self.devices.values():
            for o in ops:
                if o.start >= lo and o.end <= hi:
                    tot[o.label()] = tot.get(o.label(), 0.0) + o.self_ns
        n = len(self.devices)
        return sorted(((k, v / n / 1e9) for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """Longest idle intervals of any chip, each named by the host span
        that overlaps it most (``other`` where none does)."""
        lo, hi = self.window()
        gaps = []
        for p in self.devices:
            gaps += subtract([(lo, hi)], self.busy(p))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, name = 0.0, "other"
            for h, hs, he in self.host:
                ov = min(e, he) - max(s, hs)
                if ov > best:
                    best, name = ov, h
            out.append((name, (e - s) / 1e9))
        return out

    def kernel_events(self, match) -> List[Op]:
        """Pallas kernel ops for which ``match(op)`` is true, all chips."""
        return [o for ops in self.devices.values() for o in ops
                if is_pallas(o.text) and match(o)]

    def collective_exposed_s(self) -> Optional[float]:
        """Seconds per chip in which a collective runs and no other leaf op
        does, mean over chips; None when the trace has no collective."""
        lo, hi = self.window()
        total, seen = 0.0, False
        for ops in self.devices.values():
            coll = [(o.start, o.end) for o in ops if o.is_collective]
            seen |= bool(coll)
            comp = [(o.start, o.end) for o in ops
                    if o.leaf and not o.is_collective]
            total += length(subtract(clip(union(coll), lo, hi),
                                     clip(union(comp), lo, hi)))
        return total / len(self.devices) / 1e9 if seen else None
