"""Reduction of a profiler trace to device numbers.

A trace is read into plain events (plane, line, name, start, duration,
stats), so the reduction can be tested on a small trace recorded on the
chip and committed as JSON (``bench/tests/data``). From the events:

- each device's busy time: the union of the intervals in which an op of
  its ``XLA Ops`` line ran;
- each op's device time, by its name without the numeric suffix (a Pallas
  kernel's name is its kernel's) and by its HLO opcode; ops that only hold
  others (``while``) are not counted twice;
- the idle gaps of a device, each named by the benchmark's host span
  (``jax.profiler.TraceAnnotation``) it fell in.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_NAMES = ("client", "submit", "step", "cancel")


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(path: Path, keep=None) -> List[Event]:
    """Events of an ``.xplane.pb``; ``keep(plane, line)`` filters lines."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            if keep is not None and not keep(plane.name, line.name):
                continue
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 {k: v for k, v in e.stats}))
    return out


def relevant(plane: str, line: str) -> bool:
    """The lines the reduction reads: device ops and the host threads."""
    if plane.startswith("/device:TPU:"):
        return line == OPS_LINE
    return plane == HOST_PLANE


def save_json(events: Sequence[Event], path: Path) -> None:
    rows = [[e.plane, e.line, e.name, e.start_ns, e.dur_ns,
             {k: v for k, v in e.stats.items()
              if isinstance(v, (int, float, str))}] for e in events]
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def load_json(path: Path) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to the window [t0, t1)."""
    out = []
    for e in events:
        s, end = max(e.start_ns, t0), min(e.end_ns, t1)
        if end > s:
            out.append(Event(e.plane, e.line, e.name, s, end - s, e.stats))
    return out


def device_ops(events: Iterable[Event]) -> Dict[str, List[Event]]:
    by = defaultdict(list)
    for e in events:
        if e.plane.startswith("/device:TPU:") and e.line == OPS_LINE:
            by[e.plane].append(e)
    return dict(by)


_SUFFIX = re.compile(r"[._]\d+$")
_HLO = re.compile(r"^%?([^\s=]+)\s*=\s*.*?\s([a-z][a-z0-9_-]*)\(")
# ops that only hold others (their time is their body's)
CONTAINERS = ("while", "conditional", "call")


def hlo_parts(name: str) -> Tuple[str, str]:
    """(short name, opcode) of an op whose trace name is its HLO text,
    ``%paged_attention.6 = bf16[...] custom-call(...)`` →
    ``("paged_attention.6", "custom-call")``; a bare name is its own."""
    m = _HLO.match(name)
    if m is None:
        return name, ""
    return m.group(1), m.group(2)


def op_family(e: Event) -> str:
    """An op's short name without its numeric suffix: ``fusion.12`` →
    ``fusion``; a Pallas kernel keeps its kernel name."""
    name = hlo_parts(e.name)[0]
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return name


def opcode(e: Event) -> str:
    return hlo_parts(e.name)[1]


def host_spans(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if e.plane == HOST_PLANE
            and e.name in SPAN_NAMES]


def idle_gaps(ops: Sequence[Event], spans: Sequence[Event], t0: float,
              t1: float) -> List[Tuple[str, float]]:
    """Gaps between busy intervals of one device inside [t0, t1), each
    named by the innermost host span that covers the gap's midpoint
    (``idle`` when none does), longest first, in seconds."""
    gaps, cur = [], t0
    for s, e in sorted((o.start_ns, o.end_ns) for o in ops):
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        name = min(cover, key=lambda sp: sp.dur_ns).name if cover else "idle"
        named.append((name, (e - s) / 1e9))
    return sorted(named, key=lambda x: -x[1])


@dataclass
class Reduction:
    window_s: float
    busy_s: Dict[str, float]                 # device plane -> busy seconds
    op_s: Dict[str, Dict[str, float]]        # plane -> op family -> seconds
    opcode_s: Dict[str, Dict[str, float]]    # plane -> HLO opcode -> seconds
    gaps: List[Tuple[str, float]]            # of the first device

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def mean_op_s(self, pred) -> float:
        """Mean over devices of the time of ops whose family passes
        ``pred``."""
        tot = [sum(s for name, s in ops.items() if pred(name))
               for ops in self.op_s.values()]
        return sum(tot) / max(len(tot), 1)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        merged = defaultdict(float)
        for ops in self.op_s.values():
            for name, s in ops.items():
                merged[name] += s / len(self.op_s)
        return sorted(merged.items(), key=lambda x: -x[1])[:n]


def reduce(events: Sequence[Event], t0: float, t1: float) -> Reduction:
    """Device numbers over the window [t0, t1) (ns, the trace's clock)."""
    events = clip(events, t0, t1)
    devs = device_ops(events)
    spans = host_spans(events)
    busy, op_s, code_s = {}, {}, {}
    for plane, ops in sorted(devs.items()):
        busy[plane] = union_ns((o.start_ns, o.end_ns) for o in ops) / 1e9
        by_op, by_code = defaultdict(float), defaultdict(float)
        for o in ops:
            if opcode(o) in CONTAINERS:
                continue
            by_op[op_family(o)] += o.dur_ns / 1e9
            by_code[opcode(o)] += o.dur_ns / 1e9
        op_s[plane], code_s[plane] = dict(by_op), dict(by_code)
    first: Optional[str] = min(devs) if devs else None
    gaps = idle_gaps(devs[first], spans, t0, t1) if first else []
    return Reduction((t1 - t0) / 1e9, busy, op_s, code_s, gaps)


def GEMM_OPS(family: str) -> bool:
    """Ops that do the projection and head GEMMs: the MatrixFlow kernels
    and XLA's own matmuls (``convolution``/``dot``, fused or not)."""
    return (family.startswith("matrixflow_gemm") or "convolution" in family
            or family.startswith("dot") or "_dot" in family)


def ATTENTION_OPS(family: str) -> bool:
    return family.startswith(("paged_attention", "flash_attention"))
