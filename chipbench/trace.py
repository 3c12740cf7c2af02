"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
time and idle gaps attributed to what the host was doing.

The window is the host span the benchmark opened around the traced work
(``WINDOW``).  Busy time is the union of the intervals in which an
operation ran on a device, clipped to the window and averaged over the
devices.  An idle gap is a stretch of the window with no operation on the
first device; it is labelled by the innermost host event on the window's
thread that overlaps it (the benchmark's own spans, or JAX's dispatch
events).  Per-operation time is summed over the devices: under tensor
parallelism each chip runs its share of every operation, so an
operation's time is that of all its shares.

On a TPU the operations' names are their HLO instructions, and a ``while``
or ``call`` spans the operations of its body: only operations that hold no
other count as time of their own.  Operations are grouped under their
opcode and result shape (``custom-call bf16[64,2,16,128]``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW = "chipbench:traced"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
_ORDINAL = re.compile("^" + re.escape(DEVICE_PREFIX) + r"(\d+)")


@dataclass
class Event:
    name: str
    start: float                  # seconds on the trace's clock
    end: float
    text: str = ""                # name and string stats, for matching


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)   # the window's thread
    window: Tuple[float, float] = (0.0, 0.0)


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]
    gaps: List[Tuple[str, float]]
    ops: List[Event]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, match: Callable[[str], bool]) -> float:
        """Summed device time of the operations whose text (name and
        string stats) ``match`` accepts."""
        return sum(e.end - e.start for e in self.ops if match(e.text))


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, "
                                f"found {found}")
    return found[0]


_HLO = re.compile(r"^%[\w.-]+ = (.+?) ([a-z][\w-]*)\(")


def label(name: str) -> str:
    """``opcode result-shape`` of an HLO instruction's text, layouts
    dropped; other names unchanged."""
    m = _HLO.match(name)
    if not m:
        return name
    shape = re.sub(r"\{[^{}]*\}", "", m.group(1))
    return f"{m.group(2)} {shape}"


def leaves(events: List[Event]) -> List[Event]:
    """The events that hold no other event of their line whole."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    parent = set()
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and order[stack[-1]].end >= e.end:
            parent.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(order) if i not in parent]


def _event(ev) -> Event:
    start = float(ev.start_ns) * 1e-9
    stats = []
    for k, v in ev.stats:
        if isinstance(v, str):
            stats.append(f"{k}={v}")
    return Event(ev.name, start, start + float(ev.duration_ns) * 1e-9,
                 " ".join([ev.name] + stats))


def _ordinal(plane_name: str) -> int:
    m = _ORDINAL.match(plane_name)
    return int(m.group(1)) if m else -1


def load(path: str, window: str = WINDOW,
         devices: Optional[Sequence[int]] = None) -> Trace:
    """Device operations and the window's host thread from ``path``; with
    ``devices``, the operations of the devices of those ids only (a plane
    is named by its device's id), in the order of their ids."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    host_lines = []
    for plane in sorted(data.planes, key=lambda p: _ordinal(p.name)):
        if plane.name.startswith(DEVICE_PREFIX):
            if devices is not None and _ordinal(plane.name) not in devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices[plane.name] = [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
    for line in host_lines:
        evs = [_event(e) for e in line.events]
        spans = [e for e in evs if e.name == window]
        if spans:
            tr.host = evs
            tr.window = (spans[0].start, spans[0].end)
            break
    else:
        raise ValueError(f"no host span {window!r} in {path}")
    if not tr.devices:
        raise ValueError(f"no {OPS_LINE!r} line on any {DEVICE_PREFIX} plane "
                         f"in {path}")
    return tr


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _label(host: List[Event], a: float, b: float) -> str:
    best: Optional[Event] = None
    for e in host:
        if e.start < b and e.end > a and (best is None or e.start > best.start):
            best = e
    return best.name if best is not None else "(no host event)"


def reduce(tr: Trace, top: int = 10) -> Reduction:
    w0, w1 = tr.window
    busy = 0.0
    op_s: Dict[str, float] = {}
    ops: List[Event] = []
    first_unions = None
    for evs in tr.devices.values():
        inside = []
        for e in leaves(evs):
            a, b = max(e.start, w0), min(e.end, w1)
            if b <= a:
                continue
            inside.append((a, b))
            ops.append(Event(e.name, a, b, e.text))
            key = label(e.name)
            op_s[key] = op_s.get(key, 0.0) + (b - a)
        u = _union(inside)
        busy += sum(b - a for a, b in u)
        if first_unions is None:
            first_unions = u
    busy /= len(tr.devices)
    gaps = []
    prev = w0
    for a, b in (first_unions or []) + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(tr.host, a, b), b - a) for a, b in gaps[:top]]
    return Reduction(w1 - w0, busy, op_s, labelled, ops)


def top_ops(r: Reduction, top: int = 10) -> List[Tuple[str, float]]:
    return sorted(r.op_s.items(), key=lambda kv: -kv[1])[:top]
