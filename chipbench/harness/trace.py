"""Profiler traces: capture, normalise, reduce.

A trace is normalised to three lists on one clock (nanoseconds): the
device operations of chip 0 (``ops``: name, start, end, program), the
benchmark's own host spans (``spans``: ``bench.*`` annotations) and the
traced window (the ``bench.window`` span).  The reduction works on those
lists alone, so a small recorded trace checks it without a chip.

* busy: the union of the device operations' intervals inside the window;
  idle share is one minus busy over the window.
* time by name: summed durations of the operations whose name, or whose
  program's name, contains a given string.
* idle gaps: the stretches of the window with no device operation, each
  given to the innermost host span that holds its midpoint.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]
WINDOW = "bench.window"
OTHER = "host.other"


@dataclass
class Trace:
    ops: List[Tuple[str, float, float, str]] = field(default_factory=list)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"ops": self.ops, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls([tuple(o) for o in d["ops"]], [tuple(s) for s in d["spans"]])

    def window(self) -> Interval:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if len(w) != 1:
            raise ValueError(f"{len(w)} {WINDOW} spans in the trace")
        return w[0]


def _device_line(plane) -> Optional[object]:
    lines = {ln.name: ln for ln in plane.lines}
    return lines.get("XLA Ops")


def op_name(text: str) -> str:
    """An operation's name from the trace's event name, which on a TPU
    is the HLO instruction's whole text (``%name = type op(...)``)."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(directory: str, device_prefix: str = "/device:TPU:0") -> Trace:
    """Read the one ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{len(paths)} xplane files under {directory}")
    pd = ProfileData.from_file(paths[0])
    tr = Trace()
    for plane in pd.planes:
        if plane.name == device_prefix:
            line = _device_line(plane)
            if line is None:
                continue
            for ev in line.events:
                st = dict(ev.stats)
                tr.ops.append((op_name(ev.name), ev.start_ns, ev.end_ns,
                               str(st.get("hlo_module", ""))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        tr.spans.append((ev.name, ev.start_ns, ev.end_ns))
    return tr


def fresh_dir(directory: str) -> str:
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    return directory


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def overlap(merged: List[Interval], lo: float, hi: float) -> float:
    """Length of ``merged`` (disjoint, sorted) inside [lo, hi)."""
    return total(clip(merged, lo, hi))


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, e in clip(merged, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


class Reduced:
    """Everything the per-layer readers take from one trace."""

    def __init__(self, tr: Trace):
        self.trace = tr
        self.lo, self.hi = tr.window()
        self.window_s = (self.hi - self.lo) * 1e-9
        ops = [o for o in tr.ops if o[2] > self.lo and o[1] < self.hi]
        self.ops = ops
        self.busy = merge((s, e) for _, s, e, _ in ops)
        self.busy = clip(self.busy, self.lo, self.hi)
        self.busy_s = total(self.busy) * 1e-9
        self.spans = [s for s in tr.spans if s[0] != WINDOW]

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, needle: str) -> float:
        """Device time of operations whose name or program name contains
        ``needle``: the union of their intervals, since a loop's event
        holds the events of the operations inside it."""
        return 1e-9 * total(clip(merge(
            (s, e) for n, s, e, m in self.ops if needle in n or needle in m),
            self.lo, self.hi))

    def op_count(self, needle: str) -> int:
        return sum(1 for n, _, _, m in self.ops if needle in n or needle in m)

    def span_idle(self, name: str) -> Tuple[float, float]:
        """(seconds inside spans named ``name``, seconds of those with
        no device operation running)."""
        inside = merge(clip([(s, e) for n, s, e in self.spans if n == name],
                            self.lo, self.hi))
        t = total(inside)
        busy = sum(overlap(self.busy, s, e) for s, e in inside)
        return t * 1e-9, (t - busy) * 1e-9

    def top_ops(self, k: int = 10) -> List[list]:
        by: Dict[str, float] = defaultdict(float)
        for n, s, e, _ in self.ops:
            by[n] += (min(e, self.hi) - max(s, self.lo)) * 1e-9
        return [[n, t] for n, t in sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def idle_by_span(self, k: int = 10) -> List[list]:
        spans = sorted(self.spans, key=lambda s: s[2] - s[1])
        by: Dict[str, float] = defaultdict(float)
        for s, e in gaps(self.busy, self.lo, self.hi):
            mid = (s + e) / 2
            name = next((n for n, a, b in spans if a <= mid < b), OTHER)
            by[name] += (e - s) * 1e-9
        return [[n, t] for n, t in sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def save_json(tr: Trace, path: str):
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)
