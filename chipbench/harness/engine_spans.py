"""The program's own spans and named scopes in a traced run's profile.

``harness.trace.load`` keeps the benchmark's ``bench.*`` spans and the
device operations' names.  This reads the same ``.xplane.pb`` once more
per run (``of(r)`` keeps what it read on the readings) and keeps:

* the host spans ``bench.*`` and ``engine.*`` (``ContinuousEngine``'s
  own: ``engine.tick`` and the phases inside it), on the device
  operations' clock;
* each device operation's scope: the innermost of the model's
  ``jax.named_scope`` names (``SCOPES``) in the operation's ``tf_op``
  stat, the JAX name stack that XLA keeps as the instruction's
  ``op_name``; ``unscoped`` where it names none.

``jax.profiler.ProfileData`` does not expose an event's metadata, where
``tf_op`` lives, so the file is parsed here against the few fields of
the profiler's ``xplane.proto`` that this needs.  Both splits give each
instant to the innermost event open at it: an idle gap to the innermost
host span, device time to the innermost operation (a loop's event holds
the events of the operations inside it, so its own time is what they
leave).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import trace as TRC

SCOPES = ("embed", "head", "layer", "attention", "ssm", "mlp", "moe",
          "kv_cache", "sample")
UNSCOPED = "unscoped"
PREFIXES = ("bench.", "engine.")
TICK, FETCH, DISPATCH = "engine.tick", "engine.fetch", "engine.dispatch"
#: the engine's tick programs, by their module names in the trace
TICK_PROGRAMS = "_tick("


@functools.lru_cache(maxsize=1)
def _xspace():
    """The message class of an XSpace, cut to the fields read here (field
    numbers as in the profiler's ``xplane.proto``; maps are repeated
    key/value entries on the wire)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, STR = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    # (field, number, scalar type or message name, repeated)
    messages = {
        "XStat": [("metadata_id", 1, I64, False), ("str_value", 5, STR, False),
                  ("ref_value", 7, U64, False)],
        "XEvent": [("metadata_id", 1, I64, False), ("offset_ps", 2, I64, False),
                   ("duration_ps", 3, I64, False)],
        "XLine": [("name", 2, STR, False), ("timestamp_ns", 3, I64, False),
                  ("events", 4, "XEvent", True)],
        "XEventMetadata": [("name", 2, STR, False), ("stats", 5, "XStat", True)],
        "XStatMetadata": [("name", 2, STR, False)],
        "EventEntry": [("key", 1, I64, False),
                       ("value", 2, "XEventMetadata", False)],
        "StatEntry": [("key", 1, I64, False),
                      ("value", 2, "XStatMetadata", False)],
        "XPlane": [("name", 2, STR, False), ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "EventEntry", True),
                   ("stat_metadata", 5, "StatEntry", True)],
        "XSpace": [("planes", 1, "XPlane", True)],
    }
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    for name, fields in messages.items():
        m = fdp.message_type.add(name=name)
        for fname, num, typ, repeated in fields:
            f = m.field.add(name=fname, number=num, label=(
                F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL))
            if isinstance(typ, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".chipbench_xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def scope(text: str) -> str:
    """The innermost ``SCOPES`` name in a JAX name stack: a ``tf_op``
    value (``jit(f)/.../attention/dot_general:``) or an HLO instruction's
    text holding ``op_name="..."``; ``unscoped`` where there is none."""
    m = re.search(r'op_name="([^"]*)"', text)
    path = (m.group(1) if m else text).rsplit(":", 1)[0].split("/")
    return next((p for p in reversed(path[:-1]) if p in SCOPES), UNSCOPED)


@dataclass
class Profile:
    """A traced run's device operations and ``bench.*``/``engine.*``
    spans (``trace``), each operation's scope (``scopes``, in the order
    of ``trace.ops``), the device's program runs (``modules``: name,
    start, end), reduced over the window (``red``)."""
    trace: TRC.Trace
    scopes: List[str] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)

    def __post_init__(self):
        self.red = TRC.Reduced(self.trace)


def load(directory: str, device_prefix: str = "/device:TPU:0") -> Profile:
    """Read the one ``.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{len(paths)} xplane files under {directory}")
    space = _xspace()()
    with open(paths[0], "rb") as f:
        space.ParseFromString(f.read())
    tr, scopes, modules = TRC.Trace(), [], []
    for plane in space.planes:
        device = plane.name == device_prefix
        if not device and not plane.name.startswith("/host:"):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        if device:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            tf_op = {}
            for e in plane.event_metadata:
                for st in e.value.stats:
                    if stat_names.get(st.metadata_id) == "tf_op":
                        tf_op[e.key] = st.str_value or stat_names.get(
                            st.ref_value, "")
            scope_of = {k: scope(v) for k, v in tf_op.items()}
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                s = line.timestamp_ns + ev.offset_ps * 1e-3
                e = s + ev.duration_ps * 1e-3
                if line.name == "XLA Modules":
                    modules.append((name, s, e))
                elif device:
                    tr.ops.append((TRC.op_name(name), s, e, ""))
                    scopes.append(scope_of.get(ev.metadata_id, UNSCOPED))
                elif name.startswith(PREFIXES):
                    tr.spans.append((name, s, e))
    return Profile(tr, scopes, modules)


def of(r) -> Optional[Profile]:
    """The profile of a traced run, read once and kept on the readings;
    None for a run without a trace."""
    if r.trace is None:
        return None
    if getattr(r, "engine_profile", None) is None:
        from .cell import TRACE_DIR
        r.engine_profile = load(TRACE_DIR)
    return r.engine_profile


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def innermost(events, parts) -> Dict[object, float]:
    """Length of ``parts`` (disjoint, sorted intervals) by the innermost
    of ``events`` (``(label, start, end)``, nested as one thread's spans
    or one device's operations are) open at each instant: of those open,
    the latest to start (the shortest, among those that start together).
    Instants inside no event go under None."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    bounds = sorted({t for _, s, e in evs for t in (s, e)}
                    | {t for p in parts for t in p})
    out: Dict[object, float] = defaultdict(float)
    stack: List[tuple] = []
    i = k = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(evs) and evs[i][1] <= a:
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        while k < len(parts) and parts[k][1] <= a:
            k += 1
        if k < len(parts) and parts[k][0] <= a:
            out[stack[-1][0] if stack else None] += b - a
    return out


def tick_host_ms(red: TRC.Reduced) -> List[float]:
    """For each ``engine.tick`` span wholly inside the window, its length
    less the ``engine.fetch`` spans inside it, ms."""
    fetch = TRC.merge((s, e) for n, s, e in red.spans if n == FETCH)
    return [(e - s - TRC.overlap(fetch, s, e)) * 1e-6
            for n, s, e in red.spans
            if n == TICK and s >= red.lo and e <= red.hi]


def idle_by_span(red: TRC.Reduced) -> List[Tuple[str, float]]:
    """The window's device idle time, s, by innermost host span
    (``host.other`` outside every span), largest first."""
    by = innermost(red.spans, TRC.gaps(red.busy, red.lo, red.hi))
    return sorted(((n or TRC.OTHER, t * 1e-9) for n, t in by.items()),
                  key=lambda kv: -kv[1])


def launch_lag_ms(prof: Profile) -> List[float]:
    """For each ``engine.dispatch`` span in the window, the start of the
    tick program run nearest to it less the span's start, ms: the host's
    call reaching the device, and any offset between the host's and the
    device's clocks in the trace (negative: the device's clock runs
    early)."""
    red = prof.red
    starts = sorted(s for n, s, _ in prof.modules if TICK_PROGRAMS in n)
    out = []
    for n, s, _ in red.spans:
        if n == DISPATCH and red.lo <= s < red.hi and starts:
            j = bisect.bisect_left(starts, s)
            near = min(starts[max(j - 1, 0):j + 1], key=lambda t: abs(t - s))
            out.append((near - s) * 1e-6)
    return out


def device_by_op(prof: Profile) -> Dict[Tuple[str, str], float]:
    """The window's device busy time, s, by (scope, operation), each
    instant given to the innermost operation."""
    red = prof.red
    evs = [((sc, n), s, e) for (n, s, e, _), sc in
           zip(prof.trace.ops, prof.scopes) if e > red.lo and s < red.hi]
    return {k: t * 1e-9 for k, t in innermost(evs, red.busy).items() if k}


def notes(prof: Profile, top: int = 3) -> List[str]:
    """The idle split by span and the device split by scope, each scope
    with its largest operations."""
    red = prof.red
    idle = idle_by_span(red)
    out = [f"device idle by innermost host span, s, of {sum(t for _, t in idle)!r}"
           f" idle in a {red.window_s!r} s slice: "
           + ", ".join(f"{n} {t!r}" for n, t in idle)]
    by_op = device_by_op(prof)
    by_scope: Dict[str, float] = defaultdict(float)
    for (sc, _), t in by_op.items():
        by_scope[sc] += t
    parts = []
    for sc, t in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        ops = sorted(((n, u) for (s, n), u in by_op.items() if s == sc),
                     key=lambda kv: -kv[1])[:top]
        parts.append(f"{sc} {t!r} ({', '.join(f'{n} {u!r}' for n, u in ops)})")
    lag = launch_lag_ms(prof)
    if lag:
        out.append(f"tick program start less engine.dispatch start, ms, "
                   f"over {len(lag)} dispatches: min {min(lag)!r}, median "
                   f"{float(np.median(lag))!r}, max {max(lag)!r}")
    out.append(f"device time by innermost scope, s, of {red.busy_s!r} busy: "
               + "; ".join(parts))
    return out
