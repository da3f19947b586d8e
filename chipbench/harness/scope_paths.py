"""Each device operation's whole name stack in a traced run, and the
share of the device's busy time spent in one named scope.

``engine_spans`` gives each operation the innermost of its fixed
``SCOPES``, so a scope nested inside one of them (``ssm_inputs`` inside
``ssm``) is lost there.  This reads the same ``.xplane.pb`` once more
per run (``of(r)`` keeps what it read on the readings) and keeps, for
each device operation, its ``tf_op`` stat whole: the JAX name stack
that XLA keeps as the instruction's ``op_name``
(``jit(_chunk_tick)/kv_cache/while/body/closed_call/layer/ssm/ssm_inputs/exp:``).
Device time goes, at each instant, to the innermost operation open
then, as in ``engine_spans``.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import List, Optional

from . import engine_spans as ES
from . import trace as TRC


@dataclass
class Paths:
    """A traced run's device operations (``trace.ops``) with the
    ``bench.window`` span, and each operation's name stack (``paths``,
    in the order of ``trace.ops``; empty where the trace gives none)."""
    trace: TRC.Trace
    paths: List[str] = field(default_factory=list)

    def __post_init__(self):
        self.red = TRC.Reduced(self.trace)


def components(path: str) -> List[str]:
    """The named scopes and calls of a ``tf_op`` name stack, outermost
    first, without the operation itself (the last component)."""
    return path.rsplit(":", 1)[0].split("/")[:-1]


def load(directory: str, device_prefix: str = "/device:TPU:0") -> Paths:
    """Read the one ``.xplane.pb`` under ``directory``."""
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"{len(files)} xplane files under {directory}")
    space = ES._xspace()()
    with open(files[0], "rb") as f:
        space.ParseFromString(f.read())
    tr, paths = TRC.Trace(), []
    for plane in space.planes:
        device = plane.name == device_prefix
        if not device and not plane.name.startswith("/host:"):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        tf_op = {}
        if device:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            for e in plane.event_metadata:
                for st in e.value.stats:
                    if stat_names.get(st.metadata_id) == "tf_op":
                        tf_op[e.key] = st.str_value or stat_names.get(
                            st.ref_value, "")
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                s = line.timestamp_ns + ev.offset_ps * 1e-3
                e = s + ev.duration_ps * 1e-3
                if device:
                    tr.ops.append((TRC.op_name(name), s, e, ""))
                    paths.append(tf_op.get(ev.metadata_id, ""))
                elif name == TRC.WINDOW:
                    tr.spans.append((name, s, e))
    return Paths(tr, paths)


def of(r) -> Optional[Paths]:
    """The paths of a traced run, read once and kept on the readings;
    None for a run without a trace."""
    if r.trace is None:
        return None
    if getattr(r, "scope_paths", None) is None:
        from .cell import TRACE_DIR
        r.scope_paths = load(TRACE_DIR)
    return r.scope_paths


def seconds_in(p: Paths, scope: str) -> Optional[float]:
    """Device busy time in the window, s, whose innermost operation's
    name stack holds ``scope``; None where no operation's does."""
    red = p.red
    evs = [(scope in components(path), s, e)
           for (_, s, e, _), path in zip(p.trace.ops, p.paths)
           if e > red.lo and s < red.hi]
    if not any(inside for inside, _, _ in evs):
        return None
    return ES.innermost(evs, red.busy).get(True, 0.0) * 1e-9


def share(r, scope: str) -> Optional[float]:
    """``seconds_in`` over the window's device busy time, %."""
    p = of(r)
    if p is None or p.red.busy_s <= 0:
        return None
    t = seconds_in(p, scope)
    return None if t is None else 100.0 * t / p.red.busy_s
