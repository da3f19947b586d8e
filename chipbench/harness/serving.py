"""Drive the serving engine through its public ``submit``/``tick``.

``Driver`` wraps ``ContinuousEngine.tick``: it reads the engine's public
host state (``slots``, ``state``, ``lengths``, ``prefill_pos``,
``gen_count``) before and after each tick and records what the tick
did — which positions it decoded, which prompt chunk it prefilled, when
each request was admitted — inside a ``bench.tick`` span of the profiler's trace.

``warm`` drives the engine through every program a mix can reach before
the window opens; ``open_loop`` is the window.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax

from . import traffic as TR


@dataclass
class Served:
    """One request as the benchmark sees it."""
    planned: TR.Planned
    req: object                       # the engine's Request
    due: float = 0.0                  # wall time it was due (open loop)
    sent: float = 0.0                 # wall time it was submitted
    admitted: Optional[float] = None  # wall time of the tick that admitted it


@dataclass
class TickRecord:
    t0: float
    t1: float
    decode_pos: List[int] = field(default_factory=list)  # positions decoded
    chunk: Optional[Tuple[int, int]] = None   # (offset, rows)


class Driver:
    def __init__(self, engine, decode_state: int, prefill_state: int):
        self.eng = engine
        self.DECODE, self.PREFILL = decode_state, prefill_state
        self.ticks: List[TickRecord] = []
        self.record = False
        self.served_by_req: Dict[int, Served] = {}

    def submit(self, s: Served):
        with jax.profiler.TraceAnnotation("bench.submit"):
            s.sent = time.time()
            self.served_by_req[id(s.req)] = s
            self.eng.submit(s.req)

    def tick(self) -> bool:
        e = self.eng
        before = [(id(r) if r is not None else None, st, n, pp, g)
                  for r, st, n, pp, g in zip(e.slots, e.state, e.lengths,
                                             e.prefill_pos, e.gen_count)]
        t0 = time.time()
        with jax.profiler.TraceAnnotation("bench.tick"):
            worked = e.tick()
        t1 = time.time()
        if not worked:
            return False
        rec = TickRecord(t0, t1)
        for i, (rid, st, n, pp, g) in enumerate(before):
            r = e.slots[i]
            if r is None:
                continue
            same = id(r) == rid
            if not same:
                s = self.served_by_req.get(id(r))
                if s is not None and s.admitted is None:
                    s.admitted = t0
            if same and st == self.DECODE:
                n_after = e.lengths[i] if e.state[i] == self.DECODE \
                    else n + (e.gen_count[i] - g)
                rec.decode_pos += range(n, n_after)
            was_prefill = (not same) or st == self.PREFILL
            off = 0 if not same else pp
            if was_prefill and e.prefill_pos[i] > off:
                rec.chunk = (off, e.prefill_pos[i] - off)
        if self.record:
            self.ticks.append(rec)
        return True


def _request(planned: TR.Planned, seed: int, vocab: int, make_request):
    return make_request(planned.rid, TR.prompt_tokens(
        seed, planned.rid, planned.prompt_len, vocab), planned.out_len)


def warm(drv: Driver, mix: dict, vocab: int, seed: int,
         make_request) -> int:
    """Drive every tick program the mix can reach through the engine
    (``sync``: one token per decode step); returns ticks run.

    Prompts lie on the prefill-chunk grid, so a mix reaches a fixed set
    of programs, one per KV page bound (the engine's ``page``): a prefill
    chunk at each prompt offset; decoding at every page a request can
    reach; both together.  For each prompt length a pacer request
    decodes until it has entered the last page its requests can reach;
    at each page it enters, a longest prompt is prefilled beside it.
    Every warm-up request has a length the mix can send.
    """
    e = drv.eng
    plo, phi = TR.bounds(mix["prompt"])
    olo, ohi = TR.bounds(mix["output"])
    rid = [10 ** 6]

    def new(plen, out):
        rid[0] += 1
        s = Served(TR.Planned(rid[0], plen, out), _request(
            TR.Planned(rid[0], plen, out), seed, vocab, make_request))
        drv.submit(s)
        return s

    def run_until(reqs, on_tick: Callable = lambda: None):
        n = 0
        while not all(s.req.done for s in reqs):
            drv.tick()
            n += 1
            on_tick()
        return n

    def page_of(n):
        return -(-n // e.page)

    prefill_ticks = -(-phi // e.chunk)
    ticks = 0
    # a longest prompt alone: every prefill offset with nothing decoding
    ticks += run_until([new(phi, 1)])
    for k, plen in enumerate(TR.grid_values(mix["prompt"])):
        # decode until the last page this prompt length can reach, then
        # long enough to prefill a longest prompt beside it
        last = (page_of(plen + ohi) - 1) * e.page
        need = max(last - plen, 0) + prefill_ticks + 2
        out = next((o for o in TR.grid_values(mix["output"]) if o >= need),
                   ohi)
        live = [new(plen, out)]
        if k == 0:
            # every slot once
            live += [new(plen, olo) for _ in range(e.batch - 1)]
        pacer = live[0]
        seen = set()
        side: List[Served] = []

        def inject():
            dec = [e.lengths[i] for i in range(e.batch)
                   if e.state[i] == drv.DECODE]
            if pacer.req.done or not dec:
                return
            page = page_of(max(dec) + 1)
            # a longest prompt beside the decoding pacer
            if page not in seen and not any(
                    not s.req.done and s.planned.prompt_len == phi
                    for s in side):
                seen.add(page)
                side.append(new(phi, 1))

        ticks += run_until(live, inject)
        ticks += run_until(side)
    return ticks


def open_loop(drv: Driver, plan: List[TR.Planned], seconds: float,
              requests: List[object], on_tick: Callable = lambda: None,
              on_close: Callable = lambda: None, drain_s: float = 120.0
              ) -> Tuple[List[Served], float, float]:
    """Send each request when due; returns (served, t_open, t_close).
    Requests still unfinished when the window closes are drained."""
    served = [Served(p, r) for p, r in zip(plan, requests)]
    t_open = time.time()
    for s in served:
        s.due = t_open + s.planned.due
        s.req.t_submit = s.due
    end = t_open + seconds
    i, n = 0, len(served)
    drv.record = True
    while True:
        now = time.time()
        while i < n and served[i].due <= now:
            drv.submit(served[i])
            i += 1
        if now >= end:
            break
        if drv.tick():
            on_tick(t_open)
        else:
            nxt = served[i].due if i < n else end
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, end) - time.time()))
    t_close = time.time()
    drv.record = False
    on_close()
    stop = t_close + drain_s
    while not all(s.req.done for s in served[:i]) and time.time() < stop:
        drv.tick()
    return served[:i], t_open, t_close

