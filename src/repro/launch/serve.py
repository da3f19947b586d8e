"""Batched serving launcher: continuous batching over PolyTOPS-planned
kernels.

    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b \
        [--batch 4 --prompt-len 256 --gen 32 --chunk 256 --max-len N] \
        [--seed 0] [--pallas] [--smoke]

Without ``--smoke`` the arch is served at its published widths, with
random weights made from ``--seed``.

:class:`ContinuousEngine` serves on the model's step functions
(``chunk_step`` and ``serve_decode_step``): per-request admission into
free slots, prompt prefill in fixed-size chunks interleaved with decode
ticks (a long prompt never stalls in-flight decodes), ragged per-slot
cache lengths, and paged KV — the decode tick reads only the
page-aligned used prefix of the cache, page size from
``plan_attention``'s k tile (a stack with no attention layer reads no
KV and takes one bound, ``max_len``, for every tick, so each tick kind
compiles once).  One host sync per tick.  With ``use_pallas=True`` the
model layers route through the Pallas kernels (flash attention with the
SMEM q-offset for prefill chunks, the fused scan+gate kernel for Mamba
archs) — see :mod:`repro.model.pallas_mode`.
"""
from __future__ import annotations

import argparse
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..configs.registry import get_arch
from ..model import pallas_mode
from ..model import transformer as T


@dataclass
class Request:
    rid: int
    prompt: jnp.ndarray            # (1, plen)
    generated: List[int] = field(default_factory=list)
    done: bool = False
    max_new: int = 0               # 0 = engine default
    t_submit: float = 0.0
    t_admit: float = 0.0           # taken into a slot
    t_first: float = 0.0           # first generated token (prefill done)
    token_times: List[float] = field(default_factory=list)


FREE, PREFILL, DECODE = 0, 1, 2


def attends(cfg) -> bool:
    """True if some decoder layer is attention, i.e. reads KV rows."""
    return any(s.mixer == "attn" for s in T.layer_specs(cfg, "decoder"))


class ContinuousEngine:
    """Continuous-batching engine: per-request admission, chunked
    prefill interleaved with decode ticks, ragged paged KV.

    All decode-loop state (last token, per-slot lengths, generated-token
    buffer) lives on device and is updated functionally inside the jit'd
    ticks, so the steady-state loop dispatches work without a single
    host sync — tokens are fetched in one blocking read per *request*
    (at retirement), not per token.  The host keeps an exact mirror of
    lengths/counters (greedy decoding with a token budget is
    deterministic bookkeeping), so admission and retirement decisions
    never have to read the device.  ``eos``-triggered stopping and
    ``sync=True`` (per-token latency measurement) opt back into one
    fetch per tick."""

    def __init__(self, cfg, params, batch: int, max_len: int, *,
                 chunk: int = 16, page: Optional[int] = None,
                 use_pallas: bool = False, max_new: int = 16,
                 eos: Optional[int] = None, sync: bool = False,
                 pallas_opts: Optional[Dict] = None):
        from ..core import akg

        self.cfg, self.params = cfg, params
        self.batch, self.max_len = batch, max_len
        self.chunk, self.max_new, self.eos = chunk, max_new, eos
        self.sync = sync or eos is not None
        # pallas_opts: extra PallasMode fields (threshold overrides for
        # small-shape parity tests; see model/pallas_mode.py)
        self._mode_kw = dict(enabled=use_pallas, **(pallas_opts or {}))
        if attends(cfg):
            # paged-KV geometry from the scheduler: the attention plan's
            # k tile is the unit the flash kernel streams, so pages align
            # with kernel blocks and the page bound costs no masking slop
            plan = akg.plan_attention(max(chunk, 8), max_len, cfg.hd)
            self.page = page or max(min(plan.tile.get("kk", 128), max_len),
                                    8)
        else:
            # no layer reads KV rows: one bound for every tick, so each
            # tick kind compiles once
            self.page = page or max_len

        self.cache = T.init_cache(cfg, batch, max_len)
        # device-resident decode state: (tokens (b,1), lengths (b,),
        # out_buf (b, max_new), out_pos (b,), finite () — False once any
        # tick produced a NaN or infinite logit)
        self.dev = self._fresh_dev(max_new)
        # last-position logits of the newest prefill chunk (device array)
        self.prefill_logits = None
        self.lengths = [0] * batch          # host mirror of dev[1]
        self.gen_count = [0] * batch        # host mirror of dev[3]
        self.state = [FREE] * batch
        self.slots: List[Optional[Request]] = [None] * batch
        self.prefill_pos = [0] * batch
        self.queue: Deque[Request] = deque()
        self._active = jnp.zeros((batch,), bool)
        # tick accounting for the prefill/decode overlap ratio
        self.ticks = self.ticks_decode = self.ticks_prefill = 0
        self.ticks_overlap = 0

        def _decode_tick(p, c, dev, act, kv):
            toks, lens, buf, pos, ok = dev
            logits, c = T.serve_decode_step(p, cfg, toks, c, lens, act, kv)
            with jax.named_scope("sample"):
                ok = ok & jnp.all(jnp.isfinite(logits) | ~act[:, None])
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)    # (b,)
                toks = jnp.where(act[:, None], nxt[:, None], toks)
                lens = lens + act
                upd = jax.vmap(lambda b, t, i:
                               jax.lax.dynamic_update_slice(b, t[None], (i,)))
                buf = jnp.where(act[:, None], upd(buf, nxt, pos), buf)
                pos = pos + act
            return c, (toks, lens, buf, pos, ok), nxt

        def _chunk_tick(p, toks, c, dev, off, slot, last, kv):
            sub = T.cache_slot_view(c, slot)
            logits, sub = T.chunk_step(p, cfg, toks, sub, off, kv)
            c = T.cache_slot_write(c, sub, slot)
            t, lens, buf, pos, ok = dev
            with jax.named_scope("sample"):
                ok = ok & jnp.all(jnp.isfinite(logits))
                sl = jnp.arange(t.shape[0]) == slot
                end = off + toks.shape[1]
                lens = jnp.where(sl, end, lens)
                # final chunk: its last-position logits seed decoding
                ctok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
                fin = sl & last
                t = jnp.where(fin[:, None], ctok, t)
                buf = jnp.where(fin[:, None]
                                & (jnp.arange(buf.shape[1]) == 0)[None, :],
                                ctok, buf)
                pos = jnp.where(fin, 1, pos)
            return c, (t, lens, buf, pos, ok), logits[0, -1]

        def _mixed_tick(p, toks, c, dev, act, off, slot, last, kv_d, kv_p):
            # overlap tick: decode every active slot AND land one prefill
            # chunk in a single dispatch.  Decode runs first: its garbage
            # write into the prefilling slot (row = that slot's current
            # length) is overwritten by the chunk that follows.
            c, dev, nxt = _decode_tick(p, c, dev, act, kv_d)
            c, dev, last_logits = _chunk_tick(p, toks, c, dev, off, slot,
                                              last, kv_p)
            return c, dev, nxt, last_logits

        def _decode_k(p, c, dev, act, kv, k):
            # k decode steps fused into one dispatch (steady state: no
            # prefill pending, so nothing competes for the tick)
            def body(carry, _):
                c, dev = carry
                c, dev, _ = _decode_tick(p, c, dev, act, kv)
                return (c, dev), None
            (c, dev), _ = jax.lax.scan(body, (c, dev), None, length=k)
            return c, dev

        self._decode = jax.jit(_decode_tick, static_argnames=("kv",),
                               donate_argnums=(1, 2))
        self._decode_k = jax.jit(_decode_k, static_argnames=("kv", "k"),
                                 donate_argnums=(1, 2))
        self._chunk = jax.jit(_chunk_tick, static_argnames=("kv",),
                              donate_argnums=(2, 3))
        self._mixed = jax.jit(_mixed_tick,
                              static_argnames=("kv_d", "kv_p"),
                              donate_argnums=(2, 3))

        def _admit(c, dev, s):
            t, lens, buf, pos, ok = dev
            sl = jnp.arange(t.shape[0]) == s
            return (T.zero_cache_slot(c, s),
                    (t, jnp.where(sl, 0, lens), buf, jnp.where(sl, 0, pos),
                     ok))

        self._admit = jax.jit(_admit, donate_argnums=(0, 1))

    def _fresh_dev(self, max_new: int):
        b = self.batch
        return (jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b, max_new), jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.ones((), bool))

    def logits_finite(self) -> bool:
        """True while no tick since the last reset produced a NaN or
        infinite logit (one host read)."""
        return bool(jax.device_get(self.dev[4]))

    def lower_chunk(self, n: int, kv: int):
        """Lower the prefill chunk tick for an ``n``-token chunk against
        a ``kv``-row prefix, in this engine's kernel mode — the program
        :meth:`tick` runs for such a chunk."""
        pallas_mode.configure(**self._mode_kw)
        return self._chunk.lower(
            self.params, jnp.zeros((1, n), jnp.int32), self.cache, self.dev,
            jnp.int32(0), jnp.int32(0), jnp.asarray(True), kv)

    # -- admission -------------------------------------------------------
    def submit(self, req: Request):
        plen = req.prompt.shape[1]
        if plen + (req.max_new or self.max_new) > self.max_len:
            raise ValueError(f"request {req.rid} exceeds max_len")
        if (req.max_new or self.max_new) > self.dev[2].shape[1]:
            raise ValueError(f"request {req.rid} exceeds token buffer")
        req.t_submit = req.t_submit or time.time()
        self.queue.append(req)

    def _set_state(self, i: int, st: int):
        self.state[i] = st
        with jax.profiler.TraceAnnotation("engine.upload"):
            self._active = jnp.asarray([s == DECODE for s in self.state])

    def _admit_free_slots(self):
        if not self.queue:
            return
        with jax.profiler.TraceAnnotation("engine.admit"):
            for i in range(self.batch):
                if not self.queue:
                    return
                if self.state[i] == FREE:
                    req = self.queue.popleft()
                    req.t_admit = time.time()
                    # reused-slot hygiene: drop every cache row the previous
                    # occupant wrote before the new request's chunks land
                    self.cache, self.dev = self._admit(self.cache, self.dev,
                                                       jnp.int32(i))
                    self.slots[i] = req
                    self._set_state(i, PREFILL)
                    self.prefill_pos[i] = 0
                    self.lengths[i] = 0
                    self.gen_count[i] = 0

    def _bucket(self, need: int) -> int:
        return min(-(-need // self.page) * self.page, self.max_len)

    # -- one engine tick -------------------------------------------------
    # Host spans, in the profiler's trace on the device ops' clock:
    # ``engine.tick`` holds ``engine.admit``, ``engine.dispatch`` (the tick
    # program's arguments and its call) and ``engine.retire`` (the
    # bookkeeping after the call); ``engine.fetch`` marks each blocking
    # device read, ``engine.upload`` each upload of the active-slot mask.
    def tick(self) -> bool:
        """Run one engine iteration; returns True if any work was done."""
        if not self.queue and all(s == FREE for s in self.state):
            return False
        with jax.profiler.TraceAnnotation("engine.tick"):
            pallas_mode.configure(**self._mode_kw)
            self._admit_free_slots()
            self._step()
        return True

    def _step(self):
        decoding = [i for i in range(self.batch) if self.state[i] == DECODE]
        prefilling = [i for i in range(self.batch)
                      if self.state[i] == PREFILL]
        self.ticks += 1
        nxt_dev = None

        if decoding and not prefilling and not self.queue and not self.sync:
            # steady state: every slot is decoding and nothing is waiting,
            # so fuse up to 16 greedy steps into one dispatch.  Safe
            # because retirement is count-based host bookkeeping: the
            # earliest any slot can retire is min remaining-budget steps
            # away, and a roomier kv bucket only adds exact-zero masked
            # rows (bit-identical logits).
            rem = min((self.slots[i].max_new or self.max_new)
                      - self.gen_count[i] for i in decoding)
            k = min(rem, 16)
            k = 1 << (k.bit_length() - 1)           # quantize: few traces
            if k > 1:
                with jax.profiler.TraceAnnotation("engine.dispatch"):
                    kv = self._bucket(max(self.lengths[i]
                                          for i in decoding) + k)
                    self.cache, self.dev = self._decode_k(
                        self.params, self.cache, self.dev, self._active,
                        kv, k)
                with jax.profiler.TraceAnnotation("engine.retire"):
                    self.ticks += k - 1
                    self.ticks_decode += k
                    for i in decoding:
                        self.lengths[i] += k
                        self.gen_count[i] += k
                        self._maybe_retire(i)
                return

        with jax.profiler.TraceAnnotation("engine.dispatch"):
            kv_d = (self._bucket(max(self.lengths[i] for i in decoding) + 1)
                    if decoding else 0)
            ci = prefilling[0] if prefilling else None
            if ci is not None:
                req = self.slots[ci]
                off = self.prefill_pos[ci]
                c = min(self.chunk, req.prompt.shape[1] - off)
                toks = req.prompt[:, off:off + c]
                kv_p = self._bucket(off + c)
                last = off + c == req.prompt.shape[1]

            if decoding and ci is not None:
                (self.cache, self.dev, nxt_dev,
                 self.prefill_logits) = self._mixed(
                    self.params, toks, self.cache, self.dev, self._active,
                    jnp.int32(off), jnp.int32(ci), jnp.asarray(last),
                    kv_d, kv_p)
                self.ticks_decode += 1
                self.ticks_prefill += 1
                self.ticks_overlap += 1
            elif decoding:
                self.cache, self.dev, nxt_dev = self._decode(
                    self.params, self.cache, self.dev, self._active, kv_d)
                self.ticks_decode += 1
            else:
                self.cache, self.dev, self.prefill_logits = self._chunk(
                    self.params, toks, self.cache, self.dev, jnp.int32(off),
                    jnp.int32(ci), jnp.asarray(last), kv_p)
                self.ticks_prefill += 1

        with jax.profiler.TraceAnnotation("engine.retire"):
            for i in decoding:
                self.lengths[i] += 1
                self.gen_count[i] += 1
            if ci is not None:
                self.prefill_pos[ci] = off + c
                self.lengths[ci] = off + c
                if last:
                    self._set_state(ci, DECODE)
                    self.gen_count[ci] = 1

            if self.sync:
                # per-token observation: one fetch per tick (EOS stopping /
                # latency measurement); otherwise the loop stays async
                if nxt_dev is not None:
                    with jax.profiler.TraceAnnotation("engine.fetch"):
                        nxt = jax.device_get(nxt_dev)
                now = time.time()
                for i in decoding:
                    req = self.slots[i]
                    req.generated.append(int(nxt[i]))
                    req.token_times.append(now)
                if ci is not None and self.state[ci] == DECODE \
                        and self.gen_count[ci] == 1:
                    req = self.slots[ci]
                    req.t_first = now
                    with jax.profiler.TraceAnnotation("engine.fetch"):
                        tok0 = int(jax.device_get(self.dev[0][ci, 0]))
                    req.generated.append(tok0)
                    req.token_times.append(now)

            for i in range(self.batch):
                if self.state[i] == DECODE:
                    self._maybe_retire(i)

    def _maybe_retire(self, i: int):
        req = self.slots[i]
        limit = req.max_new or self.max_new
        if self.gen_count[i] >= limit or \
                (self.eos is not None and req.generated
                 and req.generated[-1] == self.eos):
            if not self.sync:
                # one blocking read per request: its finished token row
                n = self.gen_count[i]
                with jax.profiler.TraceAnnotation("engine.fetch"):
                    row = jax.device_get(self.dev[2][i, :n])
                req.generated = [int(x) for x in row]
            req.done = True
            self._set_state(i, FREE)
            self.lengths[i] = 0

    def run(self) -> int:
        """Tick until the queue and all slots drain; returns tick count."""
        n = 0
        while self.tick():
            n += 1
        return n

    def reset(self):
        """Back to the post-init state, keeping compiled tick functions."""
        b = self.batch
        # drop the old cache before the new one is made: both at once
        # would hold a second cache's worth of device memory
        self.cache = None
        self.cache = T.init_cache(self.cfg, b, self.max_len)
        self.dev = self._fresh_dev(self.dev[2].shape[1])
        self.prefill_logits = None
        self.lengths = [0] * b
        self.gen_count = [0] * b
        self.state = [FREE] * b
        self.slots = [None] * b
        self.prefill_pos = [0] * b
        self.queue.clear()
        self._active = jnp.zeros((b,), bool)
        self.ticks = self.ticks_decode = self.ticks_prefill = 0
        self.ticks_overlap = 0

    def overlap_ratio(self) -> float:
        busy = max(self.ticks_decode + self.ticks_prefill
                   - self.ticks_overlap, 1)
        return self.ticks_overlap / busy


def warm_kernel_plans(cfg, max_len: int, chunk: int = 16) -> int:
    """Plan the serving kernels up front, through a schedd daemon when
    ``$POLYTOPS_SCHEDD_SOCK`` names one (so N serving processes
    amortize one scheduler) and in-process otherwise — ``akg``'s remote
    hook makes the same call total either way.  Plans the shapes a
    prefill chunk of ``chunk`` rows runs; returns how many plans came
    back degraded (lowered from a fallback schedule)."""
    from ..core import akg
    from ..core.schedclient import maybe_client

    client = maybe_client()
    rows = max(chunk, 8)
    plans = ([akg.plan_attention(max_len, max_len, cfg.hd),
              akg.plan_attention(rows, max_len, cfg.hd)]
             if attends(cfg) else [])
    if cfg.d_ff:
        plans += [akg.plan_matmul(rows, cfg.d_ff, cfg.d_model),
                  akg.plan_matmul(rows, cfg.d_model, cfg.d_ff)]
    if cfg.d_inner and cfg.ssm_state:
        plans.append(akg.plan_scan_gate(rows, cfg.d_inner, cfg.ssm_state))
    degraded = sum(1 for p in plans if p.degraded)
    if client is not None:
        st = client.stats.as_dict()
        via = (f"via schedd ({client.sock_path}, "
               f"remote_ok={st['remote_ok']} fallbacks={st['fallbacks']})")
    else:
        via = "in-process"
    print(f"serve: {len(plans)} kernel plans warmed {via}"
          + (f", {degraded} degraded" if degraded else ""))
    return degraded


def init_params(cfg, seed: int):
    """Random parameters made on the device by one jitted program (a
    full-width model is never built leaf by leaf from the host)."""
    return jax.jit(T.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def main(argv=None) -> int:
    from .compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=0,
                    help="KV rows per slot (default: prompt-len + gen + 1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced smoke config")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    max_len = args.max_len or args.prompt_len + args.gen + 1
    if warm_kernel_plans(cfg, max_len, args.chunk):
        print("serve: degraded kernel plans; refusing to serve")
        return 1
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, args.seed)
    prompts = [jax.random.randint(jax.random.fold_in(key, i),
                                  (1, args.prompt_len), 2, cfg.vocab)
               for i in range(args.batch)]
    t0 = time.time()
    eng = ContinuousEngine(cfg, params, args.batch, max_len,
                           chunk=args.chunk, use_pallas=args.pallas,
                           max_new=args.gen)
    reqs = [Request(i, p) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    jax.block_until_ready(eng.cache)
    print(f"overlap ratio: {eng.overlap_ratio():.2f}, page={eng.page}")
    dt = time.time() - t0
    ntok = sum(len(r.generated) for r in reqs)
    dev = jax.devices()
    print(f"{len(reqs)} seqs, {ntok} tokens in {dt:.2f}s host wall time, "
          f"compiles included ({cfg.name}, "
          f"{dev[0].platform} {dev[0].device_kind} x{len(dev)})")
    for req in reqs:
        print(f"req{req.rid}: {req.generated[:10]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
