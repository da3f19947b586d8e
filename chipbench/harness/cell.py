"""One run of one cell: set-up, the measured window, the readings and
the check that decides ``correct``.

Set-up (``setup_s``, from process start to the window's opening): the
parameters, made on the device from the seed by the configuration's own
generator; the engine; every tick program the mix can reach, driven
through ``submit``/``tick`` (``serving.warm``); the window's requests,
made from the seed.  Later runs read every program from the persistent
compilation cache in ``<checkout>/.jax_cache``.

A traced run (``--trace 1``) records a slice of the window with the
profiler and reports the per-layer metrics; a run without reports the
end-to-end ones.  Either way the check runs once the window has closed,
the peak device memory has been read and the program's state is freed.
"""
from __future__ import annotations

import gc
import os
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from . import check as CK
from . import serving as SV
from . import traffic as TR
from . import trace as TRC
from . import work as WK
from .compiles import CompileCounter
from .spec import ROOT

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: a traced run records the last TRACE_SHARE of its window, at most
#: TRACE_MAX_S seconds of it
TRACE_SHARE, TRACE_MAX_S = 0.25, 6.0


class NoDevice(RuntimeError):
    pass


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache():
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, check_device: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform {info['platform']} device_kind {info['kind']} "
        f"count {info['count']}")
    if check_device:
        if info["platform"] != "tpu":
            raise NoDevice(f"no TPU: JAX's devices are {info['platform']}")
        if info["count"] < chips:
            raise NoDevice(f"{info['count']} chips, the cell needs {chips}")
    return info


def _same_layout(a, b) -> Optional[str]:
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb:
        return f"tree {ta} != {tb}"
    for x, y in zip(la, lb):
        if x.shape != y.shape or x.dtype != y.dtype:
            return f"leaf {x.shape} {x.dtype} != {y.shape} {y.dtype}"
    return None


class Session:
    """The engine for one cell, warmed through every program its mix
    reaches; ``use_seed`` swaps in another seed's weights."""

    def __init__(self, cell: dict, seed: int, check_device: bool = True):
        from repro.configs.registry import get_arch
        from repro.launch import serve as S
        from repro.model import transformer as T

        self.conf, self.mix = cell["conf"], cell["mix"]
        self.ref = cell["ref"]
        self.compiles = CompileCounter()
        self.device = devices(cell["chips"], check_device)
        self.peaks = WK.peaks(self.device["kind"]) if check_device else None
        if check_device:
            use_compile_cache()
        self.cfg = get_arch(self.conf["program_arch"]).scaled(
            **self.ref.program_sizes(self.conf))
        bad = _same_layout(
            jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                 self.cfg)),
            jax.eval_shape(lambda: self.ref.make_params(self.conf, seed)))
        if bad:
            raise ValueError(f"the configuration's weights do not fit the "
                             f"program's layout: {bad}")
        self.S = S
        self.seed = seed
        self.params = jax.block_until_ready(
            self.ref.make_params(self.conf, seed))
        eng = self.mix["engine"]
        _, self.out_max = TR.bounds(self.mix["output"])
        self.engine = S.ContinuousEngine(
            self.cfg, self.params, eng["slots"], eng["max_len"],
            chunk=eng["chunk"], use_pallas=True, max_new=self.out_max,
            sync=True)
        self.drv = SV.Driver(self.engine, S.DECODE, S.PREFILL)
        t = time.time()
        n = SV.warm(self.drv, self.mix, self.conf["vocab_size"], seed,
                    self.request)
        self.engine.reset()
        log(f"warm-up: {n} ticks in {time.time() - t:.3f} s, "
            f"{self.compiles.count} compiles so far "
            f"({self.compiles.secs:.3f} s), page {self.engine.page}")

    def request(self, rid: int, prompt: np.ndarray, out: int):
        return self.S.Request(rid, prompt, max_new=out)

    def use_seed(self, seed: int):
        self.seed = seed
        self.engine.params = self.params = None
        gc.collect()
        self.params = self.engine.params = jax.block_until_ready(
            self.ref.make_params(self.conf, seed))
        self.engine.reset()

    def plan(self, seconds: float):
        """The window's requests, made from the seed before it opens."""
        vocab = self.conf["vocab_size"]
        return [SV.Served(p, self.request(p.rid, TR.prompt_tokens(
            self.seed, p.rid, p.prompt_len, vocab), p.out_len))
            for p in TR.open_schedule(self.mix, seconds, self.seed)]

    def window(self, seconds: float, traced: bool, served=None,
               drain_s: float = 120.0) -> dict:
        """Run the measured window; returns what the readers need.  A
        traced run records the window's last TRACE_SHARE (at most
        TRACE_MAX_S seconds); the trace is written after the drain."""
        served = served if served is not None else self.plan(seconds)
        drv, eng = self.drv, self.engine
        drv.ticks = []
        tr = {"phase": 0}
        length = min(TRACE_MAX_S, TRACE_SHARE * seconds)

        def on_tick(t_open):
            if traced and tr["phase"] == 0 \
                    and time.time() >= t_open + seconds - length:
                jax.block_until_ready(eng.dev)
                jax.profiler.start_trace(TRC.fresh_dir(TRACE_DIR))
                tr["ann"] = jax.profiler.TraceAnnotation(TRC.WINDOW)
                tr["ann"].__enter__()
                tr["w0"], tr["phase"] = time.time(), 1

        def on_close():
            if tr["phase"] == 1:
                jax.block_until_ready(eng.dev)
                tr["w1"] = time.time()
                tr["ann"].__exit__(None, None, None)
                tr["phase"] = 2

        c0 = self.compiles.count
        served, t_open, t_close = SV.open_loop(
            drv, [s.planned for s in served], seconds,
            [s.req for s in served], on_tick, on_close, drain_s)
        in_window = self.compiles.count - c0
        if in_window:
            log(f"window: compiled {self.compiles.names[c0:]}")
        out = {"served": served, "t_open": t_open, "t_close": t_close,
               "ticks": list(drv.ticks), "compiles": in_window,
               "trace": None, "traced_ticks": []}
        if tr["phase"] == 2:
            jax.profiler.stop_trace()
            out["trace"] = TRC.Reduced(TRC.load(TRACE_DIR))
            out["traced_ticks"] = [t for t in drv.ticks
                                   if t.t0 >= tr["w0"] and t.t1 <= tr["w1"]]
        return out


class Readings:
    """What the metric readers read: the window's requests and ticks,
    the trace's reduction, the work counts and the chip's peaks."""

    def __init__(self, sess: Session, win: dict, setup_s: float):
        self.conf, self.mix = sess.conf, sess.mix
        self.shapes = sess.ref.shapes(sess.conf)
        self.peaks = sess.peaks
        self.served: List[SV.Served] = win["served"]
        self.t_open, self.t_close = win["t_open"], win["t_close"]
        self.ticks: List[SV.TickRecord] = win["ticks"]
        self.traced_ticks: List[SV.TickRecord] = win["traced_ticks"]
        self.trace: Optional[TRC.Reduced] = win["trace"]
        self.setup_s = setup_s
        self.work = WK
        self.notes: List[str] = []

    def token_flops(self, position: int) -> float:
        """Model operations of one token at ``position``: two per active
        parameter, plus attention's two products over the causal
        context in each attention layer."""
        sh = self.shapes
        f = 2.0 * sh["active_params"]
        a = sh["attention"]
        if a:
            f += sh["layers"] * 4.0 * (position + 1) * a["heads"] \
                * a["head_dim"]
        return f

    def tick_flops(self, ticks) -> float:
        total = 0.0
        for t in ticks:
            total += sum(self.token_flops(p) for p in t.decode_pos)
            if t.chunk:
                off, rows = t.chunk
                total += sum(self.token_flops(p)
                             for p in range(off, off + rows))
        return total

    def kernel_share(self, needle: str, per_chunk) -> Optional[float]:
        """Roofline share of kernel ``needle``: the least time of the
        operations it stands for in the traced ticks' prefill chunks
        (``per_chunk(offset, rows) -> Work`` for one chunk, all layers)
        over its device time in the trace."""
        if self.trace is None:
            return None
        secs = self.trace.op_seconds(needle)
        chunks = [t.chunk for t in self.traced_ticks if t.chunk]
        if secs <= 0 or not chunks:
            return None
        least = {"compute": 0.0, "memory": 0.0}
        for off, rows in chunks:
            tmin, bound = WK.least_seconds(per_chunk(off, rows), self.peaks)
            least[bound] += tmin
        bound = max(least, key=least.get)
        self.notes.append(
            f"{needle}: least time {sum(least.values())!r} s "
            f"({least['compute']!r} s of it compute-bound, "
            f"{least['memory']!r} s memory-bound) over {secs!r} s in "
            f"{self.trace.op_count(needle)} device events; "
            f"{bound} bound binds")
        return 100.0 * sum(least.values()) / secs


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def checks_of(sess: Session, win: dict, gaps: np.ndarray, limit: float,
              finite: bool) -> Dict[str, dict]:
    """Each number that decides ``correct``, beside its limit."""
    served = win["served"]
    unfinished = sum(not s.req.done for s in served)
    wrong = sum(len(s.req.generated) != s.planned.out_len
                for s in served if s.req.done)
    return {
        # no gap read is no pass: served_tokens_compared fails it
        "max_logit_gap": {"value": float(gaps.max()) if gaps.size else 0.0,
                          "limit": limit},
        "served_tokens_compared": {"value": int(gaps.size),
                                   "limit": int(sess.mix["check"]["min_tokens"])},
        "unfinished": {"value": unfinished, "limit": 0},
        "wrong_length": {"value": wrong, "limit": 0},
        "nonfinite_logits": {"value": 0 if finite else 1, "limit": 0},
        "compiles_in_window": {"value": win["compiles"], "limit": 0},
    }


def passes(checks: Dict[str, dict]) -> bool:
    """Every number within its limit: served_tokens_compared at least
    its limit, every other at most."""
    return all((c["value"] >= c["limit"]) if k == "served_tokens_compared"
               else (c["value"] <= c["limit"]) for k, c in checks.items())


def finished(win: dict) -> list:
    return [s for s in win["served"] if s.req.done]


def run(cell: dict, seed: int, seconds: float, traced: bool,
        t_start: float, check_device: bool = True) -> dict:
    """One run; returns the result object printed as the last line."""
    sess = Session(cell, seed, check_device)
    served = sess.plan(seconds)
    setup_s = time.time() - t_start
    log(f"set-up: {setup_s!r} s, {sess.compiles.count} compiles "
        f"({sess.compiles.secs!r} s)")
    win = sess.window(seconds, traced, served)
    log(f"window: {win['compiles']} compiles in the window; "
        f"{len(win['served'])} requests, {len(win['ticks'])} ticks, "
        f"{win['t_close'] - win['t_open']!r} s")
    late = max((s.sent - s.due for s in win["served"]), default=0.0)
    log(f"window: the generator sent its latest request {late!r} s "
        f"after it was due")
    ttft = [(s.req.t_first - s.due) * 1e3 for s in win["served"]
            if s.req.t_first]
    log(f"window: time to first token p50/p75/p90 "
        f"{[percentile(ttft, q) for q in (50, 75, 90)]!r} ms over "
        f"{len(ttft)} requests")
    r = Readings(sess, win, setup_s)
    wanted = cell["per_layer"] if traced else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = cell["readers"][m["name"]].read(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for note in r.notes:
        log(note)
    dev = dict(sess.device)
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    breakdown = None
    if traced and win["trace"] is not None:
        red = win["trace"]
        dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.idle_by_span(10)}
    finite = sess.engine.logits_finite()
    limit = sess.conf["correct"]["max_logit_gap"]
    # the program's state goes before the reference runs
    sess.engine = sess.drv.eng = sess.params = None
    gc.collect()
    picked = CK.sample(finished(win), sess.mix["check"]["requests"], seed)
    gaps = CK.gaps(sess.ref.Reference(sess.conf, seed), picked,
                   sess.mix["engine"]["max_len"], sess.out_max)
    checks = checks_of(sess, win, gaps, limit, finite)
    attempted = len(win["served"])
    failed = checks["unfinished"]["value"] + checks["wrong_length"]["value"]
    result = {"correct": passes(checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return result
