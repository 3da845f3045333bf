"""Run one cell once: build the stack the program's way, offer the
cell's traffic, time it from the client's side, and check the answers.

The stack is built as the program's own entry builds it
(``launch/drive.py``: ``build_engine``, ``make_loop``, the paged rung
compiles), from a ``DriveSizes`` that carries only the deployment's
sizes (sessions, max_len, num_pages); every other engine and policy
setting stays at the program's default.

Timeline of a run: set-up (weights, engine, the paged rungs, the
prefix cache filled to the pool's size with the traffic's history where
the traffic kind has one, then a traffic warm-up until the queue is
steady), the measured window of ``--seconds``, then a drain: no new
turn is offered and every request that arrived in the window is
answered (or ``DRAIN_CAP_S`` passes: what is still unanswered then
failed).  Requests are timed from when they were DUE, not from when the
harness got round to submitting them, so a long tick that delays the
submit shows in the latency.  The harness holds a due request back only
where the engine would fail it: more concurrent sessions than the
deployment serves, or more KV pages pinned than its pool holds (the
paged engine raises on an exhausted pool; it has no preemption).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, flops, manifest
from bench.traffic.source import Turn

CLOCK = time.monotonic
TRACE_SECONDS = 15.0      # the traced part of a --trace 1 window
DRAIN_CAP_S = 90.0
FILL_CAP_S = 120.0        # the prefix-cache fill must end within this
FILL_ROUNDS = 8
FILL_SESSIONS = 1 << 30   # session ids of the fill, apart from the traffic's
IDLE_SLEEP_S = 0.002


@dataclasses.dataclass
class Rec:
    turn: Turn
    due: float                    # absolute, CLOCK
    seen: float                   # when the harness saw it due
    session: int
    pages: int                    # KV pages of its whole context
    submit: Optional[float] = None
    req: object = None            # the program's Request
    first: Optional[float] = None
    stamps: List = dataclasses.field(default_factory=list)  # (t, n new)
    n: int = 0
    done: Optional[float] = None
    served: List[int] = dataclasses.field(default_factory=list)


class Spans:
    """Host spans for the traced run: ``jax.profiler.TraceAnnotation``
    around the calls into each layer, installed on the instances; off,
    they cost nothing."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()

    def wrap(self, obj, attr: str, name: str) -> None:
        if not self.on:
            return
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with self._ann(name):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)


class Steps:
    """What the engine was handed, recorded at its public entries in a
    traced run: each packed step's (new tokens, history) per segment and
    each decode row's history, for the FLOP and roofline counts."""

    def __init__(self, engine):
        self.log: List[Dict] = []
        self._depth = 0
        mixed, decode = engine.step_mixed, engine.decode_batch

        def step_mixed(prefills, decodes, *a, **k):
            segs = []
            for s, toks in prefills:
                h = engine.history(s)
                adopt = engine.probe_prefix(toks) if h == 0 else 0
                segs.append((len(toks) - adopt, h + adopt))
            segs += [(1, engine.history(s)) for s, _ in decodes]
            self._depth += 1
            try:
                out = mixed(prefills, decodes, *a, **k)
            finally:
                self._depth -= 1
            self.log.append({"t": CLOCK(), "kind": "packed", "segs": segs})
            return out

        def decode_batch(sessions, tokens, steps=1):
            outer = self._depth == 0
            rows = [(1, engine.history(s)) for s in sessions]
            self._depth += 1
            try:
                out = decode(sessions, tokens, steps)
            finally:
                self._depth -= 1
            if outer:
                self.log.append({"t": CLOCK(), "kind": "decode",
                                 "segs": rows})
            return out

        engine.step_mixed = step_mixed
        engine.decode_batch = decode_batch


class CompileCounter:
    """Compiles and compile-cache reads seen by JAX while armed."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.count = 0
        self.armed = False

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax.monitoring as mon
            c = cls()

            def on_event(name, *a, **k):
                if c.armed and name == "/jax/compilation_cache/cache_hits":
                    c.count += 1

            def on_duration(name, secs, *a, **k):
                if c.armed and name == \
                        "/jax/core/compile/backend_compile_duration":
                    c.count += 1
            mon.register_event_listener(on_event)
            mon.register_event_duration_secs_listener(on_duration)
            cls._installed = c
        return cls._installed


class Client:
    """The client side: offers the traffic's turns when due, holds them
    where the engine would fail them, and stamps every token as the
    loop hands it back."""

    def __init__(self, loop, source, slots: int, num_pages: int,
                 page_size: int, spans: Spans, first_session: int = 1):
        self.loop, self.source = loop, source
        self.arena = loop.engine.arena
        self.slots, self.num_pages, self.ps = slots, num_pages, page_size
        self.spans = spans
        self.t0 = CLOCK()
        self.backlog: deque = deque()
        self.inflight: Dict[int, Rec] = {}
        self.all: List[Rec] = []
        self.next_session = first_session
        self.prompt_submitted = 0
        self.offering = True
        self.held_for_pages = 0       # requests the page check held back
        self._held = None

    def _pages(self, t: Turn) -> int:
        return -(-(len(t.prompt) + t.out_tokens) // self.ps) + 1

    def pages_owed(self) -> int:
        """Pages the in-flight requests will still allocate on their way
        to their full length."""
        return sum(max(0, x.pages - len(self.arena.pages_of(s)))
                   for s, x in self.inflight.items())

    def _pages_fit(self, r: Rec) -> bool:
        """Would the pool still hold every in-flight context at its full
        length with ``r`` added?  Pages that sessions share (an adopted
        system prompt) count once: they are the pinned pages' union.
        Only ``r``'s own shared prefix is counted as if new."""
        pinned = set()
        for s in self.inflight:
            pinned.update(self.arena.pages_of(s))
        return len(pinned) + self.pages_owed() + r.pages <= self.num_pages

    def _poll(self, now: float) -> None:
        if not self.offering:
            return
        with self.spans("bench.traffic"):
            for t in self.source.due(now - self.t0):
                r = Rec(turn=t, due=self.t0 + t.due, seen=now,
                        session=self.next_session, pages=self._pages(t))
                if r.pages > self.num_pages:
                    raise ValueError(f"a request of {len(t.prompt)} + "
                                     f"{t.out_tokens} tokens cannot fit "
                                     f"{self.num_pages} pages")
                self.next_session += 1
                self.backlog.append(r)
                self.all.append(r)

    def _admit(self) -> None:
        while self.backlog and len(self.inflight) < self.slots:
            if not self._pages_fit(self.backlog[0]):
                if self._held is not self.backlog[0]:
                    self._held = self.backlog[0]
                    self.held_for_pages += 1
                return
            r = self.backlog.popleft()
            r.submit = CLOCK()
            with self.spans("bench.submit"):
                r.req = self.loop.submit(r.session, r.turn.prompt,
                                         decode_tokens=r.turn.out_tokens - 1)
            self.prompt_submitted += len(r.turn.prompt)
            self.inflight[r.session] = r

    def _collect(self, now: float) -> None:
        gen, active = self.loop.generated, self.loop.active_decodes
        for s, r in list(self.inflight.items()):
            toks = gen.get(s)
            n = len(toks) if toks else 0
            if n > r.n:
                if r.first is None:
                    r.first = now
                r.stamps.append((now, n - r.n))
                r.n = n
            if n >= r.turn.out_tokens and s not in active:
                r.served = [int(x) for x in toks[:r.turn.out_tokens]]
                r.done = now
                del self.inflight[s]
                with self.spans("bench.close"):
                    self.loop.close_session(s)
                with self.spans("bench.traffic"):
                    self.source.on_done(r.turn, r.served, now - self.t0)

    def step(self) -> None:
        now = CLOCK()
        self._poll(now)
        self._admit()
        did, wake = self.loop.tick()
        self._collect(CLOCK())
        if not did:
            nxt = self.source.next_due()
            until = min(x for x in (
                wake, None if nxt is None else self.t0 + nxt,
                CLOCK() + IDLE_SLEEP_S) if x is not None)
            with self.spans("bench.sleep"):
                time.sleep(max(0.0, until - CLOCK()))

    def run_until(self, t_end: float,
                  done: Optional[Callable[[], bool]] = None) -> None:
        while CLOCK() < t_end and not (done and done()):
            self.step()


class Backfill:
    """A source that offers transcripts from the traffic's ``history()``,
    first token only, while they fit the pool's free pages (less those
    the in-flight ones still need); the last is cut to what fits, as
    that session stood some turns earlier, so the pool ends full."""

    def __init__(self, transcripts, room: Callable[[], int],
                 free: Callable[[], int], page_size: int):
        self.it, self.room, self.free, self.ps = transcripts, room, free, \
            page_size
        self.tokens = 0
        self.stopped = False

    def _pages(self, n: int) -> int:
        return -(-(n + 1) // self.ps) + 1

    def due(self, now: float) -> List[Turn]:
        out: List[Turn] = []
        for _ in range(self.room()):
            if self.stopped:
                break
            avail = self.free() - sum(self._pages(len(t.prompt))
                                      for t in out)
            prompt = next(self.it)
            if self._pages(len(prompt)) > avail:
                self.stopped = True
                prompt = prompt[:(avail - 1) * self.ps - 1]
                if len(prompt) < self.ps:
                    break
            out.append(Turn(due=0.0, prompt=prompt, out_tokens=1))
            self.tokens += len(prompt)
        return out

    def next_due(self) -> Optional[float]:
        return None

    def on_done(self, turn: Turn, served: List[int], now: float) -> None:
        pass


def fill_pool(loop, source, sizes, spans: Spans) -> Dict[str, float]:
    """Fill the prefix cache to the pool's size with the traffic's own
    history, so the window runs with a full pool that evicts, as on a
    server that has been running."""
    t = CLOCK()
    ar = loop.engine.arena
    ev0 = ar.pages_evicted
    cl = Client(loop, None, sizes.sessions, sizes.num_pages,
                 sizes.page_size, spans, first_session=FILL_SESSIONS)
    cl.source = Backfill(
        source.history(),
        lambda: sizes.sessions - len(cl.inflight) - len(cl.backlog),
        lambda: ar.free_pages - cl.pages_owed(), sizes.page_size)
    # a closed session frees the pages it wrote twice (a system prompt
    # two cold transcripts prefilled at once), so fill again until a
    # round finds no room
    for _ in range(FILL_ROUNDS):
        offered = cl.source.tokens
        cl.source.stopped = False
        cl.run_until(t + FILL_CAP_S, done=lambda: cl.source.stopped
                      and not cl.inflight and not cl.backlog)
        if cl.inflight or cl.backlog or not cl.source.stopped:
            raise RuntimeError("the prefix-cache fill did not finish "
                               f"within {FILL_CAP_S} s")
        if cl.source.tokens == offered:
            break
    return {"fill_s": CLOCK() - t, "fill_tokens": cl.source.tokens,
            "fill_requests": len(cl.all),
            "free_pages_after_fill": ar.free_pages,
            "evicted_in_fill": ar.pages_evicted - ev0}


def _counters(engine, client: Client) -> Dict[str, float]:
    st = engine.stats()
    return {"prefix_hit_tokens": st["prefix_hit_tokens"],
            "pages_evicted": st["pages_evicted"],
            "prompt_tokens_submitted": client.prompt_submitted,
            "requests_held_for_pages": client.held_for_pages}


def _req_view(r: Rec, end: float) -> Dict:
    return {"due": r.due, "seen": r.seen, "submit": r.submit,
            "dispatch": getattr(r.req, "dispatch_time", None),
            "first": r.first, "done": r.done, "stamps": r.stamps,
            "prompt": len(r.turn.prompt), "out": r.turn.out_tokens,
            "reused": int(getattr(r.req, "reusable_prefix", 0) or 0),
            "failed": r.done is None, "end": end}


def mirror_check(mcfg, fields: Dict) -> None:
    """The program's model config must be the one the reference reads:
    ``fields`` are the attributes the configuration's published keys
    fix (its architecture's ``program_fields``)."""
    got = {k: getattr(mcfg, k) for k in fields}
    if got != fields:
        raise ValueError(f"program config {got} differs from the "
                         f"configuration file {fields}")


def build(cell: manifest.Cell, seed: int):
    """Weights, engine and loop as the program's entry builds them; the
    weights and the config mirror come from the module of the
    architecture the configuration names."""
    import jax
    from repro.configs import get_config
    from repro.launch import drive
    from repro.models import transformer as tr

    cf = cell.config
    hf, arch = cf["hf_config"], cf["architecture"]
    mod = manifest.arch_module(cell)
    mcfg = get_config(cf["registry"]).replace(**cf.get("overrides", {}))
    mirror_check(mcfg, mod.program_fields(hf, arch))
    dep = cf["deployment"]
    sizes = drive.DriveSizes(sessions=int(dep["sessions"]),
                             max_len=int(dep["max_len"]),
                             num_pages=int(dep["num_pages"]))
    params = mod.make(hf, arch, seed, jax.numpy.dtype(arch["dtype"]))
    want = jax.tree.structure(tr.param_shapes(mcfg))
    if jax.tree.structure(params) != want or any(
            a.shape != b.shape for a, b in zip(
                jax.tree.leaves(params),
                jax.tree.leaves(tr.param_shapes(mcfg)))):
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter layout")
    jax.block_until_ready(params)
    engine = drive.build_engine(mcfg, params, sizes)
    ar = engine.arena
    for ex in (engine.packed_executor, engine.decode_executor):
        ex.precapture_paged(engine.params, ar.arena, ar.max_pages_per_seq)
    loop = drive.make_loop(engine, sizes)
    return params, engine, loop, sizes


def device_info(trace_red=None) -> Dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace_red is not None:
        out["busy_s"] = trace_red.busy_s
        out["window_s"] = trace_red.window_s
    return out


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def serve(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
          t_proc: float, trace_dir: Optional[str] = None,
          overrides: Optional[Dict] = None,
          fault: Optional[Callable] = None) -> Dict:
    """Set up, warm up, measure, drain.  Returns the run record (before
    the check); ``fault`` breaks the built stack in place (tests)."""
    import jax

    params, engine, loop, sizes = build(cell, seed)
    if fault is not None:
        fault(engine, loop)
    traffic = dict(cell.traffic, **(overrides or {}))
    kind = manifest.traffic_kind(cell)
    source = kind.make_source(traffic, seed, int(engine.cfg.vocab_size),
                              sizes.max_len)
    spans = Spans(trace)
    steps = Steps(engine) if trace else None
    spans.wrap(loop, "tick", "bench.tick")
    spans.wrap(loop.policy, "next_work", "bench.next_work")
    spans.wrap(engine, "step_mixed", "bench.step_mixed")
    spans.wrap(engine, "decode_batch", "bench.decode_batch")
    spans.wrap(engine, "prefill_batch", "bench.prefill_batch")
    filled = fill_pool(loop, source, sizes, spans)
    client = Client(loop, source, sizes.sessions, sizes.num_pages,
                    sizes.page_size, spans)
    client.run_until(client.t0 + float(cell.traffic["warmup_s"]))

    cc = CompileCounter.get()
    red = None
    if trace:
        jax.profiler.start_trace(trace_dir)
    ws = CLOCK()
    c0 = _counters(engine, client)
    free0 = engine.arena.free_pages
    open0 = len(client.backlog) + len(client.inflight)
    cc.count, cc.armed = 0, True
    n_log = len(steps.log) if trace else 0
    untraced_from = ws
    if trace:
        with spans("bench.window"):
            client.run_until(ws + min(TRACE_SECONDS, seconds))
        trace_end = CLOCK()
        # writing the trace out stalls the loop for seconds
        jax.profiler.stop_trace()
        untraced_from = CLOCK()
    client.run_until(ws + seconds)
    we = CLOCK()
    cc.armed = False
    compiles = cc.count
    c1 = _counters(engine, client)
    open1 = len(client.backlog) + len(client.inflight)
    out_tokens = sum(n for r in client.all for t, n in r.stamps
                     if ws <= t < we)
    window = [r for r in client.all if ws <= r.due < we]
    client.offering = False
    client.run_until(we + DRAIN_CAP_S,
                     done=lambda: all(r.done is not None for r in window))
    end = CLOCK()
    dev = device_info()
    traced_steps = []
    if trace:
        traced_steps = [s for s in steps.log[n_log:] if s["t"] <= trace_end]
        from bench import trace as tr_mod
        red = tr_mod.reduce(tr_mod.find_xplane(trace_dir))
        dev = device_info(red)
    late = [r.seen - r.due for r in window]
    rec = {
        "seed": seed, "setup_s": ws - t_proc, "window_s": we - ws,
        "window_end": we, "untraced_from": untraced_from,
        "requests": [_req_view(r, end) for r in window],
        "counters": {k: c1[k] - c0[k] for k in c0},
        "output_tokens": out_tokens, "fill": filled,
        "free_pages_at_start": free0,
        "steps": traced_steps, "trace": red, "device": dev,
        "hf": cell.config["hf_config"],
        "arch": cell.config["reference"], "bench_dir": str(cell.bench_dir),
        "peak": flops.peaks(dev["kind"]) if dev["platform"] != "cpu"
        else None,
        "compiles_in_window": compiles,
        "lateness_p50_s": float(np.median(late)) if late else 0.0,
        "lateness_max_s": float(max(late)) if late else 0.0,
        "load": {k: traffic[k] for k in ("rate", "clients")
                 if k in traffic},
        "open_at_start": open0, "open_at_end": open1,
        "done": [{"prompt": r.turn.prompt, "served": r.served,
                  "chunked": len(r.turn.prompt) - (getattr(
                      r.req, "reusable_prefix", 0) or 0)
                  > engine.ecfg.chunk_tokens,
                  "adopted": bool(getattr(r.req, "reusable_prefix", 0))}
                 for r in window if r.done is not None],
    }
    del client, source, loop, engine, steps
    gc.collect()
    rec["params"] = params
    return rec


def window_stats(rec: Dict) -> Dict:
    """Statistics printed beside the metrics, for reading a run's
    spread: request counts and a few more percentiles."""
    from bench.readers import percentile, tpot_s, ttft_s
    tt, tp = ttft_s(rec), tpot_s(rec)
    out = {"requests": len(rec["requests"]), "token_gaps": len(tp),
           "output_tokens": rec["output_tokens"]}
    for q in (0.5, 0.75, 0.9, 0.95):
        if tt:
            out[f"ttft_p{int(q * 100)}_ms"] = 1e3 * percentile(tt, q)
        if tp:
            out[f"tpot_p{int(q * 100)}_ms"] = 1e3 * percentile(tp, q)
    return out


def check_sample(cell: manifest.Cell, rec: Dict) -> List[Dict]:
    """The seed's sample of finished requests: the longest, one whose
    prompt was prefilled in chunks and one that adopted indexed pages
    (where the window had them), and seeded draws to ``check_requests``."""
    done = rec["done"]
    must = []
    for flag in ("chunked", "adopted"):
        hit = [i for i, d in enumerate(done) if d[flag]]
        if hit:
            must.append(hit[len(hit) // 2])
    return check.sample(done, rec["seed"],
                        int(cell.traffic["check_requests"]), must)


def verdict(cell: manifest.Cell, rec: Dict) -> Dict:
    """Run the reference over the seed's sample of finished requests."""
    ref = manifest.reference_module(cell)
    cf = cell.config
    reqs = check_sample(cell, rec)
    t = CLOCK()
    gap, _, n_tok = check.gaps(ref, cf["hf_config"], cf["architecture"],
                               rec["params"], reqs)
    limit = float(cf["check"]["max_logit_gap"])
    return {"max_logit_gap": gap, "limit": limit, "tokens": n_tok,
            "requests": len(reqs), "seconds": CLOCK() - t,
            "ok": bool(reqs) and gap <= limit}


def metrics(cell: manifest.Cell, rec: Dict, trace: bool) -> Dict:
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = manifest.metric_reader(cell, m["name"]).read(rec)
        if v is None:
            continue
        if not math.isfinite(v):
            raise ValueError(f"{m['name']} read {v}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
        t_proc: float, fault: Optional[Callable] = None) -> Dict:
    trace_dir = None
    if trace:
        trace_dir = str(cell.bench_dir / ".trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    rec = serve(cell, seed, seconds, trace, t_proc, trace_dir, fault=fault)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    mets = metrics(cell, rec, trace)
    reqs = rec["requests"]
    _log(f"compiles in window: {rec['compiles_in_window']}")
    _log(f"generator lateness: p50 {rec['lateness_p50_s'] * 1e3:.3f} ms, "
         f"max {rec['lateness_max_s'] * 1e3:.3f} ms")
    _log(f"peak_bytes_in_use: {rec['device']['memory_peak_bytes']}")
    _log(f"load: {json.dumps(rec['load'])} ({cell.traffic['kind']})")
    _log(f"prefix-cache fill: {json.dumps(rec['fill'])}; free pages at "
         f"the window's start: {rec['free_pages_at_start']}")
    _log(f"counters over the window: {json.dumps(rec['counters'])}")
    _log(f"window statistics: {json.dumps(window_stats(rec))}")
    v = verdict(cell, rec)
    _log(f"reference check: {v['requests']} requests, {v['tokens']} "
         f"served tokens, {v['seconds']:.1f} s")
    _log(f"max_logit_gap {v['max_logit_gap']!r} limit {v['limit']!r}")
    out = {"correct": v["ok"], "attempted": len(reqs),
           "failed": sum(r["failed"] for r in reqs), "metrics": mets,
           "device": rec["device"]}
    if trace and rec["trace"] is not None:
        out["breakdown"] = {"device_ops": rec["trace"].top_ops(),
                            "idle_gaps": rec["trace"].idle_by_span()}
    out["checks"] = {"max_logit_gap": {"value": v["max_logit_gap"],
                                       "limit": v["limit"]}}
    return out
