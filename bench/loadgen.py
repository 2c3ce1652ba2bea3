"""Drive the serving engine through one measured window and record it.

The window calls ``ServeEngine.step`` the way a server's host loop would:
requests are submitted when they fall due (``submit(req, now=due)``, on the
engine's own ``time.monotonic`` base, so its queue times count from the due
time), the engine steps while it holds work, and the loop sleeps to the next
due time when it holds none.  Every output token is stamped on the host
clock by the request's ``on_token`` callback.

Two hooks are installed on the engine instance from outside, to count the
work the algorithm needs: ``decode_inputs`` (the context length of every
row a decode step serves, and the KV blocks held) and ``_prefill_step``
(offset and length of every prompt chunk ingested).  With ``trace_s`` the
profiler runs over the window's last ``trace_s`` seconds, and host spans
(``jax.profiler.TraceAnnotation``) mark the engine step, its prefill and
decode dispatches, the decode input build, sampling and the loop's waits.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.runtime.serve import Request

CLOCK = time.monotonic
TRACED_WINDOW = "bench.traced_window"
#: host spans an idle gap of the device can be charged to
SPANS = ("engine.step", "prefill.step", "prefill.dispatch", "decode.inputs",
         "decode.dispatch", "sample", "loop.wait", "loop.submit")


@dataclasses.dataclass
class Record:
    t0: float                      # window opens (CLOCK)
    t1: float                      # window closes
    requests: list                 # every Request sent (.due, .stamps)
    stats0: dict                   # engine stats when the window opened
    stats1: dict                   # ... and when it closed
    decode_log: list               # (t, [context per active row], blocks)
    prefill_log: list              # (t, [(offset, n, prompt done)])
    compiles: int = 0              # programs traced or compiled in window
    lateness_s: list = dataclasses.field(default_factory=list)
    trace_t: tuple | None = None   # (start, stop) of the traced part

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def due(self) -> list:
        """The requests due inside the window (not in the ramp before it)."""
        return [r for r in self.requests if r.due >= self.t0]

    def delta(self, key):
        return self.stats1[key] - self.stats0[key]


def _span(name, fn):
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


HOOKED = ("decode_inputs", "_prefill_step", "_prefill", "_decode", "_sample",
          "step")


def instrument(eng, logs: dict, spans: bool):
    """Install the work-counting hooks, and with ``spans`` the host spans,
    on the engine instance; returns the function that takes them off."""
    saved = {n: eng.__dict__[n] for n in HOOKED if n in eng.__dict__}
    dec_inputs, pre_step = eng.decode_inputs, eng._prefill_step

    def decode_inputs():
        out = dec_inputs()
        if out is not None:
            active = out[0]
            ctx = [int(eng.cache.lengths[s]) + 1 for s in active]
            held = eng.cache.n_blocks - eng.cache.n_free_blocks
            logs["decode"].append((CLOCK(), ctx, held))
        return out

    def prefill_step(now):
        before = {s: st.n_prefilled for s, st in eng.slots.items()
                  if st.phase == "prefill"}
        out = pre_step(now)
        rows = [(before[s], st.n_prefilled - before[s], st.phase == "decode")
                for s, st in eng.slots.items()
                if s in before and st.n_prefilled > before[s]]
        if rows:
            logs["prefill"].append((CLOCK(), rows))
        return out

    eng.decode_inputs, eng._prefill_step = decode_inputs, prefill_step
    if spans:
        eng.decode_inputs = _span("decode.inputs", eng.decode_inputs)
        eng._prefill_step = _span("prefill.step", eng._prefill_step)
        eng._prefill = _span("prefill.dispatch", eng._prefill)
        eng._decode = _span("decode.dispatch", eng._decode)
        eng._sample = _span("sample", eng._sample)
        eng.step = _span("engine.step", eng.step)

    def remove():
        for n in HOOKED:
            eng.__dict__.pop(n, None)
        eng.__dict__.update(saved)
    return remove


def warm_up(eng, vocab: int):
    """Compile the cell's own prefill (P, chunk) and decode (B, 1) programs:
    one request long enough for two prefill dispatches and two decodes."""
    prompt = np.arange(eng.prefill_chunk + 1, dtype=np.int32) % vocab
    r = Request(rid=-1, prompt=prompt, max_new_tokens=3)
    eng.run([r])
    if r.status != "done":
        raise RuntimeError(f"warm-up request ended {r.status!r}")


class CompileCounter:
    """Counts programs traced or compiled while it is entered and ``on``."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n, self.on = 0, False

    def __call__(self, name, _secs, **_kw):
        if self.on and name in self.EVENTS:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def serve_window(eng, sched, seconds: float, *, ramp_s: float = 0.0,
                 trace_s: float = 0.0, trace_dir: str | None = None) -> Record:
    """Serve ``sched`` (``bench.mixgen.Item``s by due time) for ``ramp_s``
    seconds of set-up and then for the measured ``seconds``; returns the
    window's record.  The schedule's clock starts with the ramp, so the
    window opens on traffic already in flight; requests due in the ramp are
    served but not counted as due in the window."""
    logs = {"decode": [], "prefill": []}
    remove_hooks = instrument(eng, logs, spans=trace_s > 0)
    reqs, lateness = [], []

    def submit(item, due):
        r = Request(rid=len(reqs), prompt=item.prompt,
                    max_new_tokens=item.max_new)
        r.due, r.stamps = due, []
        r.on_token = lambda _rid, _i, _tok, st=r.stamps: st.append(CLOCK())
        reqs.append(r)
        with jax.profiler.TraceAnnotation("loop.submit"):
            eng.submit(r, now=due)
        lateness.append(CLOCK() - due)

    def wait(until):
        with jax.profiler.TraceAnnotation("loop.wait"):
            time.sleep(max(0.0, until - CLOCK()))

    t_start = CLOCK()
    t0 = t_start + ramp_s
    t1 = t0 + seconds
    t_trace = t1 - trace_s if trace_s else None
    items = list(sched)

    def offer(now):
        """Submit what has fallen due; the next due time."""
        while items and t_start + items[0].due_s <= now:
            it = items.pop(0)
            submit(it, t_start + it.due_s)
        return t_start + items[0].due_s if items else t1

    span, trace_t, stats0 = None, None, None
    try:
        with CompileCounter() as counter:
            while True:
                now = CLOCK()
                if now >= t1:
                    break
                if stats0 is None and now >= t0:
                    stats0, counter.on = dict(eng.stats), True
                if t_trace is not None and span is None and now >= t_trace:
                    span = _start_trace(trace_dir)
                    trace_t = CLOCK()
                nxt = offer(now)
                if eng.queue or eng.slots:
                    eng.step()
                else:
                    if span is None and t_trace is not None:
                        nxt = min(nxt, t_trace)
                    if stats0 is None:
                        nxt = min(nxt, t0)
                    wait(min(nxt, t1))
            t_end = CLOCK()
    finally:
        remove_hooks()
        if span is not None:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
    if span is not None:
        trace_t = (trace_t, t_end)
    return Record(t0=t0, t1=t1, requests=reqs,
                  stats0=stats0 or dict(eng.stats), stats1=dict(eng.stats),
                  decode_log=[e for e in logs["decode"] if e[0] >= t0],
                  prefill_log=[e for e in logs["prefill"] if e[0] >= t0],
                  compiles=counter.n, lateness_s=lateness, trace_t=trace_t)


def _start_trace(trace_dir):
    jax.profiler.start_trace(trace_dir)
    span = jax.profiler.TraceAnnotation(TRACED_WINDOW)
    span.__enter__()
    return span
