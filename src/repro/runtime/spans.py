"""The serving engine's record: its decision log, and spans and counters.

``Recorder`` holds the engine's always-on decision log (``events``:
``(step, action, rid, slot)`` tuples) and, only while ``on`` is set, timed
spans and counters.  Off (the default), :meth:`Recorder.span` is one
attribute check that returns a shared no-op context, and nothing is kept.

On, a span is kept as a :class:`Span` on ``time.monotonic`` (the clock the
engine's own timings and a load generator share) and is also entered as a
``jax.profiler.TraceAnnotation`` of the same name, so it lands on a
profiler trace's clock beside the device's operations.  Spans and counters
go into bounded buffers that keep the newest entries and count what they
drop (``dropped``).  :func:`breakdown` reduces what was kept over a window
to the numbers of the engine's layers (device, sampler, copy to the host,
host loop, KV cache, prefill packing).
"""
from __future__ import annotations

import contextlib
import math
import time
from collections import deque
from typing import NamedTuple

import jax

__all__ = ["Counter", "Recorder", "Span", "breakdown"]


class Span(NamedTuple):
    seq: int                  # order the span began in; ``parent`` names one
    name: str
    start: float              # time.monotonic (a request span: the engine's
    end: float                # clock, which is time.monotonic unless injected)
    parent: int | None        # ``seq`` of the span it ran inside
    rid: int | None           # the request a request span belongs to
    step: int | None          # the engine step a step span ran in


class Counter(NamedTuple):
    name: str
    t: float                  # time.monotonic when counted
    value: float
    of: float | None          # the capacity ``value`` fills, where it has one
    parent: int | None        # ``seq`` of the span it was counted in


_OFF = contextlib.nullcontext()


class _Open:
    """A span being timed; entered as a TraceAnnotation of its name."""
    __slots__ = ("rec", "name", "step", "start", "end", "seq", "parent",
                 "_ann")

    def __init__(self, rec, name, step, start):
        self.rec, self.name, self.step, self.start = rec, name, step, start

    def __enter__(self):
        rec = self.rec
        outer = rec._open[-1] if rec._open else None
        self.parent = None if outer is None else outer.seq
        if self.step is None and outer is not None:
            self.step = outer.step
        self.seq = rec._next_seq()
        rec._open.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if self.start is None:
            self.start = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.end = time.monotonic()
        self._ann.__exit__(*exc)
        rec = self.rec
        rec._open.pop()
        rec._keep(rec.spans, Span(self.seq, self.name, self.start, self.end,
                                  self.parent, None, self.step))
        return False


class Recorder:
    """The engine's decision log, plus spans and counters while ``on``."""

    def __init__(self, capacity: int = 1 << 18):
        self.on = False
        self.events: list = []                  # (step, action, rid, slot)
        self.spans: deque = deque(maxlen=capacity)
        self.counters: deque = deque(maxlen=capacity)
        self.dropped = 0
        self._open: list = []
        self._seq = 0

    def span(self, name: str, *, step: int | None = None,
             start: float | None = None):
        """A context that times the code it wraps as span ``name`` (inside
        the innermost open span, whose step it inherits).  ``start``: a
        ``time.monotonic`` reading the caller already took, used as the
        start.  Entered, it gives the open span (its ``end`` is set on
        exit); off, it gives None."""
        if not self.on:
            return _OFF
        return _Open(self, name, step, start)

    def add(self, name: str, start: float, end: float, *, rid: int) -> None:
        """A span the caller timed: a request's phase, which outlasts any
        one call.  Kept in memory only (no TraceAnnotation can be entered
        after the fact)."""
        if self.on:
            self._keep(self.spans, Span(self._next_seq(), name, start, end,
                                        None, rid, None))

    def count(self, name: str, value, of=None) -> None:
        """A counter, counted now, inside the innermost open span."""
        if self.on:
            parent = self._open[-1].seq if self._open else None
            self._keep(self.counters, Counter(name, time.monotonic(), value,
                                              of, parent))

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _keep(self, buf: deque, item) -> None:
        if len(buf) == buf.maxlen:
            self.dropped += 1
        buf.append(item)


def breakdown(spans, counters, t0: float = -math.inf,
              t1: float = math.inf) -> dict:
    """What the spans and counters that lie inside ``[t0, t1]`` say of the
    engine's steps; a number with nothing to read is None.

    * ``decode_device_ms`` / ``prefill_device_ms``: mean ``*.device`` span,
      from a dispatch to its logits being ready;
    * ``logits_to_host_ms`` / ``sample_ms``: the ``*.to_host`` (the copy of
      a dispatch's output: its token ids) / ``*.sample`` (the device draw)
      spans summed per step, mean over the steps that have any;
    * ``host_bytes_per_step``: the ``*.host_bytes`` counters (bytes a
      dispatch copies off the device) summed per step, mean likewise;
    * ``step_host_share`` (%): the ``serve.step`` spans' time outside the
      ``*.device`` spans inside them, over the steps' time;
    * ``kv_block_fill`` / ``kv_blocks_held_share`` (%): mean of the
      ``kv.tokens_live`` / ``kv.blocks_held`` counters over their capacity;
    * ``prefill_chunk_fill`` (%): the ``prefill.tokens`` counted over the
      (rows x chunk) tokens the dispatches' fixed shape holds;
    * ``queued_p95_ms`` / ``prefill_phase_p95_ms`` /
      ``decode_phase_p95_ms``: nearest-rank 95th percentile of the
      ``request.queued`` / ``.prefill`` / ``.decode`` spans (arrival to a
      slot, the slot to the first token, the first token to release).
    """
    spans = [s for s in spans if t0 <= s.start and s.end <= t1]
    counters = [c for c in counters if t0 <= c.t <= t1]

    def per_step_ms(*names):
        per: dict = {}
        for s in spans:
            if s.name in names:
                per[s.parent] = per.get(s.parent, 0.0) + s.end - s.start
        return 1e3 * sum(per.values()) / len(per) if per else None

    def per_step_sum(*names):
        per: dict = {}
        for c in counters:
            if c.name in names:
                per[c.parent] = per.get(c.parent, 0.0) + c.value
        return sum(per.values()) / len(per) if per else None

    def fill(name):
        xs = [c.value / c.of for c in counters if c.name == name and c.of]
        return 100.0 * sum(xs) / len(xs) if xs else None

    def p95_ms(name):
        xs = sorted(s.end - s.start for s in spans if s.name == name)
        return 1e3 * xs[math.ceil(0.95 * len(xs)) - 1] if xs else None

    steps = {s.seq: s.end - s.start for s in spans if s.name == "serve.step"}
    step_s = sum(steps.values())
    device_s = sum(s.end - s.start for s in spans if s.parent in steps
                   and s.name in ("prefill.device", "decode.device"))
    packed = [c for c in counters if c.name == "prefill.tokens"]
    held = sum(c.of for c in packed)

    return {
        "decode_device_ms": per_step_ms("decode.device"),
        "prefill_device_ms": per_step_ms("prefill.device"),
        "logits_to_host_ms": per_step_ms("decode.to_host", "prefill.to_host"),
        "sample_ms": per_step_ms("decode.sample", "prefill.sample"),
        "host_bytes_per_step": per_step_sum("decode.host_bytes",
                                            "prefill.host_bytes"),
        "step_host_share": (100.0 * (step_s - device_s) / step_s
                            if step_s else None),
        "kv_block_fill": fill("kv.tokens_live"),
        "kv_blocks_held_share": fill("kv.blocks_held"),
        "prefill_chunk_fill": (100.0 * sum(c.value for c in packed) / held
                               if held else None),
        "queued_p95_ms": p95_ms("request.queued"),
        "prefill_phase_p95_ms": p95_ms("request.prefill"),
        "decode_phase_p95_ms": p95_ms("request.decode"),
    }
