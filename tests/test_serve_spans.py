"""The serving engine's record (``engine.rec``): off by default and
invisible to what the engine serves; on, its step spans nest, its request
spans add up to the latency stats, and its buffers count what they drop."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.nn import Model, get_config
from repro.runtime.serve import REQUEST_SPANS, SPANS, Request, ServeEngine
from repro.runtime.spans import Counter, Recorder, Span, breakdown

TIMINGS = ("prefill_s", "decode_s", "decode_tok_s", "queue_s",
           "first_token_s", "total_s")


@pytest.fixture(scope="module")
def lm():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=2, vocab=64, remat=False,
                              dtype="float32")
    return cfg, Model(cfg).init(jax.random.PRNGKey(0))


def _serve(lm, on: bool, **kw):
    cfg, params = lm
    eng = ServeEngine(cfg, params, max_batch=3, max_context=32, eos_id=-1,
                      prefill_chunk=4, prefill_batch=2, kv_block_size=8, **kw)
    eng.rec.on = on
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n)
                    .astype(np.int32), max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 4), (11, 1), (3, 6), (9, 3),
                                        (7, 5)])]
    eng.run(reqs)
    return eng, reqs


def _untimed(stats):
    return {k: v for k, v in stats.items() if k not in TIMINGS}


def test_off_by_default_records_nothing_and_changes_nothing(lm):
    off, r_off = _serve(lm, on=False)
    on, r_on = _serve(lm, on=True)
    assert not off.rec.on
    assert list(off.rec.spans) == [] and list(off.rec.counters) == []
    assert off.rec.dropped == 0
    assert len(on.rec.spans) > 0 and len(on.rec.counters) > 0
    assert [r.out_tokens for r in r_off] == [r.out_tokens for r in r_on]
    assert _untimed(off.stats) == _untimed(on.stats)
    assert [_untimed(r.stats) for r in r_off] == \
        [_untimed(r.stats) for r in r_on]
    # the always-on decision log is the same, in its tuple form
    assert off.events == on.events and off.events is off.rec.events
    assert all(len(e) == 4 for e in off.events)


def test_step_spans_nest_inside_serve_step(lm):
    eng, _ = _serve(lm, on=True)
    spans = list(eng.rec.spans)
    by_seq = {s.seq: s for s in spans}
    steps = [s for s in spans if s.name == "serve.step"]
    assert [s.step for s in steps] == list(range(1, eng.stats["steps"] + 1))
    assert {s.name for s in spans} == set(SPANS) | set(REQUEST_SPANS)
    for s in spans:
        assert s.start <= s.end
        if s.name.startswith(("prefill.", "decode.", "serve.")) \
                and s.name != "serve.step":
            parent = by_seq[s.parent]
            assert parent.name == "serve.step", s
            assert parent.start <= s.start and s.end <= parent.end
            assert s.step == parent.step
    for name in ("decode.device", "prefill.device"):
        assert sum(s.name == name for s in spans) == eng.stats[
            "decode_steps" if name[0] == "d" else "prefill_dispatches"]


def test_dispatch_seconds_are_the_spans(lm):
    """stats["decode_s"] / ["prefill_s"] are read off the same monotonic
    readings as the *.device, *.sample and *.to_host spans: each dispatch's
    seconds run from the device span's start, through the device draw, to
    the ids' copy span's end."""
    eng, _ = _serve(lm, on=True)
    for phase in ("prefill", "decode"):
        dev = [s for s in eng.rec.spans if s.name == f"{phase}.device"]
        draw = [s for s in eng.rec.spans if s.name == f"{phase}.sample"]
        host = [s for s in eng.rec.spans if s.name == f"{phase}.to_host"]
        assert len(dev) == len(draw) == len(host) > 0
        total = sum(h.end - d.start for d, h in zip(dev, host))
        assert eng.stats[f"{phase}_s"] == pytest.approx(total, rel=1e-12)
        assert all(d.end <= s.start and s.end <= h.start
                   for d, s, h in zip(dev, draw, host))


def test_request_spans_add_up_to_first_token(lm):
    """With an injected clock: request.queued + request.prefill is
    stats["first_token_s"], and request.decode ends at release."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    eng, reqs = _serve(lm, on=True, clock=clock)
    spans = {(s.name, s.rid): s for s in eng.rec.spans if s.rid is not None}
    for r in reqs:
        q = spans["request.queued", r.rid]
        p = spans["request.prefill", r.rid]
        d = spans["request.decode", r.rid]
        assert q.start == r.arrival_s and q.end == p.start
        assert (q.end - q.start) + (p.end - p.start) == \
            r.stats["first_token_s"]
        assert p.end == d.start
        assert d.end - q.start == r.stats["total_s"]


def test_counters_at_each_dispatch(lm):
    eng, reqs = _serve(lm, on=True)
    counters = list(eng.rec.counters)
    assert {c.name for c in counters} == {"kv.blocks_held", "kv.tokens_live",
                                          "prefill.tokens",
                                          "prefill.host_bytes",
                                          "decode.host_bytes"}
    toks = [c for c in counters if c.name == "prefill.tokens"]
    assert len(toks) == eng.stats["prefill_dispatches"]
    assert sum(c.value for c in toks) == sum(len(r.prompt) for r in reqs)
    assert all(c.of == 2 * 4 for c in toks)
    held = [c for c in counters if c.name == "kv.blocks_held"]
    live = [c for c in counters if c.name == "kv.tokens_live"]
    assert len(held) == len(live) == eng.stats["decode_steps"]
    assert all(c.of == eng.cache.n_blocks for c in held)
    # each decode step's live positions fit the blocks held, by less than
    # a block per live slot
    for h, c in zip(held, live):
        assert c.of == h.value * 8 and c.value <= c.of
        assert c.of - c.value < 8 * eng.max_batch
    # each dispatch copies its rows' int32 ids off the device, of the
    # (rows, V) f32 logits it made; counted in the step, outside its spans
    steps = {s.seq for s in eng.rec.spans if s.name == "serve.step"}
    vocab = lm[0].vocab
    for phase, rows, n in (("prefill", 2, eng.stats["prefill_dispatches"]),
                           ("decode", 3, eng.stats["decode_steps"])):
        copied = [c for c in counters if c.name == f"{phase}.host_bytes"]
        assert len(copied) == n
        assert all(c.value == rows * 4 and c.of == rows * vocab * 4
                   and c.parent in steps for c in copied)


def test_breakdown_of_a_hand_built_record():
    """Every number of ``breakdown`` from a record whose spans and counters
    are set by hand, and the window cuts what lies outside it."""
    steps = [Span(1, "serve.step", 0.0, 1.0, None, None, 1),
             Span(2, "prefill.device", 0.1, 0.3, 1, None, 1),
             Span(3, "prefill.to_host", 0.3, 0.35, 1, None, 1),
             Span(4, "prefill.sample", 0.35, 0.4, 1, None, 1),
             Span(5, "decode.device", 0.4, 0.6, 1, None, 1),
             Span(6, "decode.to_host", 0.6, 0.75, 1, None, 1),
             Span(7, "decode.sample", 0.75, 0.8, 1, None, 1),
             Span(8, "serve.step", 1.0, 1.5, None, None, 2),
             Span(9, "decode.device", 1.0, 1.4, 8, None, 2),
             Span(10, "decode.to_host", 1.4, 1.45, 8, None, 2),
             Span(11, "request.prefill", 0.0, 0.4, None, 3, None),
             Span(12, "request.prefill", 0.2, 1.4, None, 4, None),
             Span(14, "request.queued", 0.0, 0.2, None, 4, None),
             Span(15, "request.decode", 0.4, 1.9, None, 3, None),
             Span(16, "request.decode", 1.4, 2.5, None, 4, None),
             Span(13, "serve.step", 1.5, 9.0, None, None, 3)]
    counters = [Counter("kv.blocks_held", 0.5, 2, 8, 1),
                Counter("kv.tokens_live", 0.5, 6, 8, 1),
                Counter("kv.blocks_held", 1.2, 4, 8, 8),
                Counter("kv.tokens_live", 1.2, 16, 16, 8),
                Counter("prefill.tokens", 0.2, 3, 8, 1),
                Counter("prefill.tokens", 5.0, 8, 8, 13),
                Counter("prefill.host_bytes", 0.35, 8, 1024, 1),
                Counter("decode.host_bytes", 0.75, 12, 1536, 1),
                Counter("decode.host_bytes", 1.45, 12, 1536, 8)]
    got = breakdown(steps, counters, 0.0, 2.0)
    want = {"decode_device_ms": 300.0, "prefill_device_ms": 200.0,
            "logits_to_host_ms": 125.0, "sample_ms": 100.0,
            "host_bytes_per_step": (8 + 12 + 12) / 2,
            "step_host_share": 100.0 * (1.5 - 0.8) / 1.5,
            "kv_block_fill": 100.0 * (6 / 8 + 1) / 2,
            "kv_blocks_held_share": 100.0 * (2 / 8 + 4 / 8) / 2,
            "prefill_chunk_fill": 100.0 * 3 / 8,
            "queued_p95_ms": 200.0, "prefill_phase_p95_ms": 1200.0,
            "decode_phase_p95_ms": 1500.0}
    assert got == pytest.approx(want)
    assert breakdown([], []) == dict.fromkeys(want)


def test_breakdown_of_a_served_run(lm):
    eng, _ = _serve(lm, on=True)
    got = breakdown(eng.rec.spans, eng.rec.counters)
    assert all(v is not None for v in got.values()), got
    for k in ("step_host_share", "kv_block_fill", "kv_blocks_held_share",
              "prefill_chunk_fill"):
        assert 0 < got[k] <= 100, k


def test_buffer_counts_its_drops():
    rec = Recorder(capacity=3)
    with rec.span("a"):
        pass
    assert rec.dropped == 0 and list(rec.spans) == []   # off: nothing kept
    rec.on = True
    for i in range(5):
        with rec.span(f"s{i}"):
            rec.count("c", i)
    rec.add("request.queued", 0.0, 1.0, rid=7)
    assert [s.name for s in rec.spans] == ["s3", "s4", "request.queued"]
    assert [c.value for c in rec.counters] == [2, 3, 4]
    assert rec.dropped == 3 + 2
