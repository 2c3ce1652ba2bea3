"""Sampling on the device: each dispatch's logits stay on the device, one
jitted draw picks every row's token there, and only the (rows,) int32 ids
are copied to the host (``ServeEngine._sample``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import Model, get_config
from repro.runtime.serve import Request, ServeEngine

V = 64


@pytest.fixture(scope="module")
def lm():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=2, vocab=V, remat=False,
                              dtype="float32")
    return cfg, Model(cfg).init(jax.random.PRNGKey(0))


def _engine(lm, **kw):
    cfg, params = lm
    kw = {"max_batch": 3, "max_context": 32, "eos_id": -1,
          "prefill_chunk": 4, "prefill_batch": 3, "kv_block_size": 8, **kw}
    return ServeEngine(cfg, params, **kw)


def _requests(lens, max_new=3, seed=5):
    """rids from 1: rid 0 is what the dispatches' unused rows carry."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, V, n).astype(np.int32),
                    max_new_tokens=max_new) for i, n in enumerate(lens, 1)]


def _crafted_logits():
    """(4, V) f32 rows with ties at the top, float32's extremes, infinities
    and subnormals: every row's argmax is a tie-break or an edge."""
    big = np.finfo(np.float32).max
    x = np.random.default_rng(0).normal(size=(4, V)).astype(np.float32)
    x[0, [3, 17, 40]] = 9.0                       # a three-way tie
    x[1] = -big
    x[1, [0, V - 1]] = -big / 2                   # tie at both ends
    x[2, [5, 6]] = big
    x[2, 60] = np.inf                             # +inf beats float32 max
    x[3] = -np.inf
    x[3, [11, 12]] = np.float32(1e-45)            # subnormal tie over -inf
    return x


@pytest.mark.parametrize("shape", ["prefill", "decode"])
def test_greedy_draw_equals_numpy_argmax(lm, shape):
    eng = _engine(lm)
    host = _crafted_logits()
    dev = jnp.asarray(host if shape == "prefill" else host[:, None])
    ids = eng._sample(dev, np.arange(4), np.zeros(4, np.int64))
    assert isinstance(ids, np.ndarray) and ids.dtype == np.int32
    np.testing.assert_array_equal(ids, np.argmax(host, axis=-1))
    np.testing.assert_array_equal(ids, [3, 0, 60, 11])


@pytest.mark.parametrize("shape", ["prefill", "decode"])
def test_tempered_draw_equals_draw(lm, shape):
    """The device draw is ``_draw``'s Gumbel-argmax, keyed on the same
    (seed, rid, step), whatever the rows' shape."""
    eng = _engine(lm, temperature=0.8, seed=7)
    rng = np.random.default_rng(1)
    host = rng.normal(size=(5, V)).astype(np.float32)
    rids = rng.integers(0, 1000, 5)
    steps = rng.integers(0, 50, 5)
    dev = jnp.asarray(host if shape == "prefill" else host[:, None])
    want = np.asarray(eng._draw(jnp.asarray(rids, jnp.uint32),
                                jnp.asarray(steps, jnp.uint32),
                                jnp.asarray(host)))
    np.testing.assert_array_equal(eng._sample(dev, rids, steps), want)


def _watch_sample(eng):
    """Wrap ``eng._sample``; the (logits, rids, steps, ids) of each call."""
    sample, calls = eng._sample, []

    def watched(logits, rids, steps):
        ids = sample(logits, rids, steps)
        calls.append((logits, np.array(rids), np.array(steps), ids))
        return ids
    eng._sample = watched
    return calls


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_dispatch_hands_sample_device_logits(lm, temperature):
    """Once per dispatch, ``_sample`` gets the dispatch's device logits and
    returns the rows' int32 ids; the served streams are those the host
    argmax (or ``_draw``) gives on the same logits."""
    eng = _engine(lm, temperature=temperature, seed=3)
    eng.rec.on = True
    calls = _watch_sample(eng)
    reqs = _requests([5, 11, 3, 9, 7], max_new=4)
    emitted = []
    for r in reqs:
        r.on_token = lambda *tok: emitted.append((len(calls) - 1, *tok))
    eng.run(reqs)
    assert all(r.status == "done" for r in reqs)
    assert len(calls) == (eng.stats["prefill_dispatches"]
                          + eng.stats["decode_steps"])
    for logits, rids, steps, ids in calls:
        assert isinstance(logits, jax.Array) and logits.shape[-1] == V
        rows = logits.shape[0]
        assert ids.shape == (rows,) and ids.dtype == np.int32
        flat = np.asarray(logits).reshape(rows, V)
        if temperature:
            want = eng._draw(jnp.asarray(rids, jnp.uint32),
                             jnp.asarray(steps, jnp.uint32),
                             jnp.asarray(flat))
        else:
            want = np.argmax(flat, axis=-1)
        np.testing.assert_array_equal(ids, want)
    # each token served is its own row's id of the draw just before it
    for call, rid, step, tok in emitted:
        _, rids, steps, ids = calls[call]
        row = np.flatnonzero((rids == rid) & (steps == step))
        assert len(row) == 1 and ids[row[0]] == tok
    assert len(emitted) == sum(len(r.out_tokens) for r in reqs)
    copied = [c.value for c in eng.rec.counters
              if c.name.endswith(".host_bytes")]
    assert copied == [4 * c[0].shape[0] for c in calls]


class _Compiles:
    """Counts programs traced or compiled while it is entered."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n = 0

    def __call__(self, name, _secs, **_kw):
        self.n += name in self.EVENTS

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_prefill_completions_compile_nothing_after_warm_up(lm, temperature):
    """After one warm-up request, a (P, chunk) prefill dispatch in which
    1, 2, ... P rows complete their prompts compiles no new program: the
    draw always reads all P rows."""
    eng = _engine(lm, temperature=temperature)
    P, chunk = eng.prefill_batch, eng.prefill_chunk
    warm = Request(rid=-1, prompt=np.arange(chunk + 1, dtype=np.int32) % V,
                   max_new_tokens=3)
    eng.run([warm])
    assert warm.status == "done"
    for k in range(1, P + 1):
        # k prompts complete in their first chunk, P - k need a second
        reqs = _requests([chunk - 1] * k + [chunk + 2] * (P - k), max_new=2,
                         seed=k)
        for r in reqs:
            eng.submit(r)
        with _Compiles() as seen:
            eng.step()
        assert seen.n == 0, k
        assert sum(bool(r.out_tokens) for r in reqs) == k
        while eng.queue or eng.slots:
            eng.step()
