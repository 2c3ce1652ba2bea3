"""Paged serving engine: parity vs the reference engine, chunked prefill,
slot reuse, admission, deadlines, sampler determinism (DESIGN.md 13)."""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.nn import Model, get_config
from repro.runtime.serve import (ReferenceEngine, Request, ServeEngine,
                                 summarize)


@pytest.fixture(scope="module")
def lm32():
    """float32 tiny dense LM: parity across engines/code paths must be exact
    (the chunked-prefill and decode attention paths differ only by softmax
    association, which float32 keeps bit-stable at this scale)."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=2, vocab=64, remat=False,
                              dtype="float32")
    m = Model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _reqs(prompts, max_new=6, **kw):
    return [Request(rid=i, prompt=np.asarray(p, np.int32),
                    max_new_tokens=max_new, **kw)
            for i, p in enumerate(prompts)]


def _serve(cfg, params, prompts, engine="paged", max_new=6, **kw):
    cls = ServeEngine if engine == "paged" else ReferenceEngine
    eng = cls(cfg, params, eos_id=-1, **kw)
    reqs = _reqs(prompts, max_new=max_new)
    eng.run(reqs)
    return eng, reqs


# --------------------------------------------------- old-vs-new engine parity

def test_parity_vs_reference_equal_lengths(lm32):
    """Equal-length prompts: the reference engine pads nothing, so greedy
    outputs must match the paged engine token for token."""
    cfg, m, params = lm32
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 7) for _ in range(5)]
    _, ref = _serve(cfg, params, prompts, engine="reference",
                    max_batch=2, max_context=32)
    _, new = _serve(cfg, params, prompts, engine="paged",
                    max_batch=2, max_context=32, prefill_chunk=3)
    assert [r.out_tokens for r in new] == [r.out_tokens for r in ref]


def test_parity_vs_reference_mixed_lengths_b1(lm32):
    """Mixed prompt lengths at max_batch=1: no left-padding in either
    engine, so parity must hold for ragged prompts too."""
    cfg, m, params = lm32
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (3, 11, 6)]
    _, ref = _serve(cfg, params, prompts, engine="reference",
                    max_batch=1, max_context=32)
    _, new = _serve(cfg, params, prompts, engine="paged",
                    max_batch=1, max_context=32, prefill_chunk=4)
    assert [r.out_tokens for r in new] == [r.out_tokens for r in ref]


def test_parity_quantized(lm32):
    cfg, m, params = lm32
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, 5) for _ in range(3)]
    _, ref = _serve(cfg, params, prompts, engine="reference",
                    max_batch=2, max_context=32, quantized=True)
    _, new = _serve(cfg, params, prompts, engine="paged",
                    max_batch=2, max_context=32, quantized=True,
                    prefill_chunk=2)
    assert [r.out_tokens for r in new] == [r.out_tokens for r in ref]


# ------------------------------------------------------------ chunked prefill

def test_prefill_chunk_size_invariance(lm32):
    """The chunk size is a scheduling knob, not a numerics knob: any chunking
    of the prompt must produce identical greedy tokens (each chunk row
    attends to exactly cache[0..offset+i]; padded tail positions are masked
    and overwritten in place before the slot length crosses them)."""
    cfg, m, params = lm32
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (13, 5, 9)]
    outs = []
    for chunk in (2, 5, 64):
        _, reqs = _serve(cfg, params, prompts, max_batch=2, max_context=32,
                         prefill_chunk=chunk)
        outs.append([r.out_tokens for r in reqs])
    assert outs[0] == outs[1] == outs[2]


def test_prefill_chunk_crossing_context_boundary(lm32):
    """Regression: when the fixed chunk window crossed max_context
    (offset + chunk > C), dynamic_update_slice clamped the start index and
    shifted the chunk — pad garbage included — over earlier prompt KV.  A
    max-length prompt with non-dividing chunk sizes must match the
    single-chunk result token for token."""
    cfg, m, params = lm32
    # seed chosen so the pre-fix engine demonstrably diverges here
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 31)   # max admissible for C=32
    outs = []
    for chunk in (5, 7, 64):                  # 5, 7 do not divide 32
        _, reqs = _serve(cfg, params, [prompt], max_batch=1, max_context=32,
                         max_new=4, prefill_chunk=chunk)
        outs.append(reqs[0].out_tokens)
    assert outs[0] == outs[1] == outs[2]


def test_long_prompt_does_not_stall_decode(lm32):
    """Chunked prefill interleaves with decode: while a long prompt streams
    in, an already-decoding slot keeps emitting a token per engine step."""
    cfg, m, params = lm32
    eng = ServeEngine(cfg, params, max_batch=2, max_context=64, eos_id=-1,
                      prefill_chunk=4)
    rng = np.random.default_rng(4)
    short = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 4)
                    .astype(np.int32), max_new_tokens=16)
    long = Request(rid=1, prompt=rng.integers(0, cfg.vocab, 40)
                   .astype(np.int32), max_new_tokens=2)
    eng.submit(short)
    # one step = prefill completion (first token) + one decode token
    eng.step()
    assert len(short.out_tokens) == 2
    eng.submit(long)                # 40-token prompt = 10 more chunks
    n0 = len(short.out_tokens)
    for _ in range(5):              # long is mid-prefill the whole time
        eng.step()
    assert len(short.out_tokens) == n0 + 5     # one token per step, no stall
    while eng.queue or eng.slots:
        eng.step()
    assert short.status == long.status == "done"
    assert len(long.out_tokens) == 2


# ------------------------------------------------- slots, admission, deadline

def test_slot_reuse_and_refill_mid_stream(lm32):
    """More requests than slots: slots are released and re-assigned while
    other slots keep decoding — no whole-batch refresh barrier."""
    cfg, m, params = lm32
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, 4 + i) for i in range(6)]
    eng, reqs = _serve(cfg, params, prompts, max_batch=2, max_context=32,
                       max_new=4, prefill_chunk=8)
    assert all(r.status == "done" for r in reqs)
    assigns = [e for e in eng.events if e[1] == "assign"]
    releases = [e for e in eng.events if e[1] == "release"]
    assert len(assigns) == 6 and len(releases) == 6
    # at least one slot serves several requests...
    slots_used = [s for _, _, _, s in assigns]
    assert max(slots_used.count(s) for s in set(slots_used)) >= 2
    # ...and re-assignment happens while the other slot is mid-request
    # (some assign strictly between another slot's assign and release)
    for step, _, rid, slot in assigns[2:]:
        other = [(e[0], r[0]) for e, r in zip(assigns, releases)
                 if e[3] != slot]
        if any(a < step <= r for a, r in other):
            break
    else:
        pytest.fail("no mid-stream refill observed")


def test_admission_reject_overflow_regression(lm32):
    """Seed-engine bug: a prompt longer than max_context overflowed the KV
    ring silently.  Both engines must now reject it at admission."""
    cfg, m, params = lm32
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, 40),      # > max_context=16
               rng.integers(0, cfg.vocab, 5)]
    for engine in ("paged", "reference"):
        eng, reqs = _serve(cfg, params, prompts, engine=engine,
                           max_batch=2, max_context=16, admission="reject")
        assert reqs[0].status == "rejected" and reqs[0].out_tokens == []
        assert reqs[1].status == "done" and len(reqs[1].out_tokens) == 6


def test_admission_truncate_keeps_tail(lm32):
    cfg, m, params = lm32
    rng = np.random.default_rng(7)
    long = rng.integers(0, cfg.vocab, 40).astype(np.int32)
    for engine in ("paged", "reference"):
        eng, reqs = _serve(cfg, params, [long.copy()], engine=engine,
                           max_batch=1, max_context=16, max_new=3,
                           admission="truncate")
        r = reqs[0]
        assert r.truncated and r.status == "done"
        np.testing.assert_array_equal(r.prompt, long[-15:])  # tail kept
        # cap: prompt(15) + first token + 1 decode write fills the slot
        assert len(r.out_tokens) == 2


def test_truncated_equals_pretruncated(lm32):
    """Serving a truncated prompt == serving its tail directly."""
    cfg, m, params = lm32
    rng = np.random.default_rng(8)
    long = rng.integers(0, cfg.vocab, 30).astype(np.int32)
    _, a = _serve(cfg, params, [long.copy()], max_batch=1, max_context=16,
                  max_new=2, admission="truncate")
    _, b = _serve(cfg, params, [long[-15:].copy()], max_batch=1,
                  max_context=16, max_new=2)
    assert a[0].out_tokens == b[0].out_tokens


def test_deadline_expiry_fake_clock(lm32):
    """Queued requests past their deadline expire before ever taking a slot
    (injected clock makes the timeout deterministic)."""
    cfg, m, params = lm32
    t = [0.0]
    eng = ServeEngine(cfg, params, max_batch=1, max_context=32, eos_id=-1,
                      clock=lambda: t[0])
    rng = np.random.default_rng(9)
    reqs = _reqs([rng.integers(0, cfg.vocab, 4) for _ in range(3)],
                 max_new=3)
    reqs[1].deadline_s = 5.0      # expires while req 0 holds the only slot
    reqs[2].deadline_s = 1e9
    for r in reqs:
        eng.submit(r)
    t[0] = 10.0
    while eng.queue or eng.slots:
        eng.step()
    assert [r.status for r in reqs] == ["done", "expired", "done"]
    assert reqs[1].out_tokens == []
    assert any(e[1] == "expire" and e[2] == 1 for e in eng.events)
    assert reqs[1].stats["queue_s"] == 10.0


def test_per_request_latency_stats(lm32):
    cfg, m, params = lm32
    rng = np.random.default_rng(10)
    eng, reqs = _serve(cfg, params,
                       [rng.integers(0, cfg.vocab, 5) for _ in range(3)],
                       max_batch=2, max_context=32, max_new=4)
    for r in reqs:
        for k in ("queue_s", "prefill_s", "first_token_s", "total_s",
                  "decode_tokens", "decode_s", "max_new_eff"):
            assert k in r.stats, k
        assert r.stats["first_token_s"] <= r.stats["total_s"]
        assert r.stats["decode_tokens"] == len(r.out_tokens) - 1
    s = summarize(reqs, eng)
    assert s["done"] == 3 and s["decode_tok_s"] > 0
    assert s["p50_total_s"] <= s["p99_total_s"]
    # aggregate tok/s divides by the ENGINE's batched-decode wall time (the
    # per-request decode_s each count full shared dispatches and cannot be
    # recombined); without the engine the aggregate is not reported
    assert s["decode_tok_s"] == pytest.approx(
        s["decode_tokens"] / eng.stats["decode_s"])
    assert summarize(reqs)["decode_tok_s"] == 0.0


def test_injected_now_timebase(lm32):
    """submit(now=...)/step(now=...) keep every latency stat in the caller's
    timebase — no mixing of simulated arrival times with the real clock."""
    cfg, m, params = lm32
    eng = ServeEngine(cfg, params, max_batch=1, max_context=32, eos_id=-1,
                      prefill_chunk=64)
    rng = np.random.default_rng(20)
    r = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                max_new_tokens=3)
    eng.submit(r, now=100.0)
    t = 100.0
    while eng.queue or eng.slots:
        t += 1.0
        eng.step(now=t)
    # step 1: prompt ingested + first token + one decode token; step 2: last
    assert r.stats["queue_s"] == 1.0
    assert r.stats["first_token_s"] == 1.0
    assert r.stats["total_s"] == t - 100.0 == 2.0


# ------------------------------------------------------- sampler determinism

def test_sampler_deterministic_across_runs_and_batches(lm32):
    """temperature>0 streams depend only on (seed, rid, token index):
    identical across reruns AND across batch compositions."""
    cfg, m, params = lm32
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, 6) for _ in range(4)]

    def toks(idxs, **kw):
        eng = ServeEngine(cfg, params, eos_id=-1, temperature=0.8, seed=7,
                          max_context=32, **kw)
        reqs = [Request(rid=i, prompt=np.asarray(prompts[i], np.int32),
                        max_new_tokens=5) for i in idxs]
        eng.run(reqs)
        return {r.rid: r.out_tokens for r in reqs}

    full = toks(range(4), max_batch=4)
    again = toks(range(4), max_batch=4)
    assert full == again                                   # rerun-stable
    solo = {}
    for i in range(4):                                     # batch-of-one
        solo.update(toks([i], max_batch=1))
    assert solo == full                                    # composition-free
    pairs = toks([2, 0], max_batch=2)                      # different mix
    assert pairs[0] == full[0] and pairs[2] == full[2]
    assert toks(range(4), max_batch=4, prefill_chunk=2) == full


def test_sampler_seed_changes_stream(lm32):
    cfg, m, params = lm32
    rng = np.random.default_rng(12)
    p = [rng.integers(0, cfg.vocab, 6)]

    def toks(seed):
        eng = ServeEngine(cfg, params, eos_id=-1, temperature=0.8,
                          seed=seed, max_batch=1, max_context=32)
        reqs = _reqs(p, max_new=8)
        eng.run(reqs)
        return reqs[0].out_tokens

    assert toks(0) != toks(1)


# ------------------------------------------------------------ guard + events

def test_non_dense_family_raises(lm32):
    cfg = get_config("rwkv6-3b").reduced()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        ServeEngine(cfg, params)
    # the reference engine still serves recurrent-state families
    eng = ReferenceEngine(cfg, params, max_batch=1, max_context=16,
                          eos_id=-1)
    reqs = _reqs([np.arange(4) % cfg.vocab], max_new=2)
    eng.run(reqs)
    assert len(reqs[0].out_tokens) == 2


def test_eos_stops_decode(lm32):
    """Greedy decode stops the request the moment EOS is emitted (the EOS
    token itself is kept — reference-engine semantics)."""
    cfg, m, params = lm32
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, 6) for _ in range(3)]
    _, free = _serve(cfg, params, prompts, max_batch=2, max_context=32,
                     max_new=6)
    eos = free[0].out_tokens[2]     # force an EOS mid-stream for req 0
    eng = ServeEngine(cfg, params, max_batch=2, max_context=32, eos_id=eos,
                      prefill_chunk=64)
    reqs = _reqs(prompts, max_new=6)
    eng.run(reqs)
    assert reqs[0].out_tokens == free[0].out_tokens[:3]
    for r, f in zip(reqs, free):
        cut = (f.out_tokens[1:].index(eos) + 2 if eos in f.out_tokens[1:]
               else len(f.out_tokens))
        assert r.out_tokens == f.out_tokens[:cut]


# ------------------------------------------------------- shard_map decode DP

_DP_SCRIPT = r"""
import dataclasses
import jax
import numpy as np
from repro.nn import Model, get_config
from repro.runtime.serve import Request, ServeEngine
assert jax.device_count() == 4
cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), n_layers=2,
                          vocab=64, remat=False, dtype="float32")
m = Model(cfg)
params = m.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab, 5 + i) for i in range(5)]
outs = []
for dp in (False, True):
    eng = ServeEngine(cfg, params, max_batch=4, max_context=32, eos_id=-1,
                      prefill_chunk=4, data_parallel=dp)
    reqs = [Request(rid=i, prompt=np.asarray(p, np.int32), max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    outs.append([r.out_tokens for r in reqs])
assert outs[0] == outs[1], (outs[0], outs[1])
try:
    ServeEngine(cfg, params, max_batch=3, data_parallel=True)
except ValueError:
    print("DIV-GUARD-OK")
print("DP-OK")
"""


def test_data_parallel_decode_parity():
    """shard_map decode over 4 forced host devices == single-device greedy."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _DP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DP-OK" in out.stdout and "DIV-GUARD-OK" in out.stdout


# --------------------------------- block-paged KV + batched prefill (PR 8)

def test_parity_mixed_lengths_multiblock_batched_prefill(lm32):
    """The acceptance case: mixed prompt lengths, a chunk size that divides
    none of them, contexts spanning several KV blocks, and batched
    multi-chunk prefill — greedy outputs must be bit-identical to the
    reference engine (max_batch=1: no left-padding on either side)."""
    cfg, m, params = lm32
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (3, 17, 9, 22, 5)]
    _, ref = _serve(cfg, params, prompts, engine="reference",
                    max_batch=1, max_context=32)
    _, new = _serve(cfg, params, prompts, engine="paged",
                    max_batch=3, max_context=32, prefill_chunk=5,
                    prefill_batch=3, kv_block_size=8)
    assert [r.out_tokens for r in new] == [r.out_tokens for r in ref]


def test_block_paged_matches_contiguous(lm32):
    """kv_block_size is a memory-layout knob, not a numerics knob: the
    block-table gather path (both the jnp.take reference route and the
    Pallas scalar-prefetch kernel in interpret mode) must reproduce the
    contiguous engine's greedy tokens exactly, and the run must hand every
    block back to the pool."""
    cfg, m, params = lm32
    rng = np.random.default_rng(22)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (13, 4, 19, 7)]
    _, contig = _serve(cfg, params, prompts, max_batch=2, max_context=32,
                       prefill_chunk=6)
    want = [r.out_tokens for r in contig]
    for gather in ("take", "pallas"):
        eng = ServeEngine(cfg, params, eos_id=-1, max_batch=2,
                          max_context=32, prefill_chunk=6,
                          kv_block_size=8, kv_gather=gather)
        reqs = _reqs(prompts, max_new=6)
        eng.run(reqs)
        assert [r.out_tokens for r in reqs] == want, gather
        assert eng.cache.n_free_blocks == eng.cache.n_blocks, gather
        assert (eng.cache.block_table == eng.cache.n_blocks).all(), gather


def test_prefill_batch_invariance(lm32):
    """prefill_batch is a scheduling knob: ingesting 1, 2 or 4 chunks per
    engine step must not change any request's greedy tokens (idle rows in
    the batched dispatch write at the drop sentinel and read nothing)."""
    cfg, m, params = lm32
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (11, 3, 16, 8, 6)]
    outs = []
    for pb in (1, 2, 4):
        _, reqs = _serve(cfg, params, prompts, max_batch=4, max_context=32,
                         prefill_chunk=5, prefill_batch=pb)
        outs.append([r.out_tokens for r in reqs])
    assert outs[0] == outs[1] == outs[2]


def test_on_token_streaming_order(lm32):
    """Request.on_token streams every generated token in order, for both
    engines: per request the callback sees steps 0..n-1 exactly once, in
    order, and the streamed tokens equal the final out_tokens."""
    cfg, m, params = lm32
    rng = np.random.default_rng(24)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (4, 9, 6)]
    for engine, kw in (("paged", dict(max_batch=2, max_context=32,
                                      prefill_chunk=4, prefill_batch=2)),
                       ("reference", dict(max_batch=2, max_context=32))):
        cls = ServeEngine if engine == "paged" else ReferenceEngine
        seen = {i: [] for i in range(len(prompts))}
        eng = cls(cfg, params, eos_id=-1, **kw)
        reqs = _reqs(prompts, max_new=6,
                     on_token=lambda rid, step, tok: seen[rid].append(
                         (step, tok)))
        eng.run(reqs)
        for r in reqs:
            assert [s for s, _ in seen[r.rid]] == list(
                range(len(r.out_tokens))), engine
            assert [t for _, t in seen[r.rid]] == r.out_tokens, engine


def test_data_parallel_block_paged_routes_to_tensor_parallel(lm32):
    """data_parallel + kv_block_size no longer raises (the PR-8 guard):
    slot-sharding still cannot index the global block pool, so the engine
    routes the request to the head-sharded (tensor-parallel) decode — a
    real sharded dispatch with identical greedy tokens."""
    cfg, m, params = lm32
    rng = np.random.default_rng(25)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (13, 4, 19, 7)]
    _, plain = _serve(cfg, params, prompts, max_batch=2, max_context=32,
                      prefill_chunk=6, kv_block_size=8)
    eng, reqs = _serve(cfg, params, prompts, max_batch=2, max_context=32,
                       prefill_chunk=6, kv_block_size=8, data_parallel=True)
    assert eng.tensor_parallel
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in plain]


# --------------------------- fused paged decode + tensor parallelism (PR 9)

def test_decode_kernel_routes_token_parity(lm32):
    """The acceptance case for decode_kernel: mixed prompt lengths,
    non-dividing chunk size, several KV blocks per slot, slot churn — the
    scan-reference and fused-kernel routes must emit bit-identical token
    streams to the dense gather+masked-pass oracle (and the fused lane also
    exercises the Pallas gather in the prefill path)."""
    cfg, m, params = lm32
    rng = np.random.default_rng(30)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (3, 17, 9, 22, 5, 13)]
    _, dense = _serve(cfg, params, prompts, max_batch=3, max_context=32,
                      prefill_chunk=5, prefill_batch=2, kv_block_size=8,
                      max_new=8)
    want = [r.out_tokens for r in dense]
    for kern, gather in (("reference", "take"), ("fused", "take"),
                         ("fused", "pallas")):
        eng, reqs = _serve(cfg, params, prompts, max_batch=3, max_context=32,
                           prefill_chunk=5, prefill_batch=2, kv_block_size=8,
                           decode_kernel=kern, kv_gather=gather, max_new=8)
        assert [r.out_tokens for r in reqs] == want, (kern, gather)
        assert eng.cache.n_free_blocks == eng.cache.n_blocks, kern


def test_decode_kernel_needs_block_pool(lm32):
    """reference/fused read the block pool directly — contiguous caches
    have no pool, so the combination is a configuration error."""
    cfg, m, params = lm32
    with pytest.raises(ValueError, match="kv_block_size"):
        ServeEngine(cfg, params, max_batch=2, max_context=32,
                    decode_kernel="fused")


def test_cache_donation_frees_old_buffers(lm32):
    """Both jitted dispatches donate the KV-cache pytree: after a step, the
    PREVIOUS cache buffers must be deleted (updated in place), not left
    live alongside the new ones — the live-buffer regression that doubles
    resident KV."""
    cfg, m, params = lm32
    for kw in (dict(), dict(kv_block_size=8)):
        eng = ServeEngine(cfg, params, eos_id=-1, max_batch=2,
                          max_context=32, prefill_chunk=4, **kw)
        for r in _reqs([np.arange(1, 9)], max_new=4):
            eng.submit(r)
        old = jax.tree.leaves(eng.cache.data)
        eng.step()                                 # prefill dispatch donates
        assert all(x.is_deleted() for x in old), kw
        old = jax.tree.leaves(eng.cache.data)
        eng.step()                                 # decode dispatch donates
        assert all(x.is_deleted() for x in old), kw
        while eng.queue or eng.slots:
            eng.step()


_TP_SCRIPT = r"""
import dataclasses
import jax
import numpy as np
from repro.nn import Model, get_config
from repro.runtime.serve import Request, ServeEngine
assert jax.device_count() == 4
# MHA variant: 4 devices must divide n_kv_heads (reduced() gives 2)
cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), n_layers=2,
                          vocab=64, remat=False, dtype="float32",
                          n_kv_heads=4)
m = Model(cfg)
params = m.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab, 3 + 5 * i) for i in range(5)]
def serve(**kw):
    eng = ServeEngine(cfg, params, max_batch=4, max_context=32, eos_id=-1,
                      prefill_chunk=4, prefill_batch=2, **kw)
    reqs = [Request(rid=i, prompt=np.asarray(p, np.int32), max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return [r.out_tokens for r in reqs]
base = serve()
assert serve(tensor_parallel=True) == base, "tp contiguous"
assert serve(tensor_parallel=True, kv_block_size=8) == base, "tp block"
assert serve(tensor_parallel=True, kv_block_size=8,
             decode_kernel="fused") == base, "tp fused"
assert serve(data_parallel=True, kv_block_size=8) == base, "dp+block reroute"
try:
    ServeEngine(dataclasses.replace(cfg, n_kv_heads=2), params, max_batch=4,
                tensor_parallel=True)
except ValueError:
    print("TP-DIV-GUARD-OK")
print("TP-OK")
"""


def test_tensor_parallel_decode_parity():
    """Head-sharded shard_map decode over 4 forced host devices emits
    token streams bit-identical to the single-device route — contiguous,
    block-paged, block-paged + fused kernel, and the data_parallel+block
    reroute (psum re-associates logits, so parity is on TOKENS)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _TP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TP-OK" in out.stdout and "TP-DIV-GUARD-OK" in out.stdout
