"""Pure scheduler + paged KV cache: unit tests, property tests of the
host-side simulator oracle, and the engine-vs-oracle cross-check
(DESIGN.md 13).  Seeded-numpy property cases always run; hypothesis widens
the search when installed."""
import dataclasses

import numpy as np
import pytest

from repro.runtime.kvcache import (ADMIT_OK, ADMIT_REJECT, ADMIT_TRUNCATE,
                                   PagedKVCache, admit, alloc_blocks,
                                   assign_slots, blocks_needed, expire,
                                   free_blocks, simulate)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ------------------------------------------------------------- unit: admit

def test_admit_boundaries():
    assert admit(15, 16) == (ADMIT_OK, 15)        # max_context-1 fits
    assert admit(16, 16) == (ADMIT_REJECT, 0)     # no room for decode write
    assert admit(16, 16, "truncate") == (ADMIT_TRUNCATE, 15)
    assert admit(1000, 16, "truncate") == (ADMIT_TRUNCATE, 15)
    assert admit(0, 16) == (ADMIT_OK, 0)
    with pytest.raises(ValueError):
        admit(99, 16, "resize")


def test_assign_slots_fifo_lowest_first():
    assert assign_slots([7, 3, 9], [2, 0]) == [(7, 0), (3, 2)]
    assert assign_slots([], [0, 1]) == []
    assert assign_slots([1, 2], []) == []


def test_expire_arrival_order():
    meta = [(0, 0.0, 5.0), (1, 1.0, None), (2, 2.0, 3.0)]
    expired, remaining = expire(meta, 4.0)
    assert expired == [2] and [r for r, _, _ in remaining] == [0, 1]
    expired, remaining = expire(meta, 5.0)
    assert expired == [0, 2] and [r for r, _, _ in remaining] == [1]


# ------------------------------------------------------- unit: PagedKVCache

class _FakeModel:
    def init_cache(self, batch, context):
        return {"k": np.zeros((2, batch, context, 1, 4))}


def test_paged_cache_alloc_release_reuse():
    c = PagedKVCache(_FakeModel(), 3, 8)
    assert c.data["k"].shape == (2, 3, 8, 1, 4)
    s0, s1 = c.alloc(10), c.alloc(11)
    assert (s0, s1) == (0, 1) and c.n_free == 1
    c.lengths[s0] = 5
    c.release(s0)
    assert c.lengths[s0] == 0 and c.free_slots == [0, 2]
    assert c.alloc(12) == 0                       # lowest free slot reused
    c.alloc(13)
    with pytest.raises(RuntimeError):
        c.alloc(14)                               # pool exhausted
    c.release(1)
    with pytest.raises(AssertionError):
        c.release(1)                              # double release


# ------------------------------------------- unit: block pool (DESIGN.md 15)

def test_blocks_needed_ceil():
    assert blocks_needed(0, 4) == 0
    assert blocks_needed(1, 4) == 1
    assert blocks_needed(4, 4) == 1
    assert blocks_needed(5, 4) == 2


def test_alloc_free_blocks_pure():
    granted, free = alloc_blocks([5, 1, 3], 2)
    assert granted == [1, 3] and free == [5]      # lowest-numbered first
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc_blocks(free, 2)                     # clean failure, no grant
    free = free_blocks(free, granted)
    assert free == [1, 3, 5]                      # conservation
    with pytest.raises(AssertionError):
        free_blocks(free, [3])                    # already free
    with pytest.raises(AssertionError):
        free_blocks([], [2, 2])                   # returned twice


def test_paged_cache_block_lifecycle():
    with pytest.raises(ValueError, match="multiple"):
        PagedKVCache(_FakeModel(), 2, 10, block_size=4)
    c = PagedKVCache(_FakeModel(), 2, 8, block_size=4)
    # pool sized so a full engine can never run short
    assert c.n_blocks == 4 and c.data["k"].shape == (2, 4, 4, 1, 4)
    assert (c.block_table == c.n_blocks).all()    # high sentinel, never -1
    s = c.alloc(7)
    assert c.ensure(s, 3) and c.held_blocks(s) == [0]
    assert not c.ensure(s, 4)                     # 4 positions still 1 block
    assert c.ensure(s, 5) and c.held_blocks(s) == [0, 1]
    assert c.n_free_blocks == 2
    s2 = c.alloc(8)
    c._free_blocks = []                           # hand-shrunk pool
    with pytest.raises(RuntimeError, match="exhausted"):
        c.ensure(s2, 1)                           # a grant must fail loudly
    assert c.held_blocks(s2) == []                # failed grant left nothing
    c._free_blocks = [2, 3]
    c.ensure(s2, 8)
    assert c.held_blocks(s2) == [2, 3] and c.n_free_blocks == 0
    c.release(s2)                                 # returns BOTH its blocks
    assert c.n_free_blocks == 2 and (c.block_table[s2] == c.n_blocks).all()
    c.release(s)
    assert sorted(c._free_blocks) == [0, 1, 2, 3]


@pytest.mark.parametrize("block_size", [4, 0])
def test_paged_cache_counts_blocks_and_live_tokens(block_size):
    """``report`` counts the blocks the slots hold and their live positions
    (lengths plus the positions a dispatch writes) through admit, grow and
    release."""
    from repro.runtime.spans import Recorder
    c = PagedKVCache(_FakeModel(), 3, 16, block_size=block_size)

    def check(writing=0):
        rec = Recorder()
        rec.on = True
        c.report(rec, writing=writing)
        got = {k.name: (k.value, k.of) for k in rec.counters}
        held = sum(len(c.held_blocks(s)) for s in c.owner)
        live = sum(int(c.lengths[s]) for s in c.owner) + writing
        want = {"kv.tokens_live": (live, held * block_size if block_size
                                   else len(c.owner) * c.max_context)}
        if block_size:
            want["kv.blocks_held"] = (held, c.n_blocks)
        assert got == want
        return got

    check()
    a, b = c.alloc(1), c.alloc(2)                 # admit
    check()
    for slot, n in ((a, 3), (b, 5), (a, 4), (a, 9), (b, 16)):
        c.ensure(slot, n)                          # grow, then write
        check(writing=n - int(c.lengths[slot]))
        c.lengths[slot] = n
        check()
    if block_size:
        assert check()["kv.blocks_held"] == (3 + 4, c.n_blocks)
    c.release(a)                                   # release
    check()
    d = c.alloc(3)                                 # reuses a's slot at 0
    c.ensure(d, 2)
    check(writing=2)
    c.release(b)
    c.release(d)
    assert check() == {"kv.tokens_live": (0, 0),
                       **({"kv.blocks_held": (0, c.n_blocks)}
                          if block_size else {})}


def _block_cache_fuzz(seed):
    """Random alloc/ensure/release storm on a block-mode cache: no physical
    block is ever held by two slots, free + held is always the whole pool,
    release returns every granted block, exhaustion raises cleanly."""
    rng = np.random.default_rng(seed)
    n_slots, bs = int(rng.integers(2, 5)), int(rng.integers(1, 4)) * 2
    ctx = bs * int(rng.integers(1, 4))
    c = PagedKVCache(_FakeModel(), n_slots, ctx, block_size=bs)
    # hand-shrink the pool so exhaustion is reachable
    c._free_blocks = c._free_blocks[:max(1, c.n_blocks - bs)]
    pool = set(c._free_blocks)
    live: dict = {}
    for step in range(60):
        op = rng.random()
        if op < 0.4 and c.n_free:                   # admit
            slot = c.alloc(step)
            live[slot] = 0
        elif op < 0.8 and live:                     # grow a random slot
            slot = int(rng.choice(list(live)))
            want = min(ctx, live[slot] + int(rng.integers(1, bs + 2)))
            try:
                c.ensure(slot, want)
                live[slot] = want
            except RuntimeError:
                assert blocks_needed(want, bs) - len(c.held_blocks(slot)) \
                    > c.n_free_blocks              # only fails when short
        elif live:                                  # release
            slot = int(rng.choice(list(live)))
            c.release(slot)
            assert (c.block_table[slot] == c.n_blocks).all()
            del live[slot]
        held = [b for s in live for b in c.held_blocks(s)]
        assert len(held) == len(set(held)), "block double-booked"
        assert set(c._free_blocks) | set(held) == pool, "blocks leaked"
        assert not set(c._free_blocks) & set(held)
    for slot in list(live):
        c.release(slot)
    assert set(c._free_blocks) == pool              # full conservation


@pytest.mark.parametrize("seed", range(10))
def test_block_cache_fuzz_seeded(seed):
    _block_cache_fuzz(3000 + seed)


def test_simulate_block_scarcity_head_waits():
    """Scarce pool: the head of the queue that cannot get its blocks WAITS
    (assignment stops for the step) instead of being skipped by a smaller
    later request — starvation-free under block pressure."""
    # 2 slots, 3 blocks; rid 0 takes 2 blocks and never finishes; rid 1
    # needs 2 (can't fit), rid 2 needs 1 (could fit, must not jump the line)
    log = simulate([(0, 0), (1, 1), (1, 2)], {}, 2, n_blocks=3,
                   blocks_of={0: 2, 1: 2, 2: 1}, horizon=8)
    assigned = [rid for _, a, rid, _ in log if a == "assign"]
    assert assigned == [0]
    # once rid 0 releases (t=3; blocks usable the step after, matching the
    # slot rule), FIFO resumes: rid 1 then rid 2 get their blocks
    log = simulate([(0, 0), (1, 1), (1, 2)], {0: 3}, 2, n_blocks=3,
                   blocks_of={0: 2, 1: 2, 2: 1}, horizon=8)
    assert [(rid, t) for t, a, rid, _ in log if a == "assign"] == \
        [(0, 0), (1, 4), (2, 4)]


# ----------------------------------------------- properties of the oracle

def _check_no_double_booking(log, n_slots):
    active = {}
    for t, action, rid, slot in log:
        if action == "assign":
            assert slot not in active, (t, rid, slot)
            assert 0 <= slot < n_slots
            active[slot] = rid
        elif action == "release":
            assert active.pop(slot) == rid


def _check_fifo(log, arrivals):
    """Assignment order must follow arrival order (FIFO, no skipping)."""
    order = [rid for _, rid in sorted(arrivals)]
    assigned = [rid for _, a, rid, _ in log if a == "assign"]
    assert assigned == [r for r in order if r in set(assigned)]


def _steady_finishes(arrivals, durations, n_slots):
    """Fixed-point finish times: every assigned request runs for its
    duration.  Converges because assignments only unlock monotonically."""
    finishes = {}
    for _ in range(len(arrivals) + 2):
        log = simulate(arrivals, finishes, n_slots,
                       horizon=10 * (len(arrivals) + 1) + 20)
        new = {rid: t + durations[rid]
               for t, a, rid, _ in log if a == "assign"}
        if new == finishes:
            return log, finishes
        finishes = new
    raise AssertionError("fixed point not reached")


def _scheduler_case(rng):
    n = int(rng.integers(1, 10))
    n_slots = int(rng.integers(1, 4))
    arrivals = [(int(rng.integers(0, 10)), rid) for rid in range(n)]
    durations = {rid: int(rng.integers(1, 6)) for rid in range(n)}
    return arrivals, durations, n_slots


def _check_scheduler_props(arrivals, durations, n_slots):
    log, finishes = _steady_finishes(arrivals, durations, n_slots)
    _check_no_double_booking(log, n_slots)
    _check_fifo(log, arrivals)
    # no starvation: when every running request finishes, everyone is served
    assigned = {rid for _, a, rid, _ in log if a == "assign"}
    assert assigned == {rid for _, rid in arrivals}
    released = {rid for _, a, rid, _ in log if a == "release"}
    assert released == assigned


@pytest.mark.parametrize("seed", range(25))
def test_simulate_props_seeded(seed):
    _check_scheduler_props(*_scheduler_case(np.random.default_rng(seed)))


def _deadline_case(rng):
    n = int(rng.integers(2, 8))
    arrivals = [(int(rng.integers(0, 6)), rid) for rid in range(n)]
    deadlines = {rid: int(rng.integers(1, 12)) for rid in range(n)
                 if rng.random() < 0.7}
    return arrivals, deadlines


def _check_deadline_props(arrivals, deadlines):
    # one slot, the first assignee never finishes: every queued request with
    # a deadline must expire, at its deadline or later, never after assign
    log = simulate(arrivals, {}, 1, deadlines=deadlines, horizon=40)
    assigned = {rid for _, a, rid, _ in log if a == "assign"}
    expired = {rid: t for t, a, rid, _ in log if a == "expire"}
    # deadlines are absolute steps: a request that arrives at or after its
    # own deadline expires on arrival, so the slot is taken iff some request
    # is still live when it arrives
    live = any(rid not in deadlines or deadlines[rid] > t
               for t, rid in arrivals)
    assert len(assigned) == int(live)
    assert not (assigned & set(expired))          # running never expires
    for rid, t in expired.items():
        assert t >= deadlines[rid]                # not before its deadline
    for rid in set(deadlines) - assigned:
        assert rid in expired                     # queued + deadline => out
    # expiries at the same step follow arrival order
    arrival_of = {rid: t for t, rid in arrivals}
    by_step: dict = {}
    for t, a, rid, _ in log:
        if a == "expire":
            by_step.setdefault(t, []).append(rid)
    for rids in by_step.values():
        keys = [(arrival_of[r], r) for r in rids]
        assert keys == sorted(keys)


@pytest.mark.parametrize("seed", range(25))
def test_simulate_deadline_props_seeded(seed):
    _check_deadline_props(*_deadline_case(np.random.default_rng(1000 + seed)))


def test_simulate_default_horizon_covers_deadlines():
    """A queued request whose deadline lapses after the last arrival/finish
    must still get its expire event under the DEFAULT horizon (regression:
    the horizon once ignored ``deadlines``, silently dropping late
    expirations)."""
    log = simulate([(0, 0), (0, 1)], {}, 1, deadlines={1: 30})
    assert (30, "expire", 1, None) in log


def test_simulate_never_assigns_expired():
    # rid 0 occupies the slot; rid 1's deadline lapses at t=2; even though
    # the slot frees at t=5 (usable the step after), rid 1 must NOT be
    # assigned — rid 2 gets it
    log = simulate([(0, 0), (1, 1), (1, 2)], {0: 5}, 1, deadlines={1: 2})
    assert (6, "assign", 2, 0) in log
    assert not any(a == "assign" and rid == 1 for _, a, rid, _ in log)


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31))
    def test_simulate_props_hypothesis(seed):
        _check_scheduler_props(*_scheduler_case(np.random.default_rng(seed)))

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31))
    def test_simulate_deadline_props_hypothesis(seed):
        _check_deadline_props(*_deadline_case(np.random.default_rng(seed)))


# ----------------------------------------------- engine vs oracle cross-check

def test_engine_matches_oracle():
    """Replay the live engine's admitted arrivals + observed finish steps
    through the pure simulator: the slot decisions must coincide."""
    import jax
    from repro.nn import Model, get_config
    from repro.runtime.serve import Request, ServeEngine

    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=2, vocab=64, remat=False)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=2, max_context=32, eos_id=-1,
                      prefill_chunk=4)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 3 + 2 * i)
                    .astype(np.int32), max_new_tokens=3 + i % 3)
            for i in range(7)]
    eng.run(reqs)

    arrivals = [(t, rid) for t, a, rid, _ in eng.events if a == "admit"]
    finishes = {rid: t for t, a, rid, _ in eng.events if a == "release"}
    oracle = simulate(arrivals, finishes, eng.max_batch,
                      horizon=eng.stats["steps"] + 1)
    # same assignment sequence (order AND slot ids), same release set
    eng_assigns = [(rid, s) for _, a, rid, s in eng.events if a == "assign"]
    orc_assigns = [(rid, s) for _, a, rid, s in oracle if a == "assign"]
    assert eng_assigns == orc_assigns
    assert {(rid, s) for _, a, rid, s in eng.events if a == "release"} == \
           {(rid, s) for _, a, rid, s in oracle if a == "release"}


# ------------------------------- engine vs oracle fuzz over random traces

@pytest.fixture(scope="module")
def fuzz_model():
    import jax
    from repro.nn import Model, get_config
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=2, vocab=64, remat=False)
    m = Model(cfg)
    return cfg, m.init(jax.random.PRNGKey(0))


def _fuzz_trace(rng, max_context=12):
    """Random arrival/deadline/prompt-length trace: arrival step, prompt
    length (spanning the admission limit so reject/truncate both fire),
    decode budget, optional queue deadline."""
    trace = [dict(rid=rid,
                  t=int(rng.integers(1, 7)),
                  plen=int(rng.integers(1, max_context + 4)),
                  max_new=int(rng.integers(1, 4)),
                  ds=(None if rng.random() < 0.5
                      else int(rng.integers(1, 7))))
             for rid in range(int(rng.integers(2, 8)))]
    policy = "truncate" if rng.random() < 0.5 else "reject"
    return trace, policy, int(rng.integers(1, 3))


def _check_engine_oracle_fuzz(fuzz_model, seed, kv_block_size=0):
    """Drive the live engine on an integer step clock (submit with now=t
    just before step(now=t), so engine step index == oracle time) and
    replay the admitted arrivals + observed finishes through `simulate`:
    assignment sequence, expiries and releases must coincide STEP FOR
    STEP — the fixed-scenario cross-check above, generalized."""
    import jax  # noqa: F401  (engine dispatches)
    from repro.runtime.serve import Request, ServeEngine

    cfg, params = fuzz_model
    rng = np.random.default_rng(seed)
    trace, policy, max_batch = _fuzz_trace(rng)
    eng = ServeEngine(cfg, params, max_batch=max_batch, max_context=12,
                      eos_id=-1, prefill_chunk=5, admission=policy,
                      kv_block_size=kv_block_size)
    by_t = {}
    for it in trace:
        by_t.setdefault(it["t"], []).append(it)
    arrivals, deadlines = [], {}
    t = 0
    while by_t or eng.queue or eng.slots:
        t += 1
        assert t < 500, "fuzz trace did not drain"
        for it in by_t.pop(t, []):
            r = Request(rid=it["rid"],
                        prompt=rng.integers(0, cfg.vocab,
                                            it["plen"]).astype(np.int32),
                        max_new_tokens=it["max_new"], deadline_s=it["ds"])
            if eng.submit(r, now=float(t)) == "queued":
                arrivals.append((t, r.rid))
                if it["ds"] is not None:
                    deadlines[r.rid] = t + it["ds"]
        eng.step(now=float(t))

    finishes = {rid: s for s, a, rid, _ in eng.events if a == "release"}
    oracle = simulate(arrivals, finishes, eng.max_batch,
                      deadlines=deadlines, horizon=t + 1)
    # identical timing, order AND slot ids for assignments...
    assert [(s, rid, sl) for s, a, rid, sl in eng.events if a == "assign"] \
        == [(s, rid, sl) for s, a, rid, sl in oracle if a == "assign"]
    # ...identical expiry decisions (which request, which step)...
    assert {(s, rid) for s, a, rid, _ in eng.events if a == "expire"} == \
        {(s, rid) for s, a, rid, _ in oracle if a == "expire"}
    # ...and the oracle frees the same slot at the same step
    assert {(s, rid, sl) for s, a, rid, sl in eng.events
            if a == "release"} == \
        {(s, rid, sl) for s, a, rid, sl in oracle if a == "release"}
    _check_no_double_booking(
        [(s, a, rid, sl) for s, a, rid, sl in eng.events
         if a in ("assign", "release")], eng.max_batch)
    if kv_block_size:
        # block pool fully conserved after the trace drains, every table
        # row back to the sentinel — release returned every granted block
        assert eng.cache.n_free_blocks == eng.cache.n_blocks
        assert (eng.cache.block_table == eng.cache.n_blocks).all()


@pytest.mark.parametrize("seed", range(4))
def test_engine_oracle_fuzz_seeded(fuzz_model, seed):
    _check_engine_oracle_fuzz(fuzz_model, 1000 + seed)


@pytest.mark.parametrize("seed", range(2))
def test_engine_oracle_fuzz_block_paged(fuzz_model, seed):
    """The block-paged engine's pool can never run short (pool = slots x
    blocks_per_slot), so its scheduling decisions must coincide with the
    slot-only oracle too — plus full block conservation after the drain."""
    _check_engine_oracle_fuzz(fuzz_model, 2000 + seed, kv_block_size=4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2**31))
    def test_engine_oracle_fuzz_hypothesis(fuzz_model, seed):
        _check_engine_oracle_fuzz(fuzz_model, seed)
