"""Batched hardware-accuracy engine (repro.eval) vs the numpy oracle.

Property-style parity (bit-for-bit, including the exact float accuracy
expression), tuner regressions (batched == serial decisions), backend
demotion, and the shard_map path in a forced-multi-device subprocess.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import find_min_q, quantize_inputs
from repro.core.intmlp import HW_ACTIVATIONS, IntMLP, hardware_accuracy
from repro.core.tuning import tune_parallel, tune_time_multiplexed
from repro.data import pendigits
from repro.eval import BatchedHWEvaluator, Candidate, ha_pct, int32_safe_bound

RNG = np.random.default_rng(7)

STRUCTS = [
    ((8, 6, 4), ("htanh", "hsig")),
    ((8, 5), ("lin",)),                                  # single layer
    ((6, 7, 7, 6, 4), ("htanh", "relu", "satlin", "hsig")),  # deep: dense tail
]


def _rand_mlp(struct, acts, q):
    ws = [RNG.integers(-(1 << (q + 1)), 1 << (q + 1), (a, b)).astype(np.int64)
          for a, b in zip(struct[:-1], struct[1:])]
    bs = [RNG.integers(-(1 << q), 1 << q, (b,)).astype(np.int64)
          for b in struct[1:]]
    return IntMLP(ws, bs, list(acts), q)


def _rand_case(struct, acts, q, m=211):
    mlp = _rand_mlp(struct, acts, q)
    x = RNG.integers(-128, 128, (m, struct[0])).astype(np.int64)
    y = RNG.integers(0, struct[-1], m)
    return mlp, x, y


def _distinct_cands(mlp, k, q, n, with_bias=True):
    n_in, n_o = mlp.weights[k].shape
    pool = [(i, j) for i in range(n_in) for j in range(n_o)]
    RNG.shuffle(pool)
    return [Candidate(k, j, i,
                      int(RNG.integers(-(1 << (q + 1)), 1 << (q + 1))),
                      dbias=int(RNG.integers(-4, 5)) if with_bias else 0)
            for (i, j) in pool[:n]]


def _oracle(mlp, c, x, y):
    m2 = mlp.copy()
    if c.row >= 0:
        m2.weights[c.layer][c.row, c.col] = c.wnew
    m2.biases[c.layer][c.col] += c.dbias
    return m2, hardware_accuracy(m2, x, y)


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
@pytest.mark.parametrize("struct,acts", STRUCTS,
                         ids=[str(s) for s, _ in STRUCTS])
def test_evaluate_parity(struct, acts, backend):
    """evaluate(): every candidate accuracy equals the numpy oracle exactly,
    for every layer, random activations/q, weight+bias mutations."""
    q = int(RNG.integers(3, 9))
    mlp, x, y = _rand_case(struct, acts, q)
    ev = BatchedHWEvaluator(mlp, x, y, backend=backend, chunk=32)
    assert ev.accuracy() == hardware_accuracy(mlp, x, y)
    for k in range(len(mlp.weights)):
        cands = _distinct_cands(mlp, k, q, 19)
        for c, ha in zip(cands, ev.evaluate(cands)):
            assert ha == _oracle(mlp, c, x, y)[1], (k, c)


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_prefix_and_chain_parity(backend):
    """evaluate_prefix / evaluate_chain reproduce cumulative application and
    the serial greedy accept/reject chain bit-for-bit."""
    for struct, acts in STRUCTS:
        q = int(RNG.integers(3, 8))
        mlp, x, y = _rand_case(struct, acts, q)
        ev = BatchedHWEvaluator(mlp, x, y, backend=backend, chunk=32)
        for k in range(len(mlp.weights)):
            cands = _distinct_cands(mlp, k, q, 17)
            m2 = mlp.copy()
            for c, ha in zip(cands[:7], ev.evaluate_prefix(cands[:7])):
                m2.weights[k][c.row, c.col] = c.wnew
                m2.biases[k][c.col] += c.dbias
                assert ha == hardware_accuracy(m2, x, y), ("prefix", k)
            bha = ev.accuracy()
            flags, has = ev.evaluate_chain(cands, bha)
            m2, best = mlp.copy(), bha
            for c, flag, ha in zip(cands, flags, has):
                old_w = int(m2.weights[k][c.row, c.col])
                old_b = int(m2.biases[k][c.col])
                m2.weights[k][c.row, c.col] = c.wnew
                m2.biases[k][c.col] += c.dbias
                ref = hardware_accuracy(m2, x, y)
                assert ha == ref, ("chain", k, c)
                if ref >= best:
                    assert flag
                    best = ref
                else:
                    assert not flag
                    m2.weights[k][c.row, c.col] = old_w
                    m2.biases[k][c.col] = old_b


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_commit_keeps_caches_exact(backend):
    """Random commit chains: layer-prefix caches stay bit-exact (accuracy()
    equals a fresh oracle evaluation after every commit)."""
    struct, acts = (8, 10, 6, 5), ("htanh", "satlin", "hsig")
    q = 5
    mlp, x, y = _rand_case(struct, acts, q)
    ev = BatchedHWEvaluator(mlp, x, y, backend=backend, chunk=16)
    for _ in range(15):
        k = int(RNG.integers(0, len(mlp.weights)))
        c = _distinct_cands(ev.mlp, k, q, 1)[0]
        ev.commit(c)
        assert ev.accuracy() == hardware_accuracy(ev.mlp, x, y)
        probe = _distinct_cands(ev.mlp, k, q, 3)
        base = ev.mlp.copy()
        for cc, ha in zip(probe, ev.evaluate(probe)):
            m2 = base.copy()
            if cc.row >= 0:
                m2.weights[k][cc.row, cc.col] = cc.wnew
            m2.biases[k][cc.col] += cc.dbias
            assert ha == hardware_accuracy(m2, x, y)
    ev.commit_many(_distinct_cands(ev.mlp, 0, q, 6))
    assert ev.accuracy() == hardware_accuracy(ev.mlp, x, y)


def test_random_activation_sweep():
    """Every hardware activation appears in randomized parity sweeps."""
    for trial in range(6):
        n_layers = int(RNG.integers(1, 4))
        struct = tuple(int(RNG.integers(3, 9)) for _ in range(n_layers + 1))
        acts = [str(RNG.choice(HW_ACTIVATIONS)) for _ in range(n_layers)]
        q = int(RNG.integers(2, 8))
        mlp, x, y = _rand_case(struct, acts, q, m=97)
        ev = BatchedHWEvaluator(mlp, x, y, backend="jnp", chunk=16)
        k = int(RNG.integers(0, n_layers))
        for c, ha in zip(*(lambda cs: (cs, ev.evaluate(cs)))(
                _distinct_cands(mlp, k, q, 9))):
            assert ha == _oracle(mlp, c, x, y)[1]


def test_pallas_backend_interpret():
    """The csd_matvec-backed dense tail (interpret mode off-TPU) stays exact
    on a deep network where the kernel path is actually exercised."""
    struct, acts = (8, 10, 6, 5), ("htanh", "satlin", "hsig")
    mlp, x, y = _rand_case(struct, acts, 5, m=64)
    ev = BatchedHWEvaluator(mlp, x, y, backend="pallas", chunk=8)
    cands = _distinct_cands(mlp, 0, 5, 8, with_bias=False)
    for c, ha in zip(cands, ev.evaluate(cands)):
        assert ha == _oracle(mlp, c, x, y)[1]


def test_int32_demotion_to_numpy():
    """Weights past the int32 accumulator bound demote to the int64 numpy
    backend (with a warning) and stay exact."""
    ws = [np.full((8, 6), 1 << 24, dtype=np.int64),
          np.full((6, 4), 3, dtype=np.int64)]
    bs = [np.zeros(6, np.int64), np.zeros(4, np.int64)]
    mlp = IntMLP(ws, bs, ["htanh", "hsig"], q=20)
    assert not int32_safe_bound(mlp)
    x = RNG.integers(-128, 128, (50, 8)).astype(np.int64)
    y = RNG.integers(0, 4, 50)
    with pytest.warns(UserWarning, match="numpy"):
        ev = BatchedHWEvaluator(mlp, x, y, backend="jnp")
    assert ev.backend == "numpy"
    c = Candidate(0, 2, 3, 12345)
    assert ev.evaluate([c])[0] == _oracle(mlp, c, x, y)[1]


def test_chain_int64_fallback_on_deep_tail():
    """A deep-tail layer past the int32 bound must keep the numpy chain in
    int64 (regression: _spec_safe only bounded layers k and k+1)."""
    ws = [RNG.integers(-8, 8, (6, 5)).astype(np.int64),
          RNG.integers(-8, 8, (5, 5)).astype(np.int64),
          RNG.integers(1 << 21, 1 << 22, (5, 4)).astype(np.int64)]
    bs = [np.zeros(5, np.int64), np.zeros(5, np.int64), np.zeros(4, np.int64)]
    mlp = IntMLP(ws, bs, ["htanh", "satlin", "lin"], q=4)
    assert not int32_safe_bound(mlp)
    x = RNG.integers(-128, 128, (73, 6)).astype(np.int64)
    y = RNG.integers(0, 4, 73)
    ev = BatchedHWEvaluator(mlp, x, y, backend="numpy")
    cands = _distinct_cands(mlp, 0, 4, 11, with_bias=False)
    flags, has = ev.evaluate_chain(cands, ev.accuracy())
    m2, best = mlp.copy(), ev.accuracy()
    for c, flag, ha in zip(cands, flags, has):
        old = int(m2.weights[0][c.row, c.col])
        m2.weights[0][c.row, c.col] = c.wnew
        ref = hardware_accuracy(m2, x, y)
        assert ha == ref
        if ref >= best:
            assert flag
            best = ref
        else:
            assert not flag
            m2.weights[0][c.row, c.col] = old


def test_tune_tm_ann_scope_multilayer():
    """scope='ann' groups span layers: the batched tuner must still match the
    serial one on a multi-layer net (regression: cross-layer chunks)."""
    mlp, x, y = _rand_case((8, 6, 4), ("htanh", "hsig"), 4, m=173)
    serial = tune_time_multiplexed(mlp, x, y, scope="ann", max_sweeps=1,
                                   engine="serial")
    batched = tune_time_multiplexed(mlp, x, y, scope="ann", max_sweeps=1,
                                    engine="batched")
    _assert_same_result(serial, batched)


def test_composed_batch_guards():
    mlp, x, y = _rand_case((8, 6, 4), ("htanh", "hsig"), 4, m=40)
    ev = BatchedHWEvaluator(mlp, x, y, backend="numpy")
    dup = [Candidate(0, 1, 2, 5), Candidate(0, 1, 2, 7)]
    with pytest.raises(ValueError, match="distinct"):
        ev.evaluate_prefix(dup)
    with pytest.raises(ValueError, match="layer"):
        ev.evaluate([Candidate(0, 1, 2, 5), Candidate(1, 1, 2, 5)])
    with pytest.raises(ValueError, match="greedy invariant"):
        ev.evaluate_chain([Candidate(0, 1, 2, 5)], ev.accuracy() + 1.0)


def test_ha_pct_matches_oracle_expression():
    # same float64 ops as 100.0 * np.mean(hits): greedy >= thresholds agree
    for n, m in [(1234, 2248), (0, 7), (7, 7), (999, 3000)]:
        hits = np.zeros(m, bool)
        hits[:n] = True
        assert ha_pct(n, m) == 100.0 * float(np.mean(hits))


# ---------------------------------------------------------------------------
# Tuner regressions: batched decisions == serial decisions, bit for bit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pendigits_quantized():
    """A trained + min-q-quantized pendigits MLP (paper pipeline front end)."""
    from repro.train.zaal import TrainConfig, train
    ds = pendigits.load()
    (xtr, ytr), (xval, yval) = ds.validation_split()
    cfg = TrainConfig(structure=(16, 10), epochs=20, seed=5)
    res = train(cfg, pendigits.to_unit(xtr), ytr,
                pendigits.to_unit(xval), yval)
    x_val_int = quantize_inputs(pendigits.to_unit(xval))
    qr = find_min_q(res.weights, res.biases, ("hsig",), x_val_int, yval)
    # a validation subset keeps the serial reference fast; both engines see
    # the identical split so decision parity is unaffected
    return qr.mlp, x_val_int[:1024], yval[:1024]


def _assert_same_result(a, b):
    assert a.bha == b.bha
    assert a.initial_ha == b.initial_ha
    assert a.replacements == b.replacements
    assert a.sweeps == b.sweeps
    assert a.log == b.log
    for wa, wb in zip(a.mlp.weights, b.mlp.weights):
        np.testing.assert_array_equal(wa, wb)
    for ba, bb in zip(a.mlp.biases, b.mlp.biases):
        np.testing.assert_array_equal(ba, bb)


def test_tune_parallel_batched_equals_serial(pendigits_quantized):
    mlp, x, y = pendigits_quantized
    serial = tune_parallel(mlp, x, y, max_sweeps=2, engine="serial")
    for backend in ("jnp", "numpy"):
        batched = tune_parallel(mlp, x, y, max_sweeps=2, engine="batched",
                                backend=backend)
        _assert_same_result(serial, batched)
        assert batched.stats["commits"] == batched.replacements


@pytest.mark.parametrize("scope", ["neuron", "ann"])
def test_tune_tm_batched_equals_serial(pendigits_quantized, scope):
    mlp, x, y = pendigits_quantized
    serial = tune_time_multiplexed(mlp, x, y, scope=scope, max_sweeps=1,
                                   engine="serial")
    batched = tune_time_multiplexed(mlp, x, y, scope=scope, max_sweeps=1,
                                    engine="batched")
    _assert_same_result(serial, batched)


# ---------------------------------------------------------------------------
# shard_map data parallelism (forced host devices in a subprocess)
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import numpy as np, jax
assert jax.device_count() == 4, jax.device_count()
from repro.core.intmlp import IntMLP, hardware_accuracy
from repro.eval import BatchedHWEvaluator, Candidate
rng = np.random.default_rng(3)
ws = [rng.integers(-40, 40, (8, 6)).astype(np.int64),
      rng.integers(-40, 40, (6, 4)).astype(np.int64)]
bs = [rng.integers(-20, 20, (6,)).astype(np.int64),
      rng.integers(-20, 20, (4,)).astype(np.int64)]
mlp = IntMLP(ws, bs, ["htanh", "hsig"], 5)
M = 203   # not divisible by 4: exercises row padding
x = rng.integers(-128, 128, (M, 8)).astype(np.int64)
y = rng.integers(0, 4, M)
ev = BatchedHWEvaluator(mlp, x, y, backend="jnp", shard=True, chunk=8)
assert ev._n_shards == 4 and ev._mesh is not None
assert ev.accuracy() == hardware_accuracy(mlp, x, y)
for k in (0, 1):
    cands = [Candidate(k, int(rng.integers(0, ws[k].shape[1])),
                       int(rng.integers(0, ws[k].shape[0])),
                       int(rng.integers(-40, 40)),
                       dbias=int(rng.integers(-3, 4))) for _ in range(9)]
    for c, ha in zip(cands, ev.evaluate(cands)):
        m2 = mlp.copy()
        m2.weights[k][c.row, c.col] = c.wnew
        m2.biases[k][c.col] += c.dbias
        assert ha == hardware_accuracy(m2, x, y), (k, c)
flags, has = ev.evaluate_chain(
    [Candidate(0, 2, 3, 17), Candidate(0, 4, 1, -9)], ev.accuracy())
ev.commit(Candidate(0, 2, 3, 17))
assert ev.accuracy() == hardware_accuracy(ev.mlp, x, y)
print("SHARD-OK")
"""


def test_shard_map_data_parallel():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD-OK" in out.stdout
