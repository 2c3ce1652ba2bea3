"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import (csd_expand, csd_expand_stack, csd_matvec,
                           csd_qsweep, qmatmul, quantize_pot)
from repro.kernels import ref as kref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("M,K,N", [
    (256, 512, 256), (128, 1024, 128), (8, 512, 256),
    (300, 700, 130),              # non-divisible: exercises padding
    (1024, 512, 512),
])
def test_qmatmul_exact(M, K, N):
    x = RNG.integers(-128, 128, (M, K)).astype(np.int8)
    w = RNG.integers(-128, 128, (K, N)).astype(np.int8)
    e = RNG.integers(0, 14, (N,)).astype(np.int32)
    y = qmatmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(e))
    yr = kref.qmatmul_ref(jnp.asarray(x), jnp.asarray(w), jnp.asarray(e))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_dtypes(out_dtype):
    x = RNG.integers(-128, 128, (256, 512)).astype(np.int8)
    w = RNG.integers(-128, 128, (512, 256)).astype(np.int8)
    e = RNG.integers(0, 8, (256,)).astype(np.int32)
    from repro.kernels.qmatmul import qmatmul_kernel
    y = qmatmul_kernel(jnp.asarray(x), jnp.asarray(w), jnp.asarray(e),
                       bm=256, bn=256, bk=512, out_dtype=out_dtype,
                       interpret=True)
    assert y.dtype == out_dtype


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10**4))
def test_qmatmul_property(seed):
    rng = np.random.default_rng(seed)
    M, K, N = rng.integers(1, 64), rng.integers(1, 600), rng.integers(1, 300)
    x = rng.integers(-128, 128, (M, K)).astype(np.int8)
    w = rng.integers(-128, 128, (K, N)).astype(np.int8)
    e = rng.integers(-4, 14, (N,)).astype(np.int32)
    y = qmatmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(e))
    yr = kref.qmatmul_ref(jnp.asarray(x), jnp.asarray(w), jnp.asarray(e))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("M,K,N", [(128, 16, 128), (64, 40, 30),
                                   (200, 16, 10)])
def test_csd_matvec_exact(M, K, N):
    W = RNG.integers(-255, 256, (K, N))
    x = RNG.integers(-128, 128, (M, K)).astype(np.int32)
    y = csd_matvec(jnp.asarray(x), w_int=W)
    expect = np.asarray(x, np.int64) @ np.asarray(W, np.int64)
    np.testing.assert_array_equal(np.asarray(y, np.int64), expect)


def test_csd_matvec_matches_ref_kernel_oracle():
    W = RNG.integers(-100, 100, (16, 24))
    planes = jnp.asarray(csd_expand(W))
    x = jnp.asarray(RNG.integers(-128, 128, (32, 16)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(csd_matvec(x, planes=planes)),
        np.asarray(kref.csd_matvec_ref(x, planes)))


def test_csd_planes_are_valid_csd():
    W = RNG.integers(-255, 256, (8, 8))
    planes = csd_expand(W)
    assert set(np.unique(planes)) <= {-1, 0, 1}
    # adjacent digit planes never both nonzero at the same position
    both = (planes[:-1] != 0) & (planes[1:] != 0)
    assert not both.any()
    # reconstruction
    recon = sum((planes[d].astype(np.int64) << d)
                for d in range(planes.shape[0]))
    np.testing.assert_array_equal(recon, W)


def test_csd_expand_matches_scalar_recoder():
    """The array-backed expansion (repro.kernels public path) is
    bit-identical to stacking the scalar to_csd digit lists."""
    from repro.core import csd as C
    W = RNG.integers(-255, 256, (16, 10))
    planes = csd_expand(W)
    digits = [[C.to_csd(int(v)) for v in row] for row in W]
    D = max((len(d) for row in digits for d in row), default=1)
    ref = np.zeros((max(D, 1),) + W.shape, np.int8)
    for i, row in enumerate(digits):
        for j, ds in enumerate(row):
            ref[:len(ds), i, j] = ds
    np.testing.assert_array_equal(planes, ref)
    # depth pads with zero planes (the qsweep stacking contract)
    deeper = csd_expand(W, depth=planes.shape[0] + 3)
    np.testing.assert_array_equal(deeper[:planes.shape[0]], planes)
    assert not deeper[planes.shape[0]:].any()


def test_csd_expand_old_import_path_removed():
    # the PR 3 deprecation shim is gone: the kernel module no longer
    # exports csd_expand at all — repro.kernels is the only import path
    # the package re-exports the csd_matvec *function* under the module's
    # name, so fetch the module itself from the import system
    import importlib
    kernel_mod = importlib.import_module("repro.kernels.csd_matvec")
    assert not hasattr(kernel_mod, "csd_expand")
    assert "csd_expand" not in kernel_mod.__all__
    with pytest.raises(ImportError):
        from repro.kernels.csd_matvec import csd_expand  # noqa: F401


@pytest.mark.parametrize("Q,M,K,N", [(4, 128, 16, 128), (3, 70, 16, 10),
                                     (1, 200, 40, 30)])
def test_csd_qsweep_exact(Q, M, K, N):
    """Stacked digit-plane matvec == per-network int64 matmul, including
    padding shapes and per-network plane depths (DESIGN.md 11.4)."""
    Ws = [RNG.integers(-(1 << (3 + 2 * q)), 1 << (3 + 2 * q), (K, N))
          for q in range(Q)]
    planes = csd_expand_stack(Ws)
    # stacking contract: per-network planes zero-padded to the max depth
    assert planes.shape[:2] == (Q, max(csd_expand(w).shape[0] for w in Ws))
    x = RNG.integers(-128, 128, (Q, M, K)).astype(np.int32)
    y = np.asarray(csd_qsweep(jnp.asarray(x), jnp.asarray(planes)))
    for q in range(Q):
        np.testing.assert_array_equal(
            y[q].astype(np.int64),
            x[q].astype(np.int64) @ np.asarray(Ws[q], np.int64))


def test_csd_qsweep_matches_per_q_dispatch():
    Q, M, K, N = 3, 64, 12, 20
    Ws = [RNG.integers(-255, 256, (K, N)) for _ in range(Q)]
    planes = csd_expand_stack(Ws)
    x = RNG.integers(-128, 128, (Q, M, K)).astype(np.int32)
    y = np.asarray(csd_qsweep(jnp.asarray(x), jnp.asarray(planes)))
    for q in range(Q):
        np.testing.assert_array_equal(
            y[q], np.asarray(csd_matvec(jnp.asarray(x[q]), w_int=Ws[q])))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10**4))
def test_quantize_pot_property(seed):
    rng = np.random.default_rng(seed)
    w = rng.normal(0, rng.uniform(1e-3, 10), (64, 32)).astype(np.float32)
    wq, e = quantize_pot(jnp.asarray(w))
    assert wq.dtype == jnp.int8
    recon = np.asarray(wq, np.float32) * np.exp2(-np.asarray(e))[None, :]
    err = np.abs(recon - w).max()
    # PoT grid step = 2^-e; per-channel max error <= half step
    step = np.exp2(-np.asarray(e, np.float32))
    assert err <= step.max() * 0.5 + 1e-6


# ---------------------------------------------------------------------------
# flash attention kernel vs exact oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,D,causal,window", [
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 256, 256, 8, 8, 128, True, 0),
    (2, 100, 300, 4, 1, 64, True, 0),    # padding + cross-length causal
    (1, 256, 256, 4, 2, 64, True, 64),   # local window
    (2, 64, 200, 4, 4, 32, False, 0),    # non-causal (cross attention)
])
def test_flash_attention_vs_ref(B, Sq, Skv, Hq, Hkv, D, causal, window):
    from repro.kernels import flash_attention
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(0, 1, (B, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, Skv, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, Skv, Hkv, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=64, bk=64)
    ref = kref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_chunked():
    """The jnp chunked attention in the model and the Pallas kernel agree."""
    from repro.kernels import flash_attention
    from repro.nn.layers import chunked_attention
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(0, 1, (2, 96, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 96, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 96, 2, 32)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, bq=32, bk=32)
    b = chunked_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,W", [(2, 64, 128), (1, 100, 70), (2, 256, 256)])
def test_linear_scan_vs_ref(B, S, W):
    """Fused RG-LRU recurrence kernel == lax.scan oracle."""
    from repro.kernels.linear_scan import linear_scan, linear_scan_ref
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.uniform(0.7, 1.0, (B, S, W)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 0.1, (B, S, W)), jnp.float32)
    np.testing.assert_allclose(np.asarray(linear_scan(a, x, bt=32, bw=64)),
                               np.asarray(linear_scan_ref(a, x)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,nb,bs,H,D", [(3, 3, 4, 2, 8), (1, 2, 8, 1, 4),
                                         (4, 1, 16, 2, 4)])
def test_paged_gather_vs_take(B, nb, bs, H, D):
    """Scalar-prefetch block-table gather (interpret mode) == the jnp.take
    reference route, including out-of-range-HIGH sentinel entries (both
    routes clamp to the last physical block; the garbage those rows carry
    is masked downstream by position masks — bit-equality here is on the
    raw gathered rows)."""
    from repro.kernels import paged_gather
    from repro.nn.layers import gather_block_rows
    rng = np.random.default_rng(7)
    NB = 2 * B * nb + 1
    leaf = jnp.asarray(rng.normal(0, 1, (NB, bs, H, D)), jnp.float32)
    table = rng.permutation(NB)[:B * nb].astype(np.int32).reshape(B, nb)
    table[0, -1] = NB                       # unallocated-block sentinel
    out = paged_gather(leaf, jnp.asarray(table), interpret=True)
    ref = jnp.take(leaf, jnp.minimum(jnp.asarray(table), NB - 1),
                   axis=0)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref))
    # and the model-side wrapper reshapes to logical rows on both routes
    a = gather_block_rows(leaf, jnp.asarray(table), engine="take")
    assert a.shape == (B, nb * bs, H, D)
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(ref).reshape(B, nb * bs, H, D))
