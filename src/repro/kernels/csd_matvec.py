"""Pallas TPU kernels: CSD shift-add CAVM evaluation (bit-exact ASIC datapath).

The paper's multiplierless designs (Section V) evaluate y = C @ x as planes of
+-shifted adds over the CSD digits of C.  These kernels execute exactly that
decomposition — weight matrix expanded into D digit planes p_d in {-1,0,1},
y = sum_d (x @ p_d) << d — so the framework can simulate the synthesized
hardware's integer arithmetic at tensor speed (e.g. hardware-accuracy
evaluation inside the tuning loops for large validation sets).

Two kernels:

* ``csd_matvec_kernel`` — one network: (M, K) activations x (D, K, N) planes.
* ``csd_qsweep_kernel`` — the sweep mode (DESIGN.md 11.4): Q stacked networks
  (e.g. the same float weights quantized at Q candidate q levels), activations
  (Q, M, K) x planes (Q, D, K, N), one dispatch for every q level — the
  digit-plane twin of the sweep engine's stacked ``dot_general`` forwards.

On a real TPU the MXU int8 path (qmatmul) beats digit planes for dense math;
these kernels' value is bit-exact *hardware simulation*, not TPU roofline
(DESIGN.md 2.4).  Grid: (M/bm, N/bn) (+ a leading Q dimension for the sweep
kernel); the D digit planes are accumulated inside the kernel body with
shifts applied as exact integer scaling.

Operand types.  Mosaic has no int32 x int32 matmul, so each plane pass runs
on the MXU in bfloat16 with a float32 accumulator, and only the shift-add
across planes is int32.  This is exact: activations are the datapath's
signed 8-bit values (|x| <= 128) and digits are in {-1, 0, 1}, both exact in
bfloat16; every product is an integer of magnitude <= 128 and every partial
sum over K an integer of magnitude <= 128 * K < 2^24 (``_MAX_K``), exact in
float32.  The per-plane float result therefore converts to the int32 it
would have been, and ``sum_d (.) << d`` is the same int32 arithmetic as
before — exact whenever the network's accumulator bound
(``repro.eval.batched.csd_net_accum_bound``) is below 2^31.

The digit-plane expansion itself lives at :func:`repro.kernels.csd_expand`
(``repro.kernels.ops``), backed by the whole-array CSD recoder
(DESIGN.md 11.1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["csd_matvec_kernel", "csd_matvec", "csd_qsweep_kernel"]

# |x| <= 2^7 per product, so K * 2^7 < 2^24 keeps every float32 partial
# sum of a plane pass an exact integer
_MAX_K = 1 << 17


def _plane_pass(x, plane):
    """One digit plane on the MXU: (bm, K) bf16 x (K, bn) bf16 -> exact int32
    (see the module docstring for why the float32 accumulator is exact)."""
    y = jax.lax.dot_general(x, plane, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y.astype(jnp.int32)


def _kernel(x_ref, p_ref, o_ref, *, n_digits: int):
    x = x_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for d in range(n_digits):        # static unroll: one MXU pass per plane
        acc += _plane_pass(x, p_ref[d]) << d
    o_ref[...] = acc


def csd_matvec_kernel(x_int, planes, *, bm: int = 128, bn: int = 128,
                      interpret: bool = False):
    """y[b, j] = sum_d sum_k (x[b,k] * planes[d,k,j]) << d   (exact int32).

    x_int: (M, K) integer activations in the signed 8-bit range; planes:
    (D, K, N) digits in {-1, 0, 1}.  M, N must tile by (bm, bn); K is kept
    whole per block (layer K is small for the paper's MLPs).
    """
    M, K = x_int.shape
    D, K2, N = planes.shape
    assert K == K2 and M % bm == 0 and N % bn == 0, (x_int.shape, planes.shape)
    assert K < _MAX_K, K
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_kernel, n_digits=D),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda m, n: (m, 0)),
            pl.BlockSpec((D, K, bn), lambda m, n: (0, 0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(x_int.astype(jnp.bfloat16), planes.astype(jnp.bfloat16))


csd_matvec = csd_matvec_kernel


def _qsweep_kernel(x_ref, p_ref, o_ref, *, n_digits: int):
    x = x_ref[0]
    acc = jnp.zeros(o_ref.shape[1:], jnp.int32)
    for d in range(n_digits):        # static unroll: one MXU pass per plane
        acc += _plane_pass(x, p_ref[0, d]) << d
    o_ref[0] = acc


def csd_qsweep_kernel(x_int, planes, *, bm: int = 128, bn: int = 128,
                      interpret: bool = False):
    """y[q, b, j] = sum_d sum_k (x[q,b,k] * planes[q,d,k,j]) << d (int32).

    The sweep-mode digit-plane matvec (DESIGN.md 11.4): x_int is a (Q, M, K)
    stack of per-network signed 8-bit activations, planes a (Q, D, K, N)
    stack of per-network CSD digit planes (each network's planes zero-padded
    to the common depth D).  One grid dimension per network: every q level
    of a sweep runs through the shift-add datapath in a single dispatch.
    """
    Q, M, K = x_int.shape
    Q2, D, K2, N = planes.shape
    assert Q == Q2 and K == K2 and M % bm == 0 and N % bn == 0, \
        (x_int.shape, planes.shape)
    assert K < _MAX_K, K
    grid = (Q, M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_qsweep_kernel, n_digits=D),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, K), lambda q, m, n: (q, m, 0)),
            pl.BlockSpec((1, D, K, bn), lambda q, m, n: (q, 0, 0, n)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda q, m, n: (q, m, n)),
        out_shape=jax.ShapeDtypeStruct((Q, M, N), jnp.int32),
        interpret=interpret,
    )(x_int.astype(jnp.bfloat16), planes.astype(jnp.bfloat16))
