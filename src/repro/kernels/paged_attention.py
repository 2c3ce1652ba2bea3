"""Fused block-paged decode attention: softmax(q K^T) V straight from the
KV block pool.

The block-paged serving cache (DESIGN.md 15) stores K/V as a pool of
fixed-size blocks ``(NB, bs, Hkv, D)`` addressed by a per-slot block table.
The gather+dense route first materializes the logical rows (paying the
pool's HBM traffic twice — once to gather, once to attend — over the FULL
``max_context`` row) and then runs a dense masked pass.  This kernel fuses
the two: the block table rides in SMEM via SCALAR PREFETCH (the
``paged_gather`` idiom), each grid step's K/V BlockSpec index map reads
``table[b, j]`` and DMAs exactly that physical block, and online-softmax
state (running max / denominator / accumulator) is carried across the
KV-block grid dimension in VMEM scratch (the ``flash_attention`` idiom).
No gathered intermediate, no full-``max_context`` masked pass.

Bytes actually read scale with per-slot lengths: the wrapper remaps every
grid step past a slot's last needed block to re-index that SAME physical
block (``ops.paged_attention``'s effective table), so Pallas's revisit
optimization skips the redundant DMA, and the kernel body ``pl.when``s the
compute off.  Numerics are unaffected either way — a fully-masked block
scores NEG_INF everywhere, exp underflows to exactly 0.0 in f32, and the
carry update degenerates to an exact no-op — which is also why the kernel
is bit-identical to the scan reference
(``repro.nn.layers.paged_decode_attention_ref``) that skips nothing.

Off-TPU the same call runs in interpret mode (CI covers it); on TPU it
compiles to Mosaic unchanged.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LOG2E = 1.4426950408889634


def pow2_int(delta):
    """Exact ``2.0 ** delta`` for integer-valued f32 ``delta <= 0``.

    Built by bit-assembling the f32 exponent field, so the result is the
    exact power of two for ``delta`` in [-126, 0] and exactly ``0.0`` below
    (the total-rescale wipe; also absorbs the ``NEG_INF - finite`` case
    without int32 overflow).  Shared by the fused kernel and the scan
    reference (``repro.nn.layers.paged_decode_attention_ref``): because the
    correction factor is an exact power of two, ``carry * corr`` never
    rounds, so ``carry * corr + update`` gives the same bits whether or not
    a compiler contracts it into an FMA — the key to cross-compilation
    bit-equality of the two routes (XLA CPU contracts fused mul+add chains
    and strips optimization barriers, so equality cannot be had by asking
    for uncontracted arithmetic; it can by making contraction a no-op).
    """
    k = jnp.maximum(delta, -150.0).astype(jnp.int32)
    bits = (jnp.clip(k, -126, 0) + 127) << 23
    val = jax.lax.bitcast_convert_type(bits.astype(jnp.int32), jnp.float32)
    return jnp.where(k < -126, 0.0, val)


def _attn_kernel(table_ref, clen_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, *, nb, bs, window, scale):
    # grid (B, nb): b = slot row, j = logical KV block (innermost — the
    # (m, l, acc) scratch carries across j and is reset at j == 0)
    b = pl.program_id(0)
    j = pl.program_id(1)
    del table_ref  # consumed by the K/V index maps, not the body

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    clen = clen_ref[b]
    first = j * bs
    run = first < clen
    if window:
        run &= first + bs > clen - window

    @pl.when(run)
    def _step():
        qg = q_ref[0]                                     # (Hkv, G, D)
        k = k_ref[0]                                      # (bs, Hkv, D)
        v = v_ref[0]
        # Base-2 online softmax with the running max quantized to integers:
        # scores are scaled by log2(e) up front, the carried max is
        # ceil()'d, and the rescale factor pow2_int(m_prev - m_new) is an
        # exact power of two — so the carry updates below are immune to
        # FMA contraction and bit-identical to the scan reference however
        # XLA fuses either side.
        s = jnp.einsum("hgd,khd->hgk", qg, k,
                       preferred_element_type=jnp.float32) * scale
        pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        valid = pos < clen
        if window:
            valid &= pos >= clen - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                               # (Hkv, G)
        m_new = jnp.maximum(m_prev, jnp.ceil(s.max(axis=-1)))
        p = jnp.exp2(s - m_new[..., None])
        corr = pow2_int(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
            "hgk,khd->hgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)[..., None]
        o_ref[0] = out.astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_kernel(q, k_pool, v_pool, table, cache_len, *,
                           window: int = 0, interpret: bool = False):
    """q: (B, 1, Hq, D); pools: (NB, bs, Hkv, D); table: (B, nb) int32
    physical block ids (entries must be < NB — the ``ops.paged_attention``
    wrapper clamps the unallocated-sentinel NB and builds the
    revisit-last-block effective table); cache_len: (B,) int32 valid
    lengths.  Returns (B, 1, Hq, D) in q.dtype — bit-identical to
    ``paged_decode_attention_ref`` on the same inputs.

    The GQA grouping (Hq -> (Hkv, G)) happens here, outside the kernel: a
    reshape that splits the sublane dim inside the body is a shape cast
    Mosaic refuses for bf16 at group sizes such as G = 7 (qwen2-0.5b)."""
    B, _, Hq, D = q.shape
    NB, bs, Hkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    nb = table.shape[1]
    G = Hq // Hkv
    table = table.astype(jnp.int32)
    cache_len = cache_len.astype(jnp.int32)
    kv_spec = pl.BlockSpec((1, bs, Hkv, D),
                           lambda b, j, tref, cref: (tref[b, j], 0, 0, 0))
    q_spec = pl.BlockSpec((1, Hkv, G, D),
                          lambda b, j, tref, cref: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        partial(_attn_kernel, nb=nb, bs=bs, window=window,
                scale=LOG2E / np.sqrt(D)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(table, cache_len, q.reshape(B, Hkv, G, D), k_pool, v_pool)
    return out.reshape(B, 1, Hq, D)
