"""Operations and bytes the served decoder needs, counted from shapes.

Counts are of the work the algorithm needs, never of the traffic a route
happens to make: a prompt chunk attends to the positions before it and no
further, a decode row attends to its own live context, and the output
projection runs once per token that is sampled.  So no implementation can
push a share computed from them past 100%.
"""
from __future__ import annotations


def matmul_flops_per_token(s: dict) -> float:
    """2 * multiply-adds of every per-layer projection for one token."""
    d, f, hq, hkv, hd = s["d"], s["f"], s["hq"], s["hkv"], s["hd"]
    per_layer = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * f
    return 2.0 * s["L"] * per_layer


def head_flops(s: dict) -> float:
    """The output projection for one sampled token."""
    return 2.0 * s["d"] * s["V"]


def attention_flops(s: dict, keys: float) -> float:
    """QK^T and PV over ``keys`` (query, key) pairs, every layer."""
    return 4.0 * s["L"] * s["hq"] * s["hd"] * keys


def chunk_keys(offset: int, n: int) -> int:
    """(query, key) pairs of a causal chunk of ``n`` tokens at ``offset``."""
    return n * offset + n * (n + 1) // 2


def prefill_flops(s: dict, rows) -> float:
    """rows: (offset, n, done) chunks ingested; a chunk that completes its
    prompt (``done``) needs one output projection for the first token."""
    n = sum(r[1] for r in rows)
    keys = sum(chunk_keys(r[0], r[1]) for r in rows)
    return (n * matmul_flops_per_token(s) + attention_flops(s, keys)
            + sum(bool(r[2]) for r in rows) * head_flops(s))


def decode_flops(s: dict, ctx) -> float:
    """One decode step; ctx: context length (keys) of every active row."""
    return len(ctx) * (matmul_flops_per_token(s) + head_flops(s)) \
        + attention_flops(s, sum(ctx))


def paged_attention_work(s: dict, ctx, itemsize: int = 2):
    """(flops, bytes) one decode step's attention needs, every layer: K and
    V of each active row's live context, plus its query and output."""
    keys = sum(ctx)
    flops = attention_flops(s, keys)
    kv = 2.0 * keys * s["hkv"] * s["hd"] * itemsize
    qo = 2.0 * len(ctx) * s["hq"] * s["hd"] * itemsize
    return flops, s["L"] * (kv + qo)
