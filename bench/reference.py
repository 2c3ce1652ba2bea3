"""Plain float32 reference of the served decoder, and the comparison that
decides ``correct``.

The forward pass follows the published Qwen2 / InternLM2 description: token
embedding, then per layer RMSNorm -> GQA self-attention with rotary
positions (rotate-half form) and optional QKV bias -> residual -> RMSNorm ->
SwiGLU MLP -> residual, then a final RMSNorm and an output projection.  It
imports nothing of the program: the weights come from ``bench.weights`` and
the seed, in the engine's layout, where a norm's weight is stored as its
offset from 1.  Every matmul runs at HIGHEST precision, with no cache and no
batching, one request at a time, attention in blocks of queries so that a
16k-token row fits.  On a cell of several chips the weights come placed
over its mesh (``bench.weights.shardings``) and XLA partitions the same
jitted pass from that placement: each chip computes its share of the heads
and FFN columns, and the row-parallel products are summed across chips, so
no chip holds the whole tree and the precision is the same as on one.

The comparison: for each sampled request, run the prompt followed by its
served tokens once, and read at every served position how far the served
token's logit lies below the reference's best logit there.  The widest such
gap over the sample is the number compared with the cell's limit.  A greedy
server that rounds differently picks near-ties, which sit a rounding error
below the best; a wrong token sits far below.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block
N_SERVED = 512         # served positions compared per request (padded)
S_BUCKET = 1024        # sequence lengths are padded up to a multiple of this


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w_offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w_offset)


def _rope(x, theta):
    """x: (S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA attention. q: (S, Hq, D); k, v: (S, Hkv, D)."""
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, Hkv, G, D)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) / np.sqrt(D)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qb))
    return out.reshape(S, Hq * D)


def _layer(x, p, *, hq, hkv, hd, theta, eps):
    S = x.shape[0]
    a = p["attn"]
    h = _rms(x, p["ln1"], eps)
    q, k, v = _mm(h, a["wq"]), _mm(h, a["wk"]), _mm(h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(S, hq, hd), theta)
    k = _rope(k.reshape(S, hkv, hd), theta)
    v = v.reshape(S, hkv, hd)
    x = x + _mm(_attention(q, k, v), a["wo"])
    h = _rms(x, p["ln2"], eps)
    m = p["mlp"]
    return x + _mm(jax.nn.silu(_mm(h, m["wg"])) * _mm(h, m["wu"]), m["wd"])


@partial(jax.jit, static_argnames=("hq", "hkv", "hd", "theta", "eps"))
def served_gaps(w, tokens, idx, served, *, hq, hkv, hd, theta, eps):
    """tokens: (S,) prompt then served tokens, padded; idx: (N,) positions
    whose next-token logits are read; served: (N,) the tokens served there.
    Returns (N,) best logit minus the served token's logit."""
    x = w["embed"][tokens]

    def body(x, p):
        return _layer(x, p, hq=hq, hkv=hkv, hd=hd, theta=theta, eps=eps), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    h = _rms(x[idx], w["final_norm"], eps)
    logits = _mm(h, w["lm_head"])
    return logits.max(-1) - jnp.take_along_axis(
        logits, served[:, None], axis=1)[:, 0]


def request_gaps(w, cfg, prompt, out_tokens) -> np.ndarray:
    """Gaps of every served token of one request (out_tokens[i] was served
    after prompt + out_tokens[:i])."""
    prompt = np.asarray(prompt, np.int32)
    out = np.asarray(out_tokens, np.int32)
    n = len(out)
    seq = np.concatenate([prompt, out[:-1]])
    S = -(-len(seq) // S_BUCKET) * S_BUCKET
    toks = np.zeros(S, np.int32)
    toks[:len(seq)] = seq
    idx = np.zeros(N_SERVED, np.int32)
    idx[:n] = len(prompt) - 1 + np.arange(n)
    served = np.zeros(N_SERVED, np.int32)
    served[:n] = out
    g = served_gaps(w, toks, idx, served, hq=cfg.n_heads,
                    hkv=cfg.n_kv_heads, hd=cfg.head_dim_,
                    theta=float(cfg.rope_theta), eps=float(cfg.norm_eps))
    return np.asarray(g)[:n]


def sample(done: list, rng: np.random.Generator, *, min_tokens: int = 384,
           max_requests: int = 12) -> list:
    """Requests to compare, drawn from the seed: the longest finished one
    first, then others at random until ``min_tokens`` served tokens."""
    if not done:
        return []
    size = [len(r.prompt) + len(r.out_tokens) for r in done]
    first = int(np.argmax(size))
    rest = [i for i in rng.permutation(len(done)) if i != first]
    picked, n = [done[first]], len(done[first].out_tokens)
    for i in rest:
        if n >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(done[i])
        n += len(done[i].out_tokens)
    return picked


def compare(w, cfg, picked: list) -> dict:
    """The readings the cell's limits hold: the widest gap, and how many
    tokens and requests it was taken over."""
    gaps = [request_gaps(w, cfg, r.prompt, r.out_tokens) for r in picked]
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max_logit_gap": float(allg.max()) if allg.size else None,
            "mean_logit_gap": float(allg.mean()) if allg.size else None,
            "tokens": int(allg.size), "requests": len(picked)}
