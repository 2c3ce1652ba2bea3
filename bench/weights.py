"""Seeded weights in the serving engine's parameter layout, made on the
device in one jitted call.

The benchmark makes the weights itself, so the reference can make the same
ones from the seed without taking anything the program made.  Master
weights are float32, the type the engine serves (it casts to the compute
type inside each dispatch).  Norm scales and QKV biases are drawn too, so a
fault in either shows in the comparison.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (beyond 32 bits too)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _layer(key, s: dict):
    d, f = s["d"], s["f"]
    hq, hkv, hd = s["hq"], s["hkv"], s["hd"]
    ks = jax.random.split(key, 12)
    attn = {
        "wq": _normal(ks[0], (d, hq * hd), d ** -0.5),
        "wk": _normal(ks[1], (d, hkv * hd), d ** -0.5),
        "wv": _normal(ks[2], (d, hkv * hd), d ** -0.5),
        "wo": _normal(ks[3], (hq * hd, d), (hq * hd) ** -0.5),
    }
    if s["bias"]:
        attn["bq"] = _normal(ks[4], (hq * hd,), 0.1)
        attn["bk"] = _normal(ks[5], (hkv * hd,), 0.1)
        attn["bv"] = _normal(ks[6], (hkv * hd,), 0.1)
    return {
        "ln1": _normal(ks[7], (d,), 0.1),
        "ln2": _normal(ks[8], (d,), 0.1),
        "attn": attn,
        "mlp": {"wg": _normal(ks[9], (d, f), d ** -0.5),
                "wu": _normal(ks[10], (d, f), d ** -0.5),
                "wd": _normal(ks[11], (f, d), f ** -0.5)},
    }


def sizes(cfg) -> dict:
    """The shape parameters the weights need, from an ArchConfig."""
    return {"L": cfg.n_layers, "d": cfg.d_model, "f": cfg.d_ff,
            "hq": cfg.n_heads, "hkv": cfg.n_kv_heads, "hd": cfg.head_dim_,
            "V": cfg.vocab, "bias": bool(cfg.qkv_bias)}


@partial(jax.jit, static_argnums=(1,))
def _make(key, frozen):
    s = dict(frozen)
    k_emb, k_layers, k_head, k_norm = jax.random.split(key, 4)
    return {
        "embed": _normal(k_emb, (s["V"], s["d"]), 1.0),
        "final_norm": _normal(k_norm, (s["d"],), 0.1),
        "lm_head": _normal(k_head, (s["d"], s["V"]), s["d"] ** -0.5),
        "layers": jax.vmap(lambda k: _layer(k, s))(
            jax.random.split(k_layers, s["L"])),
    }


def make_weights(cfg, seed: int):
    """float32 weights for ``cfg`` from ``seed``, one jitted call."""
    return _make(seed_key(seed), tuple(sorted(sizes(cfg).items())))
