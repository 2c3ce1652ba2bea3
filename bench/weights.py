"""Seeded weights in the serving engine's parameter layout, made on the
device in one jitted call.

The benchmark makes the weights itself, so the reference can make the same
ones from the seed without taking anything the program made.  Master
weights are float32, the type the engine serves (it casts to the compute
type inside each dispatch).  Norm scales and QKV biases are drawn too, so a
fault in either shows in the comparison.

On a cell of several chips the same call places every leaf on the cell's
mesh where tensor-parallel serving keeps it (``shardings``), so no chip
ever holds the whole tree; the values are the same as on one chip, bit for
bit (JAX's partitionable threefry draws each element from its index).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

AXIS = "model"         # the mesh axis of a cell's chips
# decoder-layer leaves whose last dimension is a head's or an FFN column's
# output, and those whose dim -2 is the contraction of a row-parallel
# matmul (each chip's output a partial sum); every other leaf (embedding,
# norms, lm_head) is replicated
SHARD_LAST = frozenset({"wq", "wk", "wv", "bq", "bk", "bv", "wg", "wu"})
SHARD_ROWS = frozenset({"wo", "wd"})


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


def specs(tree, axis: str = AXIS):
    """The ``PartitionSpec`` of every leaf of a weight tree (or of its
    shapes) on a mesh axis ``axis``."""
    def spec(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        if "layers" in keys and keys[-1] in SHARD_LAST:
            return P(*[None] * (leaf.ndim - 1), axis)
        if "layers" in keys and keys[-1] in SHARD_ROWS:
            return P(*[None] * (leaf.ndim - 2), axis, None)
        return P()
    return jax.tree_util.tree_map_with_path(spec, tree)


def shardings(tree, mesh):
    """``specs`` as ``NamedSharding``s on ``mesh`` (its first axis)."""
    return jax.tree.map(lambda p: NamedSharding(mesh, p),
                        specs(tree, mesh.axis_names[0]),
                        is_leaf=lambda x: isinstance(x, P))


@lru_cache(maxsize=None)
def _placed(mesh, frozen):
    shapes = jax.eval_shape(_make, seed_key(0), frozen)
    return jax.jit(_make, static_argnums=(1,),
                   out_shardings=shardings(shapes, mesh))


def make_weights(cfg, seed: int, mesh=None):
    """float32 weights for ``cfg`` from ``seed``, one jitted call; with a
    ``mesh``, each leaf made where ``shardings`` places it."""
    key, frozen = seed_key(seed), tuple(sorted(sizes(cfg).items()))
    if mesh is None:
        return _make(key, frozen)
    return _placed(mesh, frozen)(key, frozen)
