"""Compile-only rehearsal: each cell's prefill and decode programs at their
real widths, compiled for a described TPU v5e with no chip attached, and
their ``memory_analysis()``.  The basis for each cell's ``max_batch``.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--max-batch B] [cell ...]

Prints one JSON line per program: argument, output, temporary and aliased
bytes, and what the program needs at once (arguments + temporaries +
outputs - aliased).  Nothing runs; no number here is a chip measurement.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def programs(cell, sharding):
    """(name, jitted fn, argument shapes) of the cell's two dispatches, as
    ``ServeEngine`` builds them."""
    import jax
    import jax.numpy as jnp
    from bench import spec, weights
    from repro.nn import Model
    cfg = spec.arch_config(cell.config)
    eng, c = cell.engine, cell.cell
    m = Model(cfg)
    place = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,  # noqa: E731
                                           sharding=sharding)
    pt = jax.tree.map(place, jax.eval_shape(
        lambda: weights._make(weights.seed_key(0),
                              tuple(sorted(weights.sizes(cfg).items())))))
    bs, B, C = eng["kv_block_size"], c["max_batch"], c["max_context"]
    nb = C // bs
    cache = m.init_cache(B * nb, bs, zeros=lambda s, d: jax.ShapeDtypeStruct(
        s, d, sharding=sharding))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,       # noqa: E731
                                          sharding=sharding)
    P, chunk = eng["prefill_batch"], eng["prefill_chunk"]
    prefill = jax.jit(
        lambda pt, cache, tok, slots, offs, nv, tbl: m.prefill_chunks(
            pt, cache, tok, slots, offs, nv, block_table=tbl),
        donate_argnums=(1,))
    decode = jax.jit(
        lambda pt, cache, tok, pos, tbl: m.decode_step(
            pt, cache, tok, pos, block_table=tbl,
            decode_kernel=eng["decode_kernel"]),
        donate_argnums=(1,))
    return [("prefill", prefill, (pt, cache, i32(P, chunk), i32(P), i32(P),
                                  i32(P), i32(B, nb))),
            ("decode", decode, (pt, cache, i32(B, 1), i32(B), i32(B, nb)))]


def main(names, max_batch=None):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import spec
    import repro.kernels.ops as ops
    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = lambda: True          # compile the kernels for Mosaic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in names or [w["name"] for w in bench["workloads"]]:
        cell = spec.load_cell(name)
        if max_batch:
            cell.cell["max_batch"] = max_batch
        for prog, fn, args in programs(cell, one):
            ma = fn.lower(*args).compile().memory_analysis()
            need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            print(json.dumps({
                "cell": name, "program": prog,
                "max_batch": cell.cell["max_batch"],
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "needs_bytes": need}), flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    mb = None
    if args[:1] == ["--max-batch"]:
        mb, args = int(args[1]), args[2:]
    main(args, mb)
