"""Compile-only rehearsal: each cell's prefill and decode programs at their
real widths, compiled for a described TPU v5e with no chip attached, and
their ``memory_analysis()``.  The basis for each cell's ``max_batch``.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--max-batch B] [cell ...]

A cell of one chip compiles for one device of the described ``v5e:2x2``; a
cell of ``chips: 4`` for a mesh of its four, with the weights placed as
``bench.weights.shardings`` places them and the KV pool sharded on its KV
heads, as tensor-parallel decode keeps it (a configuration whose engine sets
``tensor_parallel`` compiles the engine's own sharded decode).  Prints one
JSON line per program: the devices it spans and, per device, argument,
output, temporary and aliased bytes, and what the program needs at once
(arguments + temporaries + outputs - aliased), or the compiler's refusal
where it does not fit.  Nothing runs; no number here is a chip
measurement.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def programs(cell, devices):
    """(name, jitted fn, argument shapes) of the cell's two dispatches, as
    ``ServeEngine`` builds them, over ``devices`` (one, or a mesh's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench import spec, weights
    from repro.nn import Model
    from repro.runtime.serve import ServeEngine
    cfg = spec.arch_config(cell.config)
    eng, c = cell.engine, cell.cell
    m = Model(cfg)
    mesh = Mesh(np.array(devices), (weights.AXIS,))
    shaped = lambda s, sh: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=sh)
    w = jax.eval_shape(lambda: weights._make(
        weights.seed_key(0), tuple(sorted(weights.sizes(cfg).items()))))
    pt = jax.tree.map(shaped, w, weights.shardings(w, mesh))
    bs, B, C = eng["kv_block_size"], c["max_batch"], c["max_context"]
    nb = C // bs
    heads = NamedSharding(mesh, P(None, None, None, weights.AXIS, None))
    cache = m.init_cache(B * nb, bs, zeros=lambda s, d: jax.ShapeDtypeStruct(
        s, d, sharding=heads))
    rep = NamedSharding(mesh, P())
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,       # noqa: E731
                                          sharding=rep)
    pb, chunk = eng["prefill_batch"], eng["prefill_chunk"]
    prefill = jax.jit(
        lambda pt, cache, tok, slots, offs, nv, tbl: m.prefill_chunks(
            pt, cache, tok, slots, offs, nv, block_table=tbl),
        donate_argnums=(1,))
    if eng.get("tensor_parallel"):
        # the engine's own builder, handed shapes in place of arrays
        shell = SimpleNamespace(cfg=cfg, params=pt, kv_block_size=bs,
                                kv_gather=eng.get("kv_gather", "take"),
                                decode_kernel=eng["decode_kernel"],
                                cache=SimpleNamespace(data=cache))
        decode = ServeEngine._build_tp_decode(shell, lambda t: t, mesh)
    else:
        decode = jax.jit(
            lambda pt, cache, tok, pos, tbl: m.decode_step(
                pt, cache, tok, pos, block_table=tbl,
                decode_kernel=eng["decode_kernel"]),
            donate_argnums=(1,))
    return [("prefill", prefill, (pt, cache, i32(pb, chunk), i32(pb),
                                  i32(pb), i32(pb), i32(B, nb))),
            ("decode", decode, (pt, cache, i32(B, 1), i32(B), i32(B, nb)))]


def main(names, max_batch=None):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from bench import spec
    import repro.kernels.ops as ops
    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = lambda: True          # compile the kernels for Mosaic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in names or [w["name"] for w in bench["workloads"]]:
        cell = spec.load_cell(name)
        if max_batch:
            cell.cell["max_batch"] = max_batch
        devices = topo.devices[:cell.workload["chips"]]
        for prog, fn, args in programs(cell, devices):
            line = {"cell": name, "program": prog, "devices": len(devices),
                    "max_batch": cell.cell["max_batch"]}
            try:
                ma = fn.lower(*args).compile().memory_analysis()
            except jax.errors.JaxRuntimeError as e:
                # the chip's compiler refuses what does not fit its memory
                print(json.dumps(dict(line, refused=str(e).split("\n")[0])),
                      flush=True)
                continue
            need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            print(json.dumps(dict(
                line, argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes, needs_bytes=need)),
                flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    mb = None
    if args[:1] == ["--max-batch"]:
        mb, args = int(args[1]), args[2:]
    main(args, mb)
