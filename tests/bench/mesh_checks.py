"""The four-chip path of the harness on four CPU devices, in one process of
its own (``test_bench_mesh.py`` starts it with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  Prints one JSON
line of readings for the tests to judge:

* ``weights``: for each leaf, whether the tree made over a 4-device mesh
  equals the one-device tree bit for bit, its ``PartitionSpec``, and the
  shape each device holds;
* ``gaps``: the reference's gaps for one request on one device and over
  the mesh;
* ``setup``: for a cell of 1, 2 and 4 chips, what ``bench.run`` handed a
  stub ``build_engine``: the mesh's devices and axes, its keywords, and the
  devices the weights were on;
* ``run``: the result line of a whole run of a tensor-parallel cell of 4
  chips (interpret-mode kernels);
* ``no_exchange``: the same with the exchange between chips left out (the
  decode's psum of each chip's partial sums), which has to come out not
  correct.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 tests/bench/mesh_checks.py <scratch dir>
"""
from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")]

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

import tiny                                                   # noqa: E402
from bench import reference, run, spec, weights               # noqa: E402

SEED = 2 ** 40 + 3
# a whole run's seed that reads far from the tiny limit: 1.1e-4 of 3.2e-4
# (seven seeds on the CPU read 1.1e-4 to 2.4e-4)
RUN_SEED = 77


class Stop(Exception):
    """Raised by the stub engine once it has recorded its arguments."""


def check_weights(cfg, mesh):
    one = weights.make_weights(cfg, SEED)
    four = weights.make_weights(cfg, SEED, mesh)
    paths = jax.tree_util.tree_flatten_with_path(one)[0]
    out = {}
    for (path, a), b in zip(paths, jax.tree.leaves(four)):
        name = jax.tree_util.keystr(path)
        out[name] = {
            "equal": bool(np.array_equal(np.asarray(a), np.asarray(b))),
            "spec": [None if d is None else str(d)
                     for d in b.sharding.spec],
            "shards": sorted({tuple(s.data.shape)
                              for s in b.addressable_shards}),
            "shape": list(b.shape)}
    return out


def check_gaps(cfg, mesh):
    rng = np.random.default_rng(SEED)
    prompt = rng.integers(0, cfg.vocab, 150)
    served = rng.integers(0, cfg.vocab, 60)
    return {"one": reference.request_gaps(
                weights.make_weights(cfg, SEED), cfg, prompt,
                served).tolist(),
            "mesh": reference.request_gaps(
                weights.make_weights(cfg, SEED, mesh), cfg, prompt,
                served).tolist()}


def check_setup(root, chips):
    import repro.launch.serve as serve
    got = {}

    def stub(cfg, params, **kw):
        mesh = kw.get("mesh")
        got["keys"] = sorted(kw)
        got["kwargs"] = {k: v for k, v in kw.items() if k != "mesh"}
        got["mesh_devices"] = (None if mesh is None
                               else [d.id for d in mesh.devices.flat])
        got["mesh_axes"] = None if mesh is None else list(mesh.axis_names)
        got["weight_devices"] = sorted(
            d.id for d in params["layers"]["attn"]["wq"].sharding.device_set)
        raise Stop

    tiny.set_chips(root, chips)
    saved, serve.build_engine = serve.build_engine, stub
    try:
        run.main(["--workload", tiny.TP_CELL, "--seed", "5",
                  "--seconds", "1"], require_tpu=False, root=root)
    except Stop:
        pass
    finally:
        serve.build_engine = saved
    return got


def whole_run(root):
    tiny.set_chips(root, 4)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", tiny.TP_CELL, "--seed", str(RUN_SEED),
                  "--seconds", "3", "--trace", "0"], require_tpu=False,
                 root=root)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def no_exchange_run(root):
    from repro.nn.model import Model
    saved = Model._tp_reduce
    Model._tp_reduce = lambda self, t: t
    try:
        return whole_run(root)
    finally:
        Model._tp_reduce = saved


def main(scratch: str):
    root = tiny.make_tp_tree(Path(scratch))
    cfg = spec.arch_config(tiny.MHA_CONFIG)
    mesh = run.mesh_of(jax.devices()[:4])
    print(json.dumps({
        "devices": len(jax.devices()),
        "weights": check_weights(cfg, mesh),
        "gaps": check_gaps(cfg, mesh),
        "setup": {c: check_setup(root, c) for c in (1, 2, 4)},
        "all_devices": [d.id for d in jax.devices()],
        "run": whole_run(root),
        "no_exchange": no_exchange_run(root)}))


if __name__ == "__main__":
    main(sys.argv[1])
