"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (reported as ``setup_s``, from process start): make the weights on
the device from the seed in one jitted call, build the serving engine with
the cell's settings (on a cell of several chips, over a mesh of them, with
the weights placed on it; ``bench/README.md``), compile its own prefill and
decode programs through JAX's persistent cache, and serve the mix's
``ramp_s`` seconds of traffic (where it has one) so the window opens on a
loaded engine.  Then serve the
cell's traffic for ``--seconds`` (``bench/loadgen.py``), read the device's peak memory, free the
engine, and check what the window served against the float32 reference
(``bench/reference.py``).  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the profiler records the window's
last seconds and the result carries its per-layer metrics
(``bench/metrics/<name>.py``), the device's busy time and a breakdown.

The last line of standard output is the JSON result; the last lines of
standard error give every number compared with its limit.  Without a TPU,
or with fewer chips than the cell asks for, the run prints no result and
exits 3.
"""
from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is timed from here (load clock)

import argparse                                              # noqa: E402
import dataclasses                                           # noqa: E402
import gc                                                    # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import tempfile                                              # noqa: E402
from pathlib import Path                                     # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # the checkout's root, not bench/, heads the path (bench/trace.py must
    # not shadow the standard library's trace module)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

TRACE_SECONDS = 4.0        # the traced part: the window's last seconds
SAMPLE_SALT = 0x5EED       # the correctness sample's stream, apart from traffic
NO_CHIP = 3
# engine settings that describe the harness, not the engine: each value the
# harness takes, as ``build_engine``'s keywords (``eos: off`` is
# ``eos_id=-1``, which ``build_engine`` always sets)
HARNESS_KEYS = {
    "sampling": {"greedy": {"temperature": 0.0}},
    "eos": {"off": {}},
}
# ``build_engine``'s keywords that the harness sets from the cell itself
SET_BY_HARNESS = frozenset({"cfg", "params", "max_batch", "max_context",
                            "mesh", "temperature", "eos_id"})


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    rec: object
    sizes: dict
    max_batch: int
    pool_blocks: int
    kv_itemsize: int
    peaks: dict            # of one chip
    trace: dict | None     # per device: op and busy seconds are means
    chips: int             # the cell's chips, over which the work is shared


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _reader(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devs[0].platform}", file=sys.stderr)
        raise SystemExit(NO_CHIP)
    if len(devs) < chips:
        print(f"the cell needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        raise SystemExit(NO_CHIP)
    return devs[:chips]


def _keywords(build) -> set:
    """The keywords ``build`` takes; where it passes the rest on
    (``build_engine``'s ``**paged``), ``ServeEngine``'s too."""
    import inspect
    from repro.runtime.serve import ServeEngine

    def named(fn):
        ps = inspect.signature(fn).parameters.values()
        return ({p.name for p in ps if p.kind in (p.POSITIONAL_OR_KEYWORD,
                                                  p.KEYWORD_ONLY)},
                any(p.kind is p.VAR_KEYWORD for p in ps))
    names, passes_on = named(build)
    return names | named(ServeEngine.__init__)[0] if passes_on else names


def engine_kwargs(engine: dict, build) -> dict:
    """``build``'s keywords from a cell's engine settings: the harness's
    own keys through ``HARNESS_KEYS``, every other key as it stands.  A key
    that neither the harness nor ``build`` knows is refused by name."""
    known = _keywords(build) - SET_BY_HARNESS
    kw = {}
    for key, value in engine.items():
        if key in HARNESS_KEYS:
            if value not in HARNESS_KEYS[key]:
                raise SystemExit(f"engine setting {key}={value!r}: the "
                                 f"harness takes {sorted(HARNESS_KEYS[key])}")
            kw.update(HARNESS_KEYS[key][value])
        elif key in known:
            kw[key] = value
        else:
            raise SystemExit(f"unknown engine setting {key!r}: neither the "
                             f"harness nor build_engine takes it")
    return kw


def mesh_of(devs):
    """A mesh over exactly the cell's chips (axis ``weights.AXIS``), or
    None for one chip."""
    if len(devs) == 1:
        return None
    import numpy as np
    from jax.sharding import Mesh
    from bench.weights import AXIS
    return Mesh(np.array(devs), (AXIS,))


def setup(cell, seed: int, mesh=None):
    """Weights from the seed, the engine, and its programs compiled."""
    import jax
    from bench import spec, weights
    from bench.loadgen import warm_up
    from repro.launch.serve import build_engine
    kw = engine_kwargs(cell.engine, build_engine)
    if mesh is not None:
        kw["mesh"] = mesh
    cfg = spec.arch_config(cell.config)
    w = jax.block_until_ready(weights.make_weights(cfg, seed, mesh))
    eng = build_engine(cfg, w, max_batch=cell.cell["max_batch"],
                       max_context=cell.cell["max_context"], **kw)
    warm_up(eng, cfg.vocab)
    return cfg, eng


def main(argv=None, *, require_tpu: bool = True, root: Path = ROOT,
         engine: dict | None = None) -> dict:
    """One run; ``engine`` overrides the cell's engine settings (the
    control: ``{"quantized": True}``, see ``bench/calibrate.py``)."""
    args = _args(argv)
    from bench import spec
    cell = spec.load_cell(args.workload, root)
    if engine:
        cell.cell.setdefault("engine", {}).update(engine)
    import jax
    import numpy as np
    from repro.launch.compile_cache import enable_compile_cache
    if require_tpu:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = _devices(cell.workload["chips"], require_tpu)
    mesh = mesh_of(devs)
    kind = devs[0].device_kind
    from bench import loadgen, e2e, peaks, reference, weights
    from bench import trace as trace_mod
    pk = peaks.peaks(kind) if require_tpu else peaks.PEAKS["TPU v5 lite"]

    cfg, eng = setup(cell, args.seed, mesh)
    sched = cell.schedule(args.seed, cell.ramp_s + args.seconds, cfg.vocab)

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    rec = loadgen.serve_window(
        eng, sched, args.seconds, ramp_s=cell.ramp_s,
        trace_s=min(TRACE_SECONDS, args.seconds) if args.trace else 0.0,
        trace_dir=tdir)
    setup_s = rec.t0 - T_START          # the window opened: set-up ends
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
    pool_blocks = eng.cache.n_blocks
    kv_itemsize = jax.tree.leaves(eng.cache.data)[0].dtype.itemsize
    max_batch = eng.max_batch
    done = [r for r in rec.requests if r.status == "done"]
    del eng
    gc.collect()

    red = None
    if args.trace:
        red = trace_mod.reduce(
            trace_mod.find_xplane(tdir), loadgen.TRACED_WINDOW,
            host_spans=loadgen.SPANS)
        shutil.rmtree(tdir, ignore_errors=True)

    # correctness: the reference, from the seed, after the program is freed
    t_ref = time.monotonic()
    ref_w = weights.make_weights(cfg, args.seed, mesh)
    picked = reference.sample(done, np.random.default_rng(
        [args.seed, SAMPLE_SALT]))
    readings = reference.compare(ref_w, cfg, picked)
    ref_s = time.monotonic() - t_ref
    del ref_w
    limits = cell.cell["limits"]
    checks = {k: {"value": readings[k], "limit": v} for k, v in limits.items()}
    correct = bool(picked) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

    if args.trace:
        ctx = Context(rec=rec, sizes=weights.sizes(cfg), max_batch=max_batch,
                      pool_blocks=pool_blocks, kv_itemsize=kv_itemsize,
                      peaks=pk, trace=red, chips=len(devs))
        values = {m["name"]: _reader(root, m["name"])(ctx)
                  for m in cell.metrics("per_layer")}
        units = {m["name"]: m["unit"] for m in cell.metrics("per_layer")}
    else:
        values = dict(e2e.metrics(rec), setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in cell.metrics("end_to_end")}
        values = {k: v for k, v in values.items() if k in units}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()
               if v is not None}

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": len(rec.due),
           "failed": e2e.failed(rec.due), "metrics": metrics,
           "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": trace_mod.top(red["op_s"]),
                            "idle_gaps": trace_mod.top(red["idle_s"])}
    out["checks"] = checks
    info = {"cell": args.workload, "seed": args.seed, "setup_s": setup_s,
            "window_s": rec.window_s, "reference_s": ref_s,
            "compiles_in_window": rec.compiles,
            "finished": len(done), "max_logit_gap": readings["max_logit_gap"],
            "compared_requests": readings["requests"],
            "compared_tokens": readings["tokens"],
            "generator_late_p95_ms": 1e3 * e2e.nearest_rank(
                rec.lateness_s, 95) if rec.lateness_s else None}
    print(json.dumps({"info": info}), file=sys.stderr)
    for k, c in checks.items():
        print(f"{k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out))
    sys.stdout.flush()
    return out


if __name__ == "__main__":
    main()
