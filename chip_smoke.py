"""Chip smoke: drive both halves of the system once on a TPU, at real widths.

    python chip_smoke.py             # one chip: serving + design flow
    python chip_smoke.py --chips 4   # four chips: the multi-chip paths only

One chip runs two phases through the entry points a user calls:

* serving -- qwen2-0.5b at its published widths (random weights from
  ``Model.init(PRNGKey(seed))``) behind ``ServeEngine`` with block-paged KV,
  built by the launcher's own ``build_engine``.  Eight seeded prompts of
  128-1024 tokens get 32 new tokens each, once with the gather+dense decode
  route and once with the fused Pallas kernel.  One decode step's logits are
  compared across the fused kernel, the lax.scan reference and the dense
  route.
* design flow -- the pendigits 16-10-10 MLP: the Section IV-A min-q search
  on ``QSweepEvaluator`` and the IV-B ``tune_parallel`` loop on the device
  backends (``pallas``, ``jnp``) at 2248 and 8992 validation rows, each
  compared with the numpy backend and with ``hardware_accuracy``.

``--chips 4`` runs only what exists across chips: tensor-parallel decode of
internlm2-1.8b (published widths, cut depth) against the same engine on one
device, and a row-sharded ``QSweepEvaluator`` against the unsharded one.

Every input comes from a seed or a tracked file; dispatch runs on the static
heuristics, never on a local autotune cache.  Any failed check raises.  The
last line of standard output is one JSON object naming the device, printed
only when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# One decode step's logits: the largest per-row relative L2 error
# ||a - b|| / ||b|| over the active rows.  Activations are bf16 (one ulp is
# 2^-8 relative), and any difference in an attention output -- the dense
# route re-associates the softmax; the fused kernel and the scan reference
# share their per-block arithmetic but not the compiler's exp2 -- becomes
# whole-ulp rounding flips that 24 layers carry into the logits.  Both pairs
# are held to a few bf16 ulps; a wrong kernel misses by orders of magnitude.
FUSED_VS_SCAN_TOL = 2.0 ** -4
FUSED_VS_DENSE_TOL = 2.0 ** -4


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


@contextmanager
def static_dispatch():
    """Every ``auto`` knob takes its static heuristic: an empty in-memory
    dispatch cache with measuring off, whatever the environment names."""
    from repro import tune
    with tune.use_cache(tune.DispatchCache(tune.default_config()),
                        measure=False):
        yield


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    """Largest per-row ||a - b|| / ||b|| over (rows, vocab) logits."""
    num = np.linalg.norm(a - b, axis=-1)
    return float(np.max(num / np.maximum(np.linalg.norm(b, axis=-1), 1e-30)))


# --------------------------------------------------------------- serving
def seeded_prompts(vocab: int, lengths, seed: int = SEED):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lengths]


def serve_once(eng, prompts, max_new: int, *, on_all_decoding=None):
    """Serve ``prompts`` to completion on ``eng``; returns the requests and
    per-step wall seconds.  ``on_all_decoding(eng)`` runs once, before the
    first step at which every admitted request is past its prefill."""
    from repro.runtime.serve import Request
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    step_s = []
    while eng.queue or eng.slots:
        if on_all_decoding is not None and not eng.queue and eng.slots and \
                all(st.phase == "decode" for st in eng.slots.values()):
            on_all_decoding(eng)
            on_all_decoding = None
        t0 = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.cache.data)
        step_s.append(time.perf_counter() - t0)
    for r in reqs:
        if r.status != "done" or len(r.out_tokens) != max_new:
            raise AssertionError(
                f"request {r.rid}: status={r.status} "
                f"tokens={len(r.out_tokens)}/{max_new}")
    return reqs, step_s


def decode_logits(eng, decode_fn) -> np.ndarray:
    """One decode step's logits (active rows) from ``eng``'s next dispatch
    inputs, run through ``decode_fn`` on a copy of the cache (the engines'
    decode programs donate the cache they are given)."""
    active, _, _, args = eng.decode_inputs()
    cache = jax.tree.map(jnp.copy, args[1])
    lg, _ = decode_fn(args[0], cache, *args[2:])
    return np.asarray(lg, np.float32)[np.asarray(active), 0]


def serving_phase(cfg, *, prompt_lens, max_new: int, seed: int = SEED,
                  **engine_kw) -> dict:
    """qwen2 serving through ``build_engine`` with the dense and the fused
    decode routes; checks every request's tokens and one step's logits."""
    from repro.launch.serve import build_engine
    from repro.nn import Model
    params = Model(cfg).init(jax.random.PRNGKey(seed))
    prompts = seeded_prompts(cfg.vocab, prompt_lens, seed)
    out = {"engines": {}}
    engines = {}

    def compare(eng):
        ref_fn = jax.jit(
            lambda pt, cache, tok, pos, tbl: eng.model.decode_step(
                pt, cache, tok, pos, block_table=tbl,
                decode_kernel="reference"))
        fused = decode_logits(eng, eng._decode)
        scan = decode_logits(eng, ref_fn)
        dense = decode_logits(eng, engines["dense"]._decode)
        if not (np.all(np.isfinite(fused)) and np.all(np.isfinite(dense))):
            raise AssertionError("non-finite decode logits")
        out["fused_vs_scan_bitwise"] = bool(np.array_equal(fused, scan))
        out["fused_vs_scan_rel"] = rel_err(fused, scan)
        out["fused_vs_dense_rel"] = rel_err(fused, dense)
        out["logit_rows"] = int(fused.shape[0])

    tokens = {}
    for kernel in ("dense", "fused"):
        eng = build_engine(cfg, params, decode_kernel=kernel, **engine_kw)
        engines[kernel] = eng
        t0 = time.perf_counter()
        reqs, step_s = serve_once(
            eng, prompts, max_new,
            on_all_decoding=compare if kernel == "fused" else None)
        out["engines"][kernel] = {
            "wall_s": time.perf_counter() - t0,
            "first_step_s": step_s[0],
            "steps": len(step_s),
            "last_steps_median_s": float(np.median(step_s[-8:])),
            "prefill_tokens": eng.stats["prefill_tokens"],
            "decode_tokens": eng.stats["decode_tokens"],
        }
        tokens[kernel] = np.asarray([r.out_tokens for r in reqs])
    if "fused_vs_scan_rel" not in out:
        raise AssertionError("no step had every request decoding")
    # greedy tokens of a random-weight model: near-tie argmaxes flip under
    # the dense route's re-associated softmax, so this is reported only
    out["token_agreement"] = float(np.mean(tokens["dense"] == tokens["fused"]))
    if out["fused_vs_scan_rel"] > FUSED_VS_SCAN_TOL:
        raise AssertionError(f"fused vs scan logits: "
                             f"{out['fused_vs_scan_rel']} > {FUSED_VS_SCAN_TOL}")
    if out["fused_vs_dense_rel"] > FUSED_VS_DENSE_TOL:
        raise AssertionError(
            f"fused vs dense logits: {out['fused_vs_dense_rel']} > "
            f"{FUSED_VS_DENSE_TOL}")
    return out


# ----------------------------------------------------------- design flow
def pendigits_setup(*, epochs: int, n_val: int | None = None):
    """Train the pendigits 16-10-10 MLP; returns (float result, quantized
    validation inputs, labels)."""
    from repro.core import quantize_inputs
    from repro.data import pendigits
    from repro.train.zaal import TrainConfig, train
    ds = pendigits.load()
    (xtr, ytr), (xval, yval) = ds.validation_split()
    if n_val is not None:
        xval, yval = xval[:n_val], yval[:n_val]
    res = train(TrainConfig(structure=(16, 10, 10), epochs=epochs),
                pendigits.to_unit(xtr), ytr, pendigits.to_unit(xval), yval)
    return res, quantize_inputs(pendigits.to_unit(xval)), yval


def _check_minq(got, want, x, y, ev, backend: str) -> None:
    from repro.core.intmlp import hardware_accuracy
    if ev.backend != backend or ev.stats["demoted"]:
        raise AssertionError(f"QSweepEvaluator: backend={ev.backend} "
                             f"demoted={ev.stats['demoted']}")
    if (got.q, got.ha, got.history) != (want.q, want.ha, want.history):
        raise AssertionError(f"find_min_q[{backend}] != numpy: "
                             f"{(got.q, got.ha)} vs {(want.q, want.ha)}")
    if got.ha != hardware_accuracy(got.mlp, x, y):
        raise AssertionError(f"find_min_q[{backend}] accuracy != oracle")


def _check_tune(got, want, x, y, backend: str) -> None:
    from repro.core.intmlp import hardware_accuracy
    if got.stats["backend"] != backend or got.stats.get("demoted"):
        raise AssertionError(f"tune_parallel: backend={got.stats['backend']} "
                             f"demoted={got.stats.get('demoted')}")
    same = (got.bha, got.log, got.replacements) == \
        (want.bha, want.log, want.replacements) and all(
            np.array_equal(a, b) for a, b in
            zip(got.mlp.weights + got.mlp.biases,
                want.mlp.weights + want.mlp.biases))
    if not same:
        raise AssertionError(f"tune_parallel[{backend}] decisions != numpy")
    if got.bha != hardware_accuracy(got.mlp, x, y):
        raise AssertionError(f"tune_parallel[{backend}] accuracy != oracle")


def design_phase(*, epochs: int, reps=(1, 4), max_sweeps: int = 2,
                 n_val: int | None = None) -> dict:
    """Min-q search and parallel tuning on the device backends at each
    validation size (the split repeated ``rep`` times), bit-identical to the
    numpy backend and to the ``hardware_accuracy`` oracle."""
    from repro.core import find_min_q, tune_parallel
    from repro.eval import QSweepEvaluator
    from repro.kernels import csd_matvec
    res, x1, y1 = pendigits_setup(epochs=epochs, n_val=n_val)
    acts = ("htanh", "hsig")
    out = {}
    for rep in reps:
        x, y = np.concatenate([x1] * rep), np.concatenate([y1] * rep)
        row = out[f"val{x.shape[0]}"] = {}
        want_q = find_min_q(res.weights, res.biases, acts, x, y,
                            evaluator=QSweepEvaluator(x, y, backend="numpy"))
        want_t = tune_parallel(want_q.mlp, x, y, max_sweeps=max_sweeps,
                               backend="numpy")
        row["q"], row["ha"], row["bha"] = want_q.q, want_q.ha, want_t.bha
        for backend in ("pallas", "jnp"):
            ev = QSweepEvaluator(x, y, backend=backend)
            t0 = time.perf_counter()
            got = find_min_q(res.weights, res.biases, acts, x, y,
                             evaluator=ev)
            row[f"min_q_{backend}_s"] = time.perf_counter() - t0
            _check_minq(got, want_q, x, y, ev, backend)
            t0 = time.perf_counter()
            tp = tune_parallel(want_q.mlp, x, y, max_sweeps=max_sweeps,
                               backend=backend)
            row[f"tune_{backend}_s"] = time.perf_counter() - t0
            _check_tune(tp, want_t, x, y, backend)
        # the digit-plane kernel itself on the tuned first layer
        w0 = want_t.mlp.weights[0]
        got = np.asarray(csd_matvec(jnp.asarray(x, jnp.int32), w_int=w0))
        if not np.array_equal(got.astype(np.int64),
                              x.astype(np.int64) @ w0.astype(np.int64)):
            raise AssertionError("csd_matvec != int64 matmul")
    return out


# ----------------------------------------------------------- four chips
def tp_decode_phase(cfg, *, prompt_lens, max_new: int, seed: int = SEED,
                    **engine_kw) -> dict:
    """Tensor-parallel decode over every device against the same engine on
    one device, in one process: the token streams must be identical.

    Runs with full-precision float32 matmuls: the psum re-associates the
    row-parallel sums, and at the TPU's default one-pass bf16 matmul
    precision that re-association becomes rounding flips which change
    near-tie argmaxes of a random-weight model."""
    from repro.launch.serve import build_engine
    from repro.nn import Model
    params = Model(cfg).init(jax.random.PRNGKey(seed))
    prompts = seeded_prompts(cfg.vocab, prompt_lens, seed)
    toks, logits = {}, {}
    with jax.default_matmul_precision("highest"):
        for tp in (False, True):
            eng = build_engine(cfg, params, tensor_parallel=tp, **engine_kw)

            def grab(e, tp=tp):
                logits[tp] = decode_logits(e, e._decode)

            reqs, _ = serve_once(eng, prompts, max_new, on_all_decoding=grab)
            toks[tp] = [r.out_tokens for r in reqs]
            del eng
    same = np.asarray(toks[True]) == np.asarray(toks[False])
    out = {"requests": len(prompts), "tokens": int(same.size),
           "devices": jax.device_count(),
           "token_agreement": float(same.mean()),
           "all_decoding_step_logits_rel": rel_err(logits[True],
                                                   logits[False])}
    if not same.all():
        raise AssertionError(
            f"tensor-parallel tokens != single device: {json.dumps(out)}")
    return out


def sharded_sweep_phase(*, epochs: int, rep: int = 4,
                        n_val: int | None = None) -> dict:
    """``QSweepEvaluator(shard=True)`` rows over every device against the
    unsharded evaluator: identical accuracies at every q level."""
    from repro.core import find_min_q
    from repro.eval import QSweepEvaluator
    res, x1, y1 = pendigits_setup(epochs=epochs, n_val=n_val)
    x, y = np.concatenate([x1] * rep), np.concatenate([y1] * rep)
    acts = ("htanh", "hsig")
    out = {}
    for backend in ("pallas", "jnp"):
        runs = {}
        for shard in (False, True):
            ev = QSweepEvaluator(x, y, backend=backend, shard=shard)
            r = find_min_q(res.weights, res.biases, acts, x, y, evaluator=ev)
            if ev.backend != backend or ev.stats["demoted"]:
                raise AssertionError(f"{backend} shard={shard} demoted")
            runs[shard] = (r.q, r.ha, r.history)
        if runs[True] != runs[False]:
            raise AssertionError(f"sharded {backend} sweep != unsharded")
        out[backend] = {"q": runs[True][0], "levels": len(runs[True][2])}
    return out


# ------------------------------------------------------------------ main
def run_phase(name: str, fn, **kw) -> dict:
    t0 = time.perf_counter()
    res = fn(**kw)
    res["phase_s"] = time.perf_counter() - t0
    res["peak_bytes_in_use"] = peak_bytes()
    log(f"[{name}] " + json.dumps(res, default=str))
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    from repro.nn import get_config
    log(f"device_kind={dev.device_kind} count={jax.device_count()} "
        f"compile_cache={enable_compile_cache()}")
    with static_dispatch():
        if args.chips == 1:
            run_phase("serving", serving_phase,
                      cfg=get_config("qwen2-0.5b"),
                      prompt_lens=np.random.default_rng(SEED).integers(
                          128, 1025, 8),
                      max_new=32, max_batch=8, max_context=2048,
                      kv_block_size=128, prefill_batch=4)
            run_phase("design", design_phase, epochs=40)
        else:
            # published widths (16/8 heads, d_ff 8192 divide by 4); depth
            # cut to 8 of 24 layers because device 0 holds the one-device
            # engine beside its tensor-parallel shard.  float32 activations,
            # for the reason tp_decode_phase gives.
            cfg = dataclasses.replace(get_config("internlm2-1.8b"),
                                      n_layers=8, dtype="float32")
            run_phase("tp_decode", tp_decode_phase, cfg=cfg,
                      prompt_lens=np.random.default_rng(SEED).integers(
                          128, 1025, 8),
                      max_new=16, max_batch=8, max_context=2048,
                      kv_block_size=128, prefill_batch=4)
            run_phase("sharded_sweep", sharded_sweep_phase, epochs=40)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
