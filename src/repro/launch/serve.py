"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Builds the paged serving engine (optionally int8-PoT quantized — the
paper's technique as a serving flag), serves a demo request batch through
the admission queue, and reports per-request latency percentiles plus
prefill/decode throughput.  ``--engine reference`` runs the retained
continuous-batching-lite engine instead (any model family);
``--data-parallel`` shards the decode step over every visible device;
``--tensor-parallel`` shards heads + FFN instead (works with block
paging); ``--decode-kernel fused`` runs decode attention straight from
the KV block pool via the fused Pallas kernel.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.nn import Model, get_config
from repro.runtime.serve import (ReferenceEngine, Request, ServeEngine,
                                 summarize)


def build_engine(cfg, params, *, engine: str = "paged", max_batch: int = 4,
                 max_context: int = 128, quantized: bool = False,
                 quant_bits: int = 8, temperature: float = 0.0,
                 admission: str = "truncate", **paged):
    """The launcher's engine for ``cfg``: ``ServeEngine`` for the standard-KV
    families (``paged`` carries its remaining options, e.g.
    ``kv_block_size``, ``decode_kernel``, ``prefill_batch``), the retained
    ``ReferenceEngine`` on request or for any other family.  EOS is off
    (``eos_id=-1``), so every request runs to its ``max_new_tokens``."""
    if engine == "reference" or cfg.family not in ("dense", "moe"):
        return ReferenceEngine(cfg, params, max_batch=max_batch,
                               max_context=max_context, eos_id=-1,
                               quantized=quantized, temperature=temperature,
                               admission=admission)
    return ServeEngine(cfg, params, max_batch=max_batch,
                       max_context=max_context, eos_id=-1,
                       quantized=quantized, quant_bits=quant_bits,
                       temperature=temperature, admission=admission, **paged)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4,
                    help="KV slots (paged) / decode batch (reference)")
    ap.add_argument("--context", type=int, default=128)
    ap.add_argument("--quantized", action="store_true")
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--prefill-batch", type=int, default=1,
                    help="prefill chunks ingested per engine step (one "
                         "fixed-shape batched dispatch)")
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="block-paged KV block size (0 = contiguous slot "
                         "rows); must divide --context")
    ap.add_argument("--kv-gather", choices=("take", "pallas"),
                    default="take",
                    help="block-table gather route (block-paged mode only)")
    ap.add_argument("--decode-kernel",
                    choices=("auto", "dense", "reference", "fused"),
                    default="dense",
                    help="decode attention route (block-paged mode only): "
                         "gather+dense oracle, scan reference, the fused "
                         "Pallas paged-attention kernel, or auto (the "
                         "measured-dispatch cache's winner, DESIGN.md 17)")
    ap.add_argument("--tensor-parallel", action="store_true",
                    help="shard attention heads + FFN over all devices "
                         "(composes with --kv-block-size)")
    ap.add_argument("--admission", choices=("reject", "truncate"),
                    default="truncate")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request queue deadline in seconds")
    ap.add_argument("--engine", choices=("paged", "reference"),
                    default="paged")
    ap.add_argument("--data-parallel", action="store_true",
                    help="shard_map the decode step over all devices")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = build_engine(cfg, params, engine=args.engine,
                       max_batch=args.batch, max_context=args.context,
                       quantized=args.quantized, quant_bits=args.bits,
                       temperature=args.temperature,
                       admission=args.admission,
                       prefill_chunk=args.prefill_chunk,
                       prefill_batch=args.prefill_batch,
                       kv_block_size=args.kv_block_size,
                       kv_gather=args.kv_gather,
                       decode_kernel=args.decode_kernel,
                       data_parallel=args.data_parallel,
                       tensor_parallel=args.tensor_parallel)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len)
                    .astype(np.int32),
                    max_new_tokens=args.max_new,
                    deadline_s=args.deadline)
            for i in range(args.requests)]
    t0 = time.time()
    eng.run(reqs)
    wall = time.time() - t0
    print(f"served {len(reqs)} requests in {wall:.2f}s "
          f"(engine={args.engine}, quantized={args.quantized})")
    print(f"prefill: {eng.stats['prefill_tokens']} tok in "
          f"{eng.stats['prefill_s']:.2f}s; decode: "
          f"{eng.stats['decode_tokens']} tok in {eng.stats['decode_s']:.2f}s "
          f"({eng.stats['decode_tokens']/max(eng.stats['decode_s'],1e-9):.1f}"
          f" tok/s)")
    if isinstance(eng, ServeEngine):
        s = summarize(reqs, eng)
        print(f"latency: first-token p50={s['p50_first_token_s']*1e3:.1f}ms "
              f"p99={s['p99_first_token_s']*1e3:.1f}ms; total "
              f"p50={s['p50_total_s']*1e3:.1f}ms "
              f"p99={s['p99_total_s']*1e3:.1f}ms; "
              f"done={s['done']} rejected={s['rejected']} "
              f"expired={s['expired']} truncated={s['truncated']}")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {r.out_tokens}")


if __name__ == "__main__":
    main()
