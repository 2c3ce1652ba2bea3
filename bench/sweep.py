"""Find a cell's knee once, on the chip: serve its traffic at several fixed
rates in one process and print one JSON line per rate with the tails, the
tokens per second, and the queue left at the window's close.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 0.5,1,1.5,2

The knee is the highest rate whose queue stays bounded (few requests left
waiting at the close, queue wait far below the window).  A cell is then
fixed at about four fifths of it in ``bench/cells/<name>.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import loadgen, e2e, run, spec
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    run._devices(cell.workload["chips"], True)
    cfg, eng = run.setup(cell, args.seed)
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        sched = cell.schedule(args.seed + i, args.seconds, cfg.vocab,
                              load={"rate_per_s": rate})
        rec = loadgen.serve_window(eng, sched, args.seconds)
        waiting = len(eng.queue)
        m = e2e.metrics(rec)
        waits = [r.stats.get("queue_s", rec.t1 - r.due) for r in rec.requests]
        print(json.dumps({
            "rate_per_s": rate, **m, "attempted": len(rec.requests),
            "finished": sum(r.status == "done" for r in rec.requests),
            "queued_at_close": waiting,
            "queue_wait_p95_ms": 1e3 * e2e.nearest_rank(waits, 95),
            "decode_steps": rec.delta("decode_steps"),
            "prefill_dispatches": rec.delta("prefill_dispatches"),
            "compiles_in_window": rec.compiles}), flush=True)
        eng.queue.clear()           # start the next load empty
        for slot in list(eng.slots):
            eng.slots.pop(slot)
            eng.cache.release(slot)


if __name__ == "__main__":
    main()
