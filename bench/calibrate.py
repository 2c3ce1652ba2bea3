"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> \
        --seeds 1,2,...,12 --control-seeds 101,102,103

In one process, one run of ``bench/run.py`` per seed, then one per control
seed with the control switched on: the program's own lower-precision path,
int8 power-of-two weights (``quantized``), the step that would tempt a
later change.  After each run's own lines, one JSON line with the seed and
every number compared.  The lower reading of a number is the largest over
the sound seeds, the upper the smallest over the control's.
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

CONTROL = {"quantized": True}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from bench import run
    for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
        for s in [int(x) for x in seeds.split(",") if x]:
            out = run.main(["--workload", args.workload, "--seed", str(s),
                            "--seconds", str(args.seconds), "--trace", "0"],
                           engine=CONTROL if control else None)
            print(json.dumps({
                "calibrate": args.workload, "seed": s, "control": control,
                "correct": out["correct"],
                **{k: c["value"] for k, c in out["checks"].items()}}),
                flush=True)


if __name__ == "__main__":
    main()
