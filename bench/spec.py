"""Find everything a cell needs by name, from data files only.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration's file (its ``file`` entry) holds the model sizes under the
published config's own keys plus the engine settings of the deployment;
``bench/traffic/<traffic>.json`` holds the mix's parameters, read by the
general generator (``bench/mixgen.py``), or ``bench/traffic/<traffic>.py``
holds a generator of the mix's own; ``bench/cells/<cell>.json`` holds what
belongs to the pairing alone (slots, context, offered load, the correctness
limit).  A new cell is new files plus a ``workloads`` entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable

ROOT = Path(__file__).resolve().parents[1]

# published config key -> repro.nn ArchConfig field
ARCH_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "qkv_bias": "qkv_bias",
}


@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    bench: dict            # the whole BENCHMARK.json
    workload: dict         # its ``workloads`` entry
    config: dict           # the configuration file
    traffic: dict          # the traffic mix's parameters ({} if none)
    cell: dict             # the cell file
    generator: Callable    # (mix | None, load, seed, seconds, vocab) -> items

    @property
    def engine(self) -> dict:
        """Engine settings: the configuration's, then the cell's own."""
        eng = dict(self.config.get("engine", {}))
        eng.update(self.cell.get("engine", {}))
        return eng

    @property
    def ramp_s(self) -> float:
        """Seconds of the mix served as set-up before the window opens."""
        return float(self.traffic.get("ramp_s", 0.0))

    def schedule(self, seed: int, seconds: float, vocab: int,
                 load: dict | None = None) -> list:
        """The requests (``bench.mixgen.Item``) of one run of ``seconds``,
        by due time, at the cell's offered load or at ``load``."""
        return self.generator(self.traffic or None, load or self.cell["load"],
                              seed, seconds, vocab)

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = _read(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    wl = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    mixes = root / "bench" / "traffic"
    params = mixes / f"{wl['traffic']}.json"
    code = mixes / f"{wl['traffic']}.py"
    if not (params.exists() or code.exists()):
        raise SystemExit(f"no traffic mix {wl['traffic']!r} in {mixes}")
    return Cell(name=name, root=root, bench=bench, workload=wl,
                config=_read(root / conf["file"]),
                traffic=_read(params) if params.exists() else {},
                cell=_read(root / "bench" / "cells" / f"{name}.json"),
                generator=_generator(code) if code.exists() else _mixgen())


def _mixgen():
    from bench import mixgen
    return mixgen.generate


def _generator(path: Path):
    """``generate`` of a mix's own generator file, loaded by its path."""
    name = "bench_traffic_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


def arch_config(config: dict):
    """The ``repro.nn`` ArchConfig the configuration file describes: the
    registered arch id with every size the file states written over it."""
    from repro.nn.types import get_config
    base = get_config(config["arch"])
    over = {field: config[key] for key, field in ARCH_KEYS.items()
            if key in config}
    if "head_dim" in config:
        over["head_dim"] = config["head_dim"]
    return dataclasses.replace(base, **over)
