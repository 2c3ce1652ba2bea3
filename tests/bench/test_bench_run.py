"""End-to-end CPU rehearsal of ``bench/run.py`` on a tiny configuration
(interpret-mode kernels), through the test-only entry that skips the look
for a chip; the real command's refusal without one; and a cell, a
configuration, a traffic mix and a per-layer metric added as new files."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import tiny
from bench import run

REPO = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "tiny-qwen2.chat", "--seed", "501",
        "--seconds", "4"]


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("bench"))


def test_untraced_run_prints_end_to_end_metrics(tree, capsys):
    run.main(ARGS + ["--trace", "0"], require_tpu=False, root=tree)
    line = last_line(capsys)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 16
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                    "output_tok_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert line["device"]["count"] == 1
    assert {"platform", "kind", "memory_peak_bytes"} <= set(line["device"])
    gap = line["checks"]["mean_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_traced_run_prints_per_layer_metrics(tree, capsys):
    run.main(ARGS + ["--trace", "1"], require_tpu=False, root=tree)
    line = last_line(capsys)
    assert line["correct"] is True
    got = set(line["metrics"])
    # the CPU has no device plane: the trace's readers find nothing
    assert {"queue_wait_p95_ms", "decode_batch_occupancy",
            "kv_blocks_used_share", "prefill_dispatch_ms",
            "decode_dispatch_ms", "serve_mfu"} <= got
    assert "paged_attention_roofline" not in got
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < line["metrics"]["decode_batch_occupancy"]["value"] <= 100


def test_added_files_make_a_new_cell(tmp_path, capsys):
    """A configuration, a mix, a metric and a cell, each a new file."""
    per_layer = [{"name": "decode_steps_count", "unit": "steps",
                  "better": "higher", "source": "program_counter",
                  "layer": "scheduler", "moves": "output_tok_s"}]
    root = tiny.make_tree(tmp_path, cell="tiny-qwen2.other",
                          per_layer=per_layer)
    (root / "bench/metrics/decode_steps_count.py").write_text(
        "def read(ctx):\n    return ctx.rec.delta('decode_steps') or None\n")
    cfg = dict(tiny.TINY_CONFIG, hidden_size=32, num_attention_heads=2,
               num_key_value_heads=1)
    (root / "bench/configs/tiny-other.json").write_text(json.dumps(cfg))
    mix = dict(tiny.TINY_MIX, output_len={"dist": "uniform", "min": 2,
                                          "max": 6})
    (root / "bench/traffic/tinyshort.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-other", "source": "test",
                             "file": "bench/configs/tiny-other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] = [{"name": "tiny-qwen2.other", "config": "tiny-other",
                           "traffic": "tinyshort", "chips": 1, "why": "t"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    run.main(["--workload", "tiny-qwen2.other", "--seed", "4",
              "--seconds", "2", "--trace", "1"], require_tpu=False, root=root)
    line = last_line(capsys)
    assert "mean_logit_gap" in line["checks"]
    assert list(line["metrics"]) == ["decode_steps_count"]
    assert line["metrics"]["decode_steps_count"]["value"] > 0


def test_a_mix_with_a_generator_of_its_own(tmp_path, capsys):
    """A mix the general generator cannot describe (bursts of four at once)
    added as ``bench/traffic/<mix>.py`` alone, with no JSON beside it."""
    root = tiny.make_tree(tmp_path)
    (root / "bench/traffic/tinyburst.py").write_text(
        "import numpy as np\n"
        "from bench.mixgen import Item\n\n\n"
        "def generate(mix, load, seed, seconds, vocab):\n"
        "    assert mix is None\n"
        "    rng = np.random.default_rng(seed)\n"
        "    n = int(load['rate_per_s'] * seconds) // 4 * 4\n"
        "    return [Item(seconds * (i // 4) * 4 / n,\n"
        "                 rng.integers(0, vocab, 24, dtype=np.int32), 8)\n"
        "            for i in range(n)]\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["traffic"] = "tinyburst"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    run.main(ARGS + ["--trace", "0"], require_tpu=False, root=root)
    line = last_line(capsys)
    assert line["correct"] is True
    assert line["attempted"] == 16 and line["failed"] == 0


def test_real_command_refuses_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench/run.py"),
                        "--workload", "qwen2-0.5b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == run.NO_CHIP
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A tree with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-0.5b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
