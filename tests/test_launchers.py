"""CLI launcher smoke tests: train/serve entry points on reduced configs."""
import sys

import pytest


def test_train_launcher(tmp_path, capsys):
    from repro.launch.train import main
    main(["--arch", "qwen2-0.5b", "--reduced", "--steps", "4",
          "--batch", "2", "--seq", "16", "--vocab", "64",
          "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"])
    out = capsys.readouterr().out
    assert "loss" in out


def test_train_launcher_compressed(tmp_path, capsys):
    from repro.launch.train import main
    main(["--arch", "internlm2-1.8b", "--reduced", "--steps", "3",
          "--batch", "2", "--seq", "16", "--vocab", "64",
          "--compress-grads", "--ckpt-dir", str(tmp_path)])
    assert "loss" in capsys.readouterr().out


def test_serve_launcher(capsys):
    from repro.launch.serve import main
    main(["--arch", "qwen2-0.5b", "--reduced", "--requests", "2",
          "--prompt-len", "4", "--max-new", "3", "--batch", "2",
          "--context", "16"])
    out = capsys.readouterr().out
    assert "served 2 requests" in out


def test_serve_launcher_record(capsys):
    from repro.launch.serve import main
    main(["--arch", "qwen2-0.5b", "--reduced", "--requests", "3",
          "--prompt-len", "4", "--max-new", "3", "--batch", "2",
          "--context", "16", "--kv-block-size", "8", "--record"])
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("record: "))
    parts = dict(p.split("=") for p in line[len("record: "):].split())
    assert "-" not in parts.values() and parts.pop("dropped") == "0"
    assert float(parts["decode_device_ms"]) > 0


def test_serve_launcher_fused_tensor_parallel(capsys):
    from repro.launch.serve import main
    main(["--arch", "qwen2-0.5b", "--reduced", "--requests", "2",
          "--prompt-len", "4", "--max-new", "3", "--batch", "2",
          "--context", "16", "--kv-block-size", "8",
          "--decode-kernel", "fused", "--tensor-parallel"])
    assert "served 2 requests" in capsys.readouterr().out


def test_serve_launcher_quantized(capsys):
    from repro.launch.serve import main
    main(["--arch", "qwen2-0.5b", "--reduced", "--requests", "1",
          "--prompt-len", "4", "--max-new", "3", "--quantized",
          "--context", "16"])
    assert "quantized=True" in capsys.readouterr().out
