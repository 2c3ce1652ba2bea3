"""CPU rehearsal of ``chip_smoke.py``: every phase at a reduced size (Pallas
kernels in interpret mode), and the refusal to report a result off-TPU."""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.nn import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lm(arch, **kw):
    return dataclasses.replace(get_config(arch).reduced(), n_layers=2, **kw)


@pytest.mark.parametrize("chips", [[], ["--chips", "4"]])
def test_main_refuses_off_tpu(smoke, capsys, chips):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(chips) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and "platform" not in out


def test_serving_phase(smoke):
    with smoke.static_dispatch():
        out = smoke.serving_phase(_lm("qwen2-0.5b"), prompt_lens=[5, 17, 9, 30],
                                  max_new=4, max_batch=4, max_context=64,
                                  kv_block_size=8, prefill_batch=2,
                                  prefill_chunk=8)
    # off-TPU the kernel reproduces the scan reference bit for bit
    assert out["fused_vs_scan_bitwise"] and out["fused_vs_scan_rel"] == 0.0
    assert out["fused_vs_dense_rel"] <= smoke.FUSED_VS_DENSE_TOL
    for eng in out["engines"].values():
        assert eng["decode_tokens"] == 4 * 3   # the first token is prefill's
    json.dumps(out)                      # the phase line is plain JSON


def test_design_phase(smoke):
    with smoke.static_dispatch():
        out = smoke.design_phase(epochs=1, reps=(1, 2), max_sweeps=1,
                                 n_val=300)
    assert set(out) == {"val300", "val600"}
    assert all(0 < row["q"] <= 16 for row in out.values())


def test_multi_chip_phases_on_one_device(smoke):
    """The --chips 4 phases' control flow, on the one CPU device."""
    with smoke.static_dispatch():
        tp = smoke.tp_decode_phase(_lm("internlm2-1.8b", dtype="float32"),
                                   prompt_lens=[6, 11], max_new=3,
                                   max_batch=2, max_context=32,
                                   kv_block_size=8, prefill_batch=2,
                                   prefill_chunk=8)
        sw = smoke.sharded_sweep_phase(epochs=1, rep=2, n_val=200)
    assert tp["tokens"] == 6
    assert set(sw) == {"pallas", "jnp"}
