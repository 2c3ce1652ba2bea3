"""The harness on a cell of several chips: the mesh it hands the engine,
the weights and the float32 reference placed over it, the engine settings
passed whole, and the per-chip readers.  What needs four devices runs in
one subprocess of four CPU devices (``mesh_checks.py``); the rest here."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import tiny
from bench import run, spec, weights

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(HERE / "mesh_checks.py"),
         str(tmp_path_factory.mktemp("mesh"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


def mha_cfg():
    return spec.arch_config(tiny.MHA_CONFIG)


def test_weights_over_a_mesh_equal_one_devices(four):
    """Bit for bit, each leaf held where tensor-parallel serving keeps it:
    a quarter of the heads or FFN columns a device, the rest whole."""
    w = four["weights"]
    assert all(leaf["equal"] for leaf in w.values())
    assert w["['layers']['attn']['wq']"]["spec"] == [None, None, "model"]
    assert w["['layers']['attn']['bk']"]["spec"] == [None, "model"]
    assert w["['layers']['mlp']['wd']"]["spec"] == [None, "model", None]
    for name, leaf in w.items():
        shards = leaf["shards"]
        assert len(shards) == 1            # every device holds one shape
        split = [s // n for s, n in zip(leaf["shape"], shards[0])]
        sharded = [i for i, d in enumerate(leaf["spec"]) if d == "model"]
        assert split == [4 if i in sharded else 1
                         for i in range(len(split))], name


# sha256 of the leaves' bytes, in tree order, that the generator made at
# the tiny MHA size on the CPU before it learnt to place leaves on a mesh
# (computed from that earlier tree): the one-chip weights stay as they were
PARENT_SHA256 = {
    0: "a425e7abaf0c383b13b13fdca5922424157f481ef4f5c4867549856004981fcc",
    2 ** 40 + 3:
        "e46e28dc4a3b3fd6e0e85f5f441b9ddc6a6a156cadab72ad6c0e64272d5bb4c1",
}


@pytest.mark.parametrize("seed", sorted(PARENT_SHA256))
def test_one_chip_weights_are_the_parents(seed):
    h = hashlib.sha256()
    for x in jax.tree.leaves(weights.make_weights(mha_cfg(), seed)):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == PARENT_SHA256[seed]


def test_reference_gaps_over_the_mesh(four):
    """The sharded pass sums each row-parallel product (``wo``, ``wd``) as
    four float32 partial sums, in another order than one device does, so
    the gaps agree to a few float32 roundings of the logits' scale, not bit
    for bit: 1e-5 of the largest gap."""
    one = np.array(four["gaps"]["one"])
    mesh = np.array(four["gaps"]["mesh"])
    assert one.shape == mesh.shape == (60,) and one.max() > 1.0
    np.testing.assert_allclose(mesh, one, rtol=0,
                               atol=1e-5 * np.abs(one).max())


def test_placement_rule_is_the_engines():
    from repro.runtime.serve import _tp_param_specs
    w = jax.eval_shape(lambda: weights.make_weights(mha_cfg(), 0))
    mine = weights.specs(w, "model")
    theirs = _tp_param_specs(w, "model")
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: a == b, mine, theirs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    assert len(flat) == len(jax.tree.leaves(w)) and all(flat)


@pytest.mark.parametrize("chips", ["1", "2", "4"])
def test_setup_hands_the_engine_the_cells_chips(four, chips):
    """A mesh over exactly the first ``chips`` devices (none for one), the
    weights on those devices, and every engine setting of the cell: the
    harness's own keys as ``build_engine``'s keywords, the rest whole."""
    got = four["setup"][chips]
    n = int(chips)
    want = four["all_devices"][:n]
    assert got["mesh_devices"] == (None if n == 1 else want)
    assert got["mesh_axes"] == (None if n == 1 else ["model"])
    assert got["weight_devices"] == want
    eng = dict(tiny.MHA_CONFIG["engine"])
    del eng["sampling"], eng["eos"]
    assert got["kwargs"] == dict(eng, temperature=0.0,
                                 **{k: tiny.TINY_CELL[k]
                                    for k in ("max_batch", "max_context")})
    assert ("mesh" in got["keys"]) == (n > 1)


def test_four_chip_cell_runs_correct(four):
    """A whole run of the tensor-parallel cell over four devices."""
    line = four["run"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    gap = line["checks"]["mean_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_exchange_left_out_is_not_correct(four):
    """The decode's sum over the chips dropped: each chip's partial
    attention and FFN outputs go on alone, and the comparison sees it."""
    line = four["no_exchange"]
    assert line["correct"] is False
    gap = line["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("engine, named", [
    ({"kv_blok_size": 16}, "kv_blok_size"),
    ({"sampling": "nucleus"}, "sampling"),
    ({"max_batch": 8}, "max_batch"),       # the cell file's, not the engine's
])
def test_unknown_engine_setting_is_refused(tmp_path, engine, named):
    from repro.launch.serve import build_engine
    root = tiny.make_tree(tmp_path)
    cell = spec.load_cell("tiny-qwen2.chat", root)
    cell.cell["engine"] = engine
    with pytest.raises(SystemExit, match=named):
        run.engine_kwargs(cell.engine, build_engine)
    with pytest.raises(SystemExit, match=named):
        run.setup(cell, 1)


def window():
    """A hand-built record: two prefill dispatches, three decode steps, a
    trace with the kernel's device time."""
    rec = SimpleNamespace(
        window_s=2.0, trace_t=(0.0, 2.0),
        prefill_log=[(0.1, [(0, 32, False), (0, 20, True)]),
                     (0.2, [(32, 16, True)])],
        decode_log=[(0.5, [21, 49], 2), (0.6, [22, 50], 2),
                    (1.5, [23, 51, 9], 3)],
        stats0={"decode_steps": 0, "decode_tokens": 0, "decode_s": 0.0,
                "prefill_dispatches": 0, "prefill_s": 0.0},
        stats1={"decode_steps": 3, "decode_tokens": 7, "decode_s": 0.09,
                "prefill_dispatches": 2, "prefill_s": 0.1},
        due=[SimpleNamespace(status="done", stats={"queue_s": 0.01},
                             due=0.0)], t1=2.0)
    rec.delta = lambda k: rec.stats1[k] - rec.stats0[k]
    trace = {"window_s": 2.0, "busy_s": 1.5,
             "op_s": {"paged_attention_kernel.12": 1e-6}}
    return rec, trace


@pytest.mark.parametrize("name, share", [
    ("serve_mfu", 0.25), ("paged_attention_roofline", 0.25),
    ("device_idle_share", 1), ("decode_batch_occupancy", 1),
    ("kv_blocks_used_share", 1), ("decode_dispatch_ms", 1),
    ("prefill_dispatch_ms", 1), ("queue_wait_p95_ms", 1)])
def test_readers_per_chip(name, share):
    """Whole-model work over four chips' peaks reads a quarter of one
    chip's; what is counted per step or per device reads the same."""
    rec, trace = window()
    sizes = weights.sizes(mha_cfg())
    read = run._reader(run.ROOT, name)
    ctx = lambda chips: run.Context(                          # noqa: E731
        rec=rec, sizes=sizes, max_batch=4, pool_blocks=64, kv_itemsize=2,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        trace=trace, chips=chips)
    one, four = read(ctx(1)), read(ctx(4))
    assert one is not None and one > 0
    assert four == pytest.approx(share * one, rel=1e-12)
