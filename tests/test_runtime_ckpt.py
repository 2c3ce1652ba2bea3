"""Fault tolerance: checkpoint/restart determinism, failure injection,
straggler detection, elastic restore, data pipeline contracts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.data.tokens import TokenPipeline
from repro.nn import Model, get_config
from repro.optim.adamw import AdamW
from repro.runtime.step import make_train_step
from repro.runtime.train import TrainConfig, TrainLoop


@pytest.fixture()
def tiny():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=2, vocab=64)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    state = opt.init(params)
    step = jax.jit(make_train_step(m, opt))
    pipe = TokenPipeline(vocab=64, seq_len=16, global_batch=4)
    return params, state, step, pipe


def _leaf0(tree):
    return np.asarray(jax.tree_util.tree_leaves(tree)[0], np.float32)


def test_restart_reproduces_uninterrupted_run(tiny, tmp_path):
    params, state, step, pipe = tiny
    cfg = TrainConfig(total_steps=12, ckpt_every=4,
                      ckpt_dir=str(tmp_path / "a"), log_every=50)
    p1, _ = TrainLoop(cfg, step, pipe).run(params, state)

    # same training, but a simulated node failure at step 9
    boom = {"armed": True}

    def failure_hook(s):
        if s == 9 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("simulated node failure")

    cfg2 = TrainConfig(total_steps=12, ckpt_every=4,
                       ckpt_dir=str(tmp_path / "b"), log_every=50)
    loop = TrainLoop(cfg2, step, pipe, failure_hook=failure_hook)
    p2, _ = loop.run(params, state)
    assert loop.restarts == 1
    np.testing.assert_allclose(_leaf0(p1), _leaf0(p2), rtol=1e-6)


def test_checkpoint_atomic_and_pruned(tiny, tmp_path):
    params, state, _, _ = tiny
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"params": params})
    assert mgr.all_steps() == [3, 4]
    restored, step, _ = mgr.restore({"params": params})
    assert step == 4
    np.testing.assert_array_equal(_leaf0(restored), _leaf0({"params": params}))


def test_checkpoint_corruption_detected(tiny, tmp_path):
    params, state, _, _ = tiny
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, {"params": {"embed": params["embed"]}})
    import glob, os
    victim = glob.glob(str(tmp_path / "step_1" / "*.npy"))[0]
    arr = np.load(victim)
    np.save(victim, arr.ravel()[: arr.size // 2])   # truncate
    with pytest.raises(Exception):
        mgr.restore({"params": {"embed": params["embed"]}})


def test_async_save_then_restore(tiny, tmp_path):
    params, state, _, _ = tiny
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, {"p": params}, blocking=False)
    mgr.wait()
    _, step, _ = mgr.restore({"p": params})
    assert step == 7


def test_elastic_restore_new_sharding(tiny, tmp_path):
    """Restore places leaves with an explicitly different sharding."""
    params, _, _, _ = tiny
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"p": {"w": params["embed"]}})
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    restored, _, _ = mgr.restore({"p": {"w": params["embed"]}},
                                 shardings={"p": {"w": sh}})
    assert restored["p"]["w"].sharding == sh


def test_straggler_detection(tiny, tmp_path):
    params, state, step, pipe = tiny
    import time
    slow = {"hit": []}

    def failure_hook(s):          # abuse the hook to inject latency
        if s == 10:
            time.sleep(1.0)

    cfg = TrainConfig(total_steps=13, ckpt_every=100,
                      ckpt_dir=str(tmp_path), straggler_factor=3.0,
                      log_every=50)
    loop = TrainLoop(cfg, step, pipe, failure_hook=failure_hook,
                     on_straggler=lambda s, dt, med: slow["hit"].append(s))
    loop.run(params, state)
    assert 10 in slow["hit"]
    assert any(s == 10 for s, _, _ in loop.straggler_steps)


def test_pipeline_determinism_and_sharding():
    p1 = TokenPipeline(vocab=97, seq_len=8, global_batch=8, seed=5)
    p2 = TokenPipeline(vocab=97, seq_len=8, global_batch=8, seed=5)
    np.testing.assert_array_equal(p1.batch(3)["tokens"], p2.batch(3)["tokens"])
    # shards are deterministic and distinct
    s0 = TokenPipeline(vocab=97, seq_len=8, global_batch=8, seed=5,
                       n_shards=2, shard=0)
    s1 = TokenPipeline(vocab=97, seq_len=8, global_batch=8, seed=5,
                       n_shards=2, shard=1)
    assert s0.batch(0)["tokens"].shape == (4, 8)
    assert not np.array_equal(s0.batch(0)["tokens"], s1.batch(0)["tokens"])
    # labels are next-token shifted
    b = p1.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_grad_compression_numerics():
    from repro.optim.compress import pot_compressor, pot_quantize_dequantize
    g = jax.random.normal(jax.random.PRNGKey(0), (256, 64)) * 0.01
    gq = pot_quantize_dequantize(g)
    rel = float(jnp.abs(gq - g).max() / jnp.abs(g).max())
    assert rel < 0.02                      # int8 grid on a PoT scale
    comp = pot_compressor(min_size=10**9)  # everything passes through
    out = comp({"g": g})
    np.testing.assert_array_equal(np.asarray(out["g"]), np.asarray(g))


def test_compressed_psum_shardmap():
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import compressed_psum
    mesh = jax.make_mesh((1,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    f = shard_map(partial(compressed_psum, axis_name="data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P("data"))
    y = f(x)
    rel = float(jnp.abs(y - x).max() / jnp.abs(x).max())
    assert rel < 0.02
