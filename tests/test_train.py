"""Training substrate: optimizer, checkpointing (atomic + reshard),
NaN-guard auto-restore, microbatch accumulation, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.train.checkpoint import CheckpointManager
from repro.train.compression import (compress_with_feedback, compressed_psum,
                                     init_residual, quantize_leaf,
                                     dequantize_leaf)
from repro.train.loop import (StragglerMonitor, Trainer, TrainLoopConfig,
                              make_train_step)
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   init_opt_state, lr_at)


def _quadratic_loss(params, batch):
    return jnp.sum((params["w"] - batch["target"]) ** 2)


def test_adamw_converges_quadratic():
    params = {"w": jnp.ones((8,)) * 5.0}
    opt = init_opt_state(params)
    cfg = OptimizerConfig(lr=0.2, warmup_steps=0, total_steps=200,
                          weight_decay=0.0)
    batch = {"target": jnp.zeros((8,))}
    step = jax.jit(make_train_step(_quadratic_loss, cfg))
    for _ in range(150):
        params, opt, m = step(params, opt, batch)
    assert float(m["loss"]) < 1e-2


def test_lr_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    assert float(lr_at(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(lr_at(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    assert float(lr_at(cfg, jnp.asarray(100))) <= 0.11
    assert float(lr_at(cfg, jnp.asarray(5))) == pytest.approx(0.5)


def test_microbatch_accumulation_matches_full_batch():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)

    def loss(params, batch):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, weight_decay=0.0)
    full = make_train_step(loss, cfg, microbatches=1)
    micro = make_train_step(loss, cfg, microbatches=4)
    p1, _, m1 = full({"w": w}, init_opt_state({"w": w}), {"x": x})
    p2, _, m2 = micro({"w": w}, init_opt_state({"w": w}),
                      {"x": x.reshape(4, 2, 4)})
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-5, atol=1e-6)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)


def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
             "nested": {"b": jnp.ones((4,))}}
    for s in (1, 2, 3):
        mgr.save(s, state, metadata={"step": s})
    assert mgr.latest_step() == 3
    # GC keeps only 2
    steps = [d for d in os.listdir(tmp_path) if d.startswith("step-")]
    assert len(steps) == 2
    restored, meta = mgr.restore(state)
    assert meta["step"] == 3
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(state["a"]))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"a": jnp.ones((4,))})
    with pytest.raises(ValueError):
        mgr.restore({"a": jnp.ones((5,))})


def test_checkpoint_atomicity_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, {"a": jnp.ones((2,))})
    assert not any(d.startswith("tmp-") for d in os.listdir(tmp_path))


def test_nan_guard_restores(tmp_path):
    """Step 5 produces a poisoned batch -> trainer must restore and keep
    the params finite and training running."""
    calls = {"n": 0}

    def loss(params, batch):
        return jnp.sum((params["w"] * batch["x"]) ** 2)

    params = {"w": jnp.ones((4,))}
    loop_cfg = TrainLoopConfig(total_steps=12, ckpt_every=2,
                               ckpt_dir=str(tmp_path), log_every=100,
                               nan_skip_window=2)
    trainer = Trainer(loss, params, OptimizerConfig(lr=0.01,
                                                    warmup_steps=0),
                      loop_cfg, donate=False)

    def batches():
        step = 0
        while True:
            x = np.ones(4, np.float32)
            if step == 5:
                x = x * np.nan
            yield {"x": jnp.asarray(x)}
            step += 1

    hist = trainer.run(batches(), log=lambda s: None)
    assert trainer.nan_events == [5]
    assert np.isfinite(np.asarray(trainer.params["w"])).all()
    assert trainer.step >= 12


def test_straggler_monitor():
    mon = StragglerMonitor(factor=3.0)
    for i in range(10):
        assert not mon.record(i, 0.1)
    assert mon.record(10, 1.0)
    assert mon.flagged == [(10, 1.0)]


# ---------------------------------------------------------------- compression
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31), st.floats(0.01, 1000))
def test_quantize_leaf_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(scale * rng.standard_normal(64), jnp.float32)
    q, s = quantize_leaf(g)
    err = jnp.abs(dequantize_leaf(q, s) - g)
    assert float(err.max()) <= float(s) * 0.5001


def test_error_feedback_accumulates():
    g = {"w": jnp.asarray([1e-4, 1.0, -1.0], jnp.float32)}
    res = init_residual(g)
    total = jnp.zeros((3,))
    for _ in range(100):
        deq, res = compress_with_feedback(g, res)
        total = total + deq["w"]
    # with feedback, the tiny 1e-4 component must not be lost over time
    np.testing.assert_allclose(np.asarray(total / 100),
                               np.asarray(g["w"]), rtol=0.05, atol=2e-5)


def test_compressed_psum_single_device():
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray([0.5, -2.0, 3.0], jnp.float32)}
    f = jax.shard_map(lambda t: compressed_psum(t, "pod"), mesh=mesh,
                      in_specs=(P(),), out_specs=P())
    out = f(g)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               rtol=0.02, atol=0.02)
