"""Substrate tests: optimizer, data pipeline, checkpointing, FT, serving."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import DataConfig, SyntheticCorpus, make_pipeline
from repro.checkpoint import CheckpointManager, restore_checkpoint, \
    save_checkpoint
from repro.checkpoint.ckpt import latest_step
from repro.ft import (HeartbeatMonitor, RestartPolicy, StragglerMitigator,
                      plan_rescale)
from repro.train import OptConfig, adamw_init, adamw_update, lr_schedule


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def _quad_params():
    return {"w": jnp.array([1.5, -2.0, 3.0]), "b": jnp.zeros(())}


def test_adamw_converges_quadratic():
    cfg = OptConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                    total_steps=300, clip_norm=0.0)
    params = _quad_params()
    state = adamw_init(params, cfg)
    for _ in range(300):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2) + p["b"] ** 2)(params)
        params, state, _ = adamw_update(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_stacked_leaf_matches_flat():
    """The fori_loop chunked path must produce identical updates to the
    plain path (stacked leaf with first dim >= 8)."""
    cfg = OptConfig(lr=0.01, warmup_steps=1, total_steps=10)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(12, 4, 5)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(12, 4, 5)), jnp.float32)
    ps, ss = {"w": w}, adamw_init({"w": w}, cfg)
    pf, sf = {"w": w[0]}, adamw_init({"w": w[0]}, cfg)
    ps2, ss2, _ = adamw_update(ps, {"w": g}, ss, cfg)
    # same slice updated standalone (clip differs through gnorm; disable)
    cfg2 = OptConfig(lr=0.01, warmup_steps=1, total_steps=10, clip_norm=0.0)
    ps3, _, _ = adamw_update(ps, {"w": g}, adamw_init(ps, cfg2), cfg2)
    pf3, _, _ = adamw_update(pf, {"w": g[0]}, adamw_init(pf, cfg2), cfg2)
    np.testing.assert_allclose(np.asarray(ps3["w"][0]),
                               np.asarray(pf3["w"]), rtol=1e-6)


def test_bf16_state_roundtrip():
    # lr must exceed the bf16 ulp at 1.0 (0.0078): without an fp32 master,
    # smaller updates round away — the documented trade of the giant configs.
    cfg = OptConfig(lr=0.1, state_dtype=jnp.bfloat16, master_fp32=False,
                    math_dtype=jnp.bfloat16, warmup_steps=1, total_steps=10)
    params = {"w": jnp.ones((16, 8), jnp.bfloat16)}
    state = adamw_init(params, cfg)
    g = {"w": jnp.full((16, 8), 0.5, jnp.bfloat16)}
    p2, s2, _ = adamw_update(params, g, state, cfg)
    assert p2["w"].dtype == jnp.bfloat16
    assert s2["params"]["w"]["m"].dtype == jnp.bfloat16
    assert float(p2["w"][0, 0]) < 1.0            # moved in -grad direction


def test_lr_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(cfg, jnp.int32(s))) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0
    assert lrs[99] < lrs[50] < lrs[12]
    assert lrs[99] >= 0.099                      # floor ~10%


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_restartable():
    cfg = DataConfig(vocab_size=100, seq_len=32, global_batch=4, seed=7)
    c1 = SyntheticCorpus(cfg)
    c2 = SyntheticCorpus(cfg)
    np.testing.assert_array_equal(c1.batch(5), c2.batch(5))
    assert not np.array_equal(c1.batch(5), c1.batch(6))
    # restart mid-stream == fresh stream at that step
    it = make_pipeline(cfg, start_step=3)
    np.testing.assert_array_equal(next(it), c1.batch(3))
    np.testing.assert_array_equal(next(it), c1.batch(4))


def test_data_host_sharding_partitions_global_batch():
    whole = SyntheticCorpus(
        DataConfig(vocab_size=50, seq_len=16, global_batch=8, seed=1))
    parts = [SyntheticCorpus(
        DataConfig(vocab_size=50, seq_len=16, global_batch=8, seed=1,
                   n_hosts=4, host_id=h)) for h in range(4)]
    got = np.concatenate([p.batch(2) for p in parts])
    np.testing.assert_array_equal(got, whole.batch(2))


@given(st.integers(0, 30), st.integers(2, 6))
@settings(max_examples=10, deadline=None)
def test_data_tokens_in_range(step, batch):
    cfg = DataConfig(vocab_size=64, seq_len=24, global_batch=batch, seed=3)
    b = SyntheticCorpus(cfg).batch(step)
    assert b.shape == (batch, 24)
    assert b.min() >= 0 and b.max() < 64


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16),
                  "step": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(tmp_path, t, step=42, extra={"note": "x"})
    assert latest_step(tmp_path) == 42
    got, step, extra = restore_checkpoint(tmp_path, t)
    assert step == 42 and extra["note"] == "x"
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(t["a"]))
    assert got["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_atomicity_and_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    t = _tree()
    for s in (1, 2, 3):
        mgr.save_async(t, s)
        mgr.wait()
    steps = sorted(int(p.name.split("_")[1]) for p in
                   pathlib.Path(tmp_path).glob("step_*"))
    assert steps == [2, 3]
    # a leftover .tmp dir must never be picked up
    (pathlib.Path(tmp_path) / "step_00000099.tmp").mkdir()
    assert latest_step(tmp_path) == 3


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore onto a different 'mesh' (here: different sharding = None ->
    plain arrays; the reshard path is device_put with target shardings)."""
    t = {"w": jnp.arange(64.0).reshape(8, 8)}
    save_checkpoint(tmp_path, t, step=1)
    got, _, _ = restore_checkpoint(tmp_path, t, shardings=None)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(t["w"]))


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_detects_dead_host():
    clock = [0.0]
    mon = HeartbeatMonitor(n_hosts=3, timeout_s=10.0,
                           clock=lambda: clock[0])
    for h in range(3):
        mon.beat(h, step=1, step_s=1.0)
    clock[0] = 5.0
    mon.beat(0, 2)
    mon.beat(1, 2)
    clock[0] = 12.0
    assert mon.dead_hosts() == [2]
    assert not mon.healthy()


def test_straggler_needs_persistence():
    s = StragglerMitigator(threshold=1.5, patience=2)
    base = {0: 1.0, 1: 1.0, 2: 1.0, 3: 2.0}
    assert s.update(base) == []                   # first strike
    assert s.update(base) == [3]                  # persistent -> flagged
    assert s.update({0: 1.0, 1: 1.0, 2: 1.0, 3: 1.0}) == []


def test_plan_rescale_keeps_model_axis():
    plan = plan_rescale(old_devices=256, lost_hosts=2, devices_per_host=8,
                        mesh_axes=(16, 16), global_batch=256,
                        restore_step=100)
    assert plan.new_mesh_shape[1] == 16            # model axis intact
    assert plan.new_devices == plan.new_mesh_shape[0] * 16
    assert plan.new_global_batch % plan.new_mesh_shape[0] == 0


def test_restart_policy_backoff():
    p = RestartPolicy(max_restarts=3, backoff_s=1.0)
    d = [p.next_delay() for _ in range(3)]
    assert d == [1.0, 2.0, 4.0]
    assert not p.should_restart()


# ---------------------------------------------------------------------------
# end-to-end: tiny training run learns; checkpoint/restart resumes exactly
# ---------------------------------------------------------------------------

def test_train_loss_decreases_and_resumes(tmp_path):
    from repro.launch.train import run
    out1 = run("mamba2-370m", smoke=True, steps=16, global_batch=4,
               seq_len=32, lr=5e-3, ckpt_dir=str(tmp_path), ckpt_every=8,
               log_every=100)
    first, last = out1["losses"][0], out1["final_loss"]
    assert last < first, (first, last)
    # resume from step 16's checkpoint... (ckpt at 8 and 16)
    out2 = run("mamba2-370m", smoke=True, steps=20, global_batch=4,
               seq_len=32, lr=5e-3, ckpt_dir=str(tmp_path), ckpt_every=8,
               log_every=100)
    assert out2["start_step"] == 16
    assert len(out2["losses"]) == 4


def test_compile_cache_env_wins_else_fixed_path_in_checkout(monkeypatch):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
        assert compile_cache.enable() == "/elsewhere/jax"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable()
        root = pathlib.Path(__file__).resolve().parents[1]
        assert got == str(root / ".jax_cache") == compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_make_mesh_axes_are_auto():
    from jax.sharding import AxisType
    from repro import substrate
    devs = jax.devices()
    for mesh in (substrate.make_mesh((1, 1), ("data", "model")),
                 substrate.make_mesh((1, 1), ("data", "model"),
                                     devices=devs[-1:])):
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert substrate.make_mesh((1,), ("x",), devices=devs[-1:]) \
        .devices.item() == devs[-1]


def test_child_env_pins_cpu(monkeypatch):
    """Check-module children never reach for an accelerator the parent
    may hold."""
    from repro.testing.subproc import pinned_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert pinned_env()["JAX_PLATFORMS"] == "cpu"


def test_serving_engine_batches_requests():
    from repro.launch.serve import run
    finished = run("deepseek-7b", smoke=True, n_requests=5, max_new=8,
                   max_batch=3, max_seq=64)
    assert len(finished) == 5
    assert all(1 <= len(r.out) <= 8 for r in finished)
