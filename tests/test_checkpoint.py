"""Checkpoint fault-tolerance coverage (`repro.checkpoint.ckpt`):
sync + async round-trips, restore onto a *smaller* mesh via re-derived
shardings, torn-write detection (a corrupted newest step is skipped in
favour of the previous durable one), simulated mid-write crashes, and
retention over valid steps only."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import substrate
from repro.checkpoint import (CheckpointManager, SimulatedCrash, latest_step,
                              restore_checkpoint, save_checkpoint,
                              tear_checkpoint, valid_steps)
from repro.ft import plan_rescale, rescale_rules
from repro.parallel.sharding import PV, param_shardings


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(4,)).astype(np.float32)),
        "half": jnp.asarray(rng.normal(size=(4, 4))).astype(jnp.bfloat16),
        "step": jnp.asarray(3, jnp.int32),
    }


def _assert_trees_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# round trips
# ---------------------------------------------------------------------------

def test_sync_round_trip(tmp_path):
    tree = _tree()
    save_checkpoint(tmp_path, tree, step=7, extra={"data_cursor": 7})
    got, step, extra = restore_checkpoint(tmp_path, tree)
    assert step == 7
    assert extra == {"data_cursor": 7}
    _assert_trees_equal(got, tree)                 # incl. bf16 leaf bitwise


def test_async_round_trip_and_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    trees = {s: _tree(seed=s) for s in (1, 2, 3)}
    for s in (1, 2, 3):
        mgr.save_async(trees[s], step=s)
    mgr.wait()
    assert valid_steps(tmp_path) == [2, 3]         # keep=2 pruned step 1
    got, step, _ = restore_checkpoint(tmp_path, trees[3])
    assert step == 3
    _assert_trees_equal(got, trees[3])


def test_manifest_records_leaf_sizes(tmp_path):
    d = save_checkpoint(tmp_path, _tree(), step=0)
    manifest = json.loads((d / "manifest.json").read_text())
    for i, meta in enumerate(manifest["leaves"]):
        f = d / f"leaf_{i:05d}.npy"
        assert meta["nbytes"] == f.stat().st_size


# ---------------------------------------------------------------------------
# torn writes + simulated crashes
# ---------------------------------------------------------------------------

def test_torn_checkpoint_is_skipped(tmp_path):
    trees = {s: _tree(seed=s) for s in (1, 2)}
    for s in (1, 2):
        save_checkpoint(tmp_path, trees[s], step=s)
    assert latest_step(tmp_path) == 2
    tear_checkpoint(tmp_path, step=2)              # truncate a leaf file
    assert valid_steps(tmp_path) == [1]
    assert latest_step(tmp_path) == 1
    # step=None restores the previous durable step, not the torn one
    got, step, _ = restore_checkpoint(tmp_path, trees[1])
    assert step == 1
    _assert_trees_equal(got, trees[1])
    # asking for the torn step explicitly is a loud error naming survivors
    with pytest.raises(ValueError, match=r"torn.*valid steps: \[1\]"):
        restore_checkpoint(tmp_path, trees[2], step=2)


def test_simulated_crash_leaves_only_tmp(tmp_path):
    save_checkpoint(tmp_path, _tree(seed=1), step=1)
    with pytest.raises(SimulatedCrash):
        save_checkpoint(tmp_path, _tree(seed=2), step=2,
                        crash_after_leaves=1)
    names = sorted(p.name for p in pathlib.Path(tmp_path).glob("step_*"))
    assert names == ["step_00000001", "step_00000002.tmp"]
    assert latest_step(tmp_path) == 1              # readers never see .tmp
    # a retried save of the same step succeeds over the stale .tmp
    save_checkpoint(tmp_path, _tree(seed=2), step=2)
    assert latest_step(tmp_path) == 2


def test_gc_keeps_durable_over_newer_torn(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=1)
    for s in (1, 2):
        save_checkpoint(tmp_path, _tree(seed=s), step=s)
    tear_checkpoint(tmp_path, step=2)
    mgr._gc()
    # retention counts valid steps only: the torn 2 must not evict 1,
    # and torn dirs older than the newest durable step are removed
    assert valid_steps(tmp_path) == [1]
    save_checkpoint(tmp_path, _tree(seed=3), step=3)
    mgr._gc()
    assert valid_steps(tmp_path) == [3]
    assert not (pathlib.Path(tmp_path) / "step_00000002").exists()


def test_empty_dir_has_no_latest(tmp_path):
    assert latest_step(tmp_path) is None
    assert valid_steps(tmp_path) == []


# ---------------------------------------------------------------------------
# elastic restore onto a smaller mesh (8 fake devices)
# ---------------------------------------------------------------------------

def test_restore_onto_smaller_mesh(tmp_path):
    defs = {"w": PV((16, 8), jnp.float32, ("fsdp", "model")),
            "b": PV((8,), jnp.float32, ("model",))}
    devices = jax.devices()
    big = substrate.make_mesh((4, 2), ("data", "model"), devices=devices)
    from repro.parallel.sharding import default_rules
    big_rules = default_rules(big, batch=8)

    rng = np.random.default_rng(0)
    vals = {k: rng.normal(size=d.shape).astype(np.float32)
            for k, d in defs.items()}
    big_sh = param_shardings(defs, big_rules)
    placed = {k: jax.device_put(vals[k], big_sh[k]) for k in defs}
    save_checkpoint(tmp_path, placed, step=5)

    # host 0 (devices 0-3) dies: re-derive shardings on the survivor mesh
    plan = plan_rescale(old_devices=8, lost_hosts=1, devices_per_host=4,
                        mesh_axes=(4, 2), global_batch=8, restore_step=5)
    mesh, rules = rescale_rules(plan, [0], 4)
    small_sh = param_shardings(defs, rules)
    like = {k: jax.ShapeDtypeStruct(d.shape, d.dtype)
            for k, d in defs.items()}
    got, step, _ = restore_checkpoint(tmp_path, like, shardings=small_sh)

    assert step == 5
    for k in defs:
        np.testing.assert_array_equal(np.asarray(got[k]), vals[k])
        used = {d.id for d in got[k].sharding.device_set}
        assert used <= {4, 5, 6, 7}, f"{k} landed on a dead host: {used}"
    assert dict(got["w"].sharding.mesh.shape) == {"data": 2, "model": 2}


# ---------------------------------------------------------------------------
# crash-atomic writes (real-SIGKILL torn states, not just simulated ones)
# ---------------------------------------------------------------------------

def test_truncated_manifest_is_invalid(tmp_path):
    """A manifest cut mid-byte (power loss after rename, before the data
    hit disk) must fail the validity gate, not crash restore."""
    save_checkpoint(tmp_path, _tree(), step=1)
    save_checkpoint(tmp_path, _tree(1), step=2)
    man = tmp_path / "step_00000002" / "manifest.json"
    man.write_bytes(man.read_bytes()[: len(man.read_bytes()) // 2])
    assert valid_steps(tmp_path) == [1]
    assert latest_step(tmp_path) == 1


def test_renamed_but_unsynced_leaf_is_invalid(tmp_path):
    """Model the rename-durable-but-data-lost window: the leaf file name
    exists (dir entry synced) but its bytes were never flushed, so the
    file is empty.  The byte-size gate must reject the step."""
    save_checkpoint(tmp_path, _tree(), step=1)
    save_checkpoint(tmp_path, _tree(1), step=2)
    (tmp_path / "step_00000002" / "leaf_00000.npy").write_bytes(b"")
    assert valid_steps(tmp_path) == [1]


def test_after_leaf_hook_sees_durable_prefix(tmp_path):
    """``after_leaf(i)`` fires only once leaf ``i`` is published: at each
    callback the staging dir holds exactly leaves 0..i and no manifest —
    the window where a SIGKILL produces a torn (and rejected) step."""
    tree = _tree()
    n = len(jax.tree.leaves(tree))
    seen = []

    def hook(i):
        stage = tmp_path / "step_00000001.tmp"
        leaves = sorted(p.name for p in stage.glob("leaf_*.npy"))
        assert leaves == [f"leaf_{j:05d}.npy" for j in range(i + 1)]
        assert not (stage / "manifest.json").exists()
        assert not list(stage.glob("*.part")), "unpublished temp visible"
        seen.append(i)

    save_checkpoint(tmp_path, tree, step=1, after_leaf=hook)
    assert seen == list(range(n))
    assert valid_steps(tmp_path) == [1]


def test_publish_leaves_no_part_turds(tmp_path):
    """Every file goes through the .part-then-replace protocol; after a
    clean save no temp names survive anywhere under the step dir."""
    save_checkpoint(tmp_path, _tree(), step=3)
    assert not list(tmp_path.rglob("*.part"))
    assert not list(tmp_path.glob("*.tmp"))
    got, step, _ = restore_checkpoint(tmp_path, _tree())
    assert step == 3
    _assert_trees_equal(got, _tree())
