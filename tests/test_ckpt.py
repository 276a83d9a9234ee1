"""Checkpoint substrate: exact roundtrips (incl. bfloat16), incremental
reuse, async saves, resharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (AsyncCheckpointer, CheckpointCorruptError,
                        load_latest_good, load_snapshot, reshard_params,
                        save_snapshot, snapshot_candidates)
from repro.core.state import GuestState, TaskSnapshot


def _snap(step=0, versions=None, val=1.0):
    buffers = {
        "params": {"w": np.full((4, 4), val, np.float32),
                   "b": jnp.ones((3,), jnp.bfloat16) * val},
        "opt_state": {"m": (np.zeros(2, np.int64),)},
    }
    return TaskSnapshot(task_id="t", guest_state=GuestState(step=step),
                        buffers=buffers, step=step,
                        versions=versions or {"params": 1, "opt_state": 1})


def test_roundtrip_exact(tmp_path):
    p = str(tmp_path / "ck")
    save_snapshot(p, _snap(step=5))
    snap, image = load_snapshot(p)
    assert snap.step == 5
    assert snap.guest_state.step == 5
    np.testing.assert_array_equal(snap.buffers["params"]["w"],
                                  np.full((4, 4), 1.0))
    b = snap.buffers["params"]["b"]
    assert b.dtype == jnp.bfloat16                # dtype survives npz
    np.testing.assert_array_equal(np.asarray(b, np.float32), np.ones(3))
    assert isinstance(snap.buffers["opt_state"]["m"], tuple)  # structure


def test_incremental_reuses_unchanged_buffers(tmp_path):
    p1 = str(tmp_path / "c1")
    p2 = str(tmp_path / "c2")
    s1 = _snap(step=1, versions={"params": 3, "opt_state": 3})
    stats1 = save_snapshot(p1, s1)
    assert stats1["reused_buffers"] == 0
    # params changed (version bump), opt_state unchanged
    s2 = _snap(step=2, versions={"params": 4, "opt_state": 3}, val=2.0)
    stats2 = save_snapshot(p2, s2, prev_path=p1)
    assert stats2["reused_buffers"] == 1
    assert stats2["written_bytes"] < stats1["written_bytes"]
    snap, _ = load_snapshot(p2)
    np.testing.assert_array_equal(snap.buffers["params"]["w"],
                                  np.full((4, 4), 2.0))


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer()
    p = str(tmp_path / "a1")
    ck.save(p, _snap(step=9))
    stats = ck.wait()
    assert stats["written_bytes"] > 0
    snap, _ = load_snapshot(p)
    assert snap.step == 9


def test_reshard_params_roundtrip():
    from repro.configs import get_arch
    from repro.models import build_model

    cfg = get_arch("yi-9b-smoke")
    b = build_model(cfg)
    params = b.init(jax.random.PRNGKey(0))
    host = jax.tree.map(lambda x: np.asarray(x), params)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    new = reshard_params(cfg, host, mesh)
    for a, c in zip(jax.tree.leaves(new), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_versions_persisted(tmp_path):
    p = str(tmp_path / "v")
    save_snapshot(p, _snap(versions={"params": 42, "opt_state": 7}))
    snap, _ = load_snapshot(p)
    assert snap.versions == {"params": 42, "opt_state": 7}


# ---------------------------------------------------------------------------
# Crash consistency & integrity (on-disk format v2)
# ---------------------------------------------------------------------------
def test_torn_write_never_discoverable(tmp_path):
    """A crash mid-save publishes nothing: no snapshot dir, no manifest,
    and discovery never sees the hidden write debris."""
    from repro.chaos import FaultPlan, FaultSpec, InjectedCrash

    p = str(tmp_path / "t-step3")
    plan = FaultPlan([FaultSpec(site="ckpt.save", kind="torn", at=1)])
    with pytest.raises(InjectedCrash):
        save_snapshot(p, _snap(step=3), chaos=plan)
    assert not os.path.exists(p)
    assert snapshot_candidates(str(tmp_path), "t") == []
    debris = os.listdir(tmp_path)
    assert debris and all(d.startswith(".tmp-") for d in debris)
    with pytest.raises(CheckpointCorruptError, match="manifest.json missing"):
        load_snapshot(p)


def test_torn_manifest_write_never_discoverable(tmp_path):
    """Same, crashing after all buffers but before the manifest."""
    from repro.chaos import FaultPlan, FaultSpec, InjectedCrash

    p = str(tmp_path / "t-step4")
    plan = FaultPlan([FaultSpec(site="ckpt.save", kind="torn", at=1,
                                match="manifest")])
    with pytest.raises(InjectedCrash):
        save_snapshot(p, _snap(step=4), chaos=plan)
    assert not os.path.exists(p)
    assert snapshot_candidates(str(tmp_path), "t") == []


def test_bitflip_detected_and_names_buffer(tmp_path):
    p = str(tmp_path / "bf")
    save_snapshot(p, _snap(step=1))
    f = os.path.join(p, "params.npz")
    with open(f, "r+b") as fh:
        fh.seek(os.path.getsize(f) // 2)
        b = fh.read(1)
        fh.seek(-1, os.SEEK_CUR)
        fh.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(CheckpointCorruptError, match="'params'"):
        load_snapshot(p)


def test_truncation_detected(tmp_path):
    p = str(tmp_path / "tr")
    save_snapshot(p, _snap(step=1))
    f = os.path.join(p, "opt_state.npz")
    with open(f, "r+b") as fh:
        fh.truncate(os.path.getsize(f) // 2)
    with pytest.raises(CheckpointCorruptError, match="'opt_state'"):
        load_snapshot(p)


def test_missing_incremental_parent_buffer_named(tmp_path):
    """An incremental snapshot whose reused buffer rotted away in the
    *previous* directory fails loudly, naming the buffer."""
    p1, p2 = str(tmp_path / "c1"), str(tmp_path / "c2")
    save_snapshot(p1, _snap(step=1, versions={"params": 3, "opt_state": 3}))
    save_snapshot(p2, _snap(step=2, versions={"params": 4, "opt_state": 3},
                            val=2.0), prev_path=p1)
    os.remove(os.path.join(p1, "opt_state.npz"))
    with pytest.raises(CheckpointCorruptError, match="'opt_state'"):
        load_snapshot(p2)


def test_load_latest_good_walks_chain(tmp_path):
    """Corrupting the newest snapshot falls back along prev_path to the
    last ancestor that verifies, reporting what was skipped."""
    p1, p2 = str(tmp_path / "c1"), str(tmp_path / "c2")
    save_snapshot(p1, _snap(step=1, versions={"params": 3, "opt_state": 3}))
    save_snapshot(p2, _snap(step=2, versions={"params": 4, "opt_state": 3},
                            val=2.0), prev_path=p1)
    os.remove(os.path.join(p2, "params.npz"))
    snap, _, used, skipped = load_latest_good(p2)
    assert used == os.path.abspath(p1) and snap.step == 1
    assert len(skipped) == 1 and skipped[0][0] == p2
    np.testing.assert_array_equal(snap.buffers["params"]["w"],
                                  np.full((4, 4), 1.0))
    # whole chain rotten -> loud failure listing everything tried
    os.remove(os.path.join(p1, "manifest.json"))
    with pytest.raises(CheckpointCorruptError, match="no restorable"):
        load_latest_good(p2)


def test_legacy_manifest_without_digests_loads(tmp_path):
    """Format-1 snapshots (no digest fields) still restore, unverified."""
    import json

    p = str(tmp_path / "v1")
    save_snapshot(p, _snap(step=6))
    mpath = os.path.join(p, "manifest.json")
    with open(mpath) as f:
        m = json.load(f)
    for k in ("digests", "file_digests", "prev_path", "format"):
        m.pop(k, None)
    with open(mpath, "w") as f:
        json.dump(m, f)
    snap, _ = load_snapshot(p)
    assert snap.step == 6


def test_snapshot_candidates_numeric_order(tmp_path):
    """step10 sorts after step9 (numeric, not lexicographic) and write
    debris / foreign dirs are never candidates."""
    for step in (2, 9, 10):
        save_snapshot(str(tmp_path / f"c-step{step}"), _snap(step=step))
    os.makedirs(tmp_path / ".tmp-c-step11-x")
    os.makedirs(tmp_path / "c-stepNaN")
    got = snapshot_candidates([str(tmp_path)], "c")
    assert [os.path.basename(p) for p in got] == \
        ["c-step10", "c-step9", "c-step2"]
