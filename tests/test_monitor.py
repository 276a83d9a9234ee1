"""Monitor unit tests: isolation, memory caps, evict/resume, checkpoint."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DeviceMemoryExceeded, Direction, FunkyCL,
                        FunkyRequest, GuestState, Monitor, MonitorError,
                        MonitorState, Program, RequestKind, SliceAllocator)


def _monitor(mem_cap=1 << 20):
    alloc = SliceAllocator("n0", 1, mem_cap_bytes=mem_cap)
    m = Monitor("task0", alloc)
    prog = Program("double", lambda x: x * 2.0)
    m.vfpga_init(prog, (jax.ShapeDtypeStruct((8,), jnp.float32),))
    return m


def test_execute_and_buffer_states():
    m = _monitor()
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.arange(8, dtype=np.float32))
    cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    out = cl.read_buffer("x")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(8, dtype=np.float32) * 2)
    m.vfpga_exit()
    assert m.state is MonitorState.EXITED


def test_memory_cap_enforced():
    m = _monitor(mem_cap=100)
    cl = FunkyCL(m)
    with pytest.raises(DeviceMemoryExceeded):
        cl.clCreateBuffer("big", jax.ShapeDtypeStruct((1000,), jnp.float32))
        cl.clFinish()


def test_foreign_buffer_rejected():
    m = _monitor()
    cl = FunkyCL(m)
    with pytest.raises(MonitorError):
        cl.clEnqueueKernel("double", ("nope",), ("nope",))
        cl.clFinish()


def test_unknown_program_rejected():
    m = _monitor()
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.zeros(8, np.float32))
    with pytest.raises(MonitorError):
        cl.clEnqueueKernel("evil", ("x",), ("x",))
        cl.clFinish()


def test_evict_resume_preserves_values_and_frees_slot():
    alloc = SliceAllocator("n0", 1)
    m = Monitor("t", alloc)
    m.vfpga_init(Program("double", lambda x: x * 2.0),
                 (jax.ShapeDtypeStruct((8,), jnp.float32),))
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.ones(8, np.float32))
    cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    assert alloc.free_count() == 0
    stats = m.evict()
    assert alloc.free_count() == 1            # slot released
    assert stats["n_dirty"] == 1
    assert m.state is MonitorState.EVICTED
    m.resume()
    cl2 = FunkyCL(m)
    np.testing.assert_array_equal(np.asarray(cl2.read_buffer("x")),
                                  np.full(8, 2.0, np.float32))


def test_evict_skips_clean_buffers():
    m = _monitor()
    cl = FunkyCL(m)
    cl.clCreateBuffer("input", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("input", np.ones(8, np.float32))   # SYNC after h2d
    cl.clFinish()
    stats = m.evict()
    assert stats["saved_bytes"] == 0
    assert stats["skipped_bytes"] == 32


def test_checkpoint_keep_running():
    m = _monitor()
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.ones(8, np.float32))
    cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    snap = m.checkpoint(GuestState(step=3), keep_running=True)
    assert m.state is MonitorState.RUNNING
    assert snap.step == 3
    np.testing.assert_array_equal(snap.buffers["x"], np.full(8, 2.0))
    # still usable afterwards
    cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    np.testing.assert_array_equal(np.asarray(cl.read_buffer("x")),
                                  np.full(8, 4.0, np.float32))


def test_no_slice_available():
    from repro.core import NoSliceAvailable

    alloc = SliceAllocator("n0", 1)
    m1 = Monitor("a", alloc)
    m1.vfpga_init(Program("id", lambda x: x),
                  (jax.ShapeDtypeStruct((2,), jnp.float32),))
    m2 = Monitor("b", alloc)
    with pytest.raises(NoSliceAvailable):
        m2.vfpga_init(Program("id2", lambda x: x),
                      (jax.ShapeDtypeStruct((2,), jnp.float32),))


def test_sync_blocks_only_buffers_written_since_last_sync():
    """SYNC drains the dirty-since-last-sync set, not the whole table."""
    m = _monitor()
    cl = FunkyCL(m)
    cl.clCreateBuffer("a", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.clCreateBuffer("b", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("a", np.ones(8, np.float32))
    cl.write_buffer("b", np.ones(8, np.float32))
    cl.clFinish()
    assert m.buffers.unsynced_count() == 0
    cl.clEnqueueKernel("double", ("a",), ("a",))
    # queue the sync behind the execute; only "a" is pending
    req = FunkyRequest(kind=RequestKind.SYNC)
    m.submit(req)
    pending_before = m.buffers.unsynced_count()
    req.completion.wait()
    assert pending_before <= 1           # b was never re-dirtied
    assert m.buffers.unsynced_count() == 0


def test_exec_signature_cache_invalidated_on_reshape():
    """A shape-changing h2d bumps the spec token; the cached signature is
    dropped and the request recompiles instead of calling a stale entry."""
    m = _monitor()
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.ones(8, np.float32))
    cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    misses0 = m.programs.stats["misses"]
    cl.write_buffer("x", np.ones(4, np.float32))    # reshape
    cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    assert m.programs.stats["misses"] == misses0 + 1
    np.testing.assert_array_equal(np.asarray(cl.read_buffer("x")),
                                  np.full(4, 2.0, np.float32))


def test_shape_changing_inplace_program_never_replays_stale_entry():
    """A program that writes a different shape back into its own input
    must miss the signature cache every call (compiled-entry avals can't
    be replayed against the grown buffer)."""
    m = _monitor()
    m.register_program(Program("grow", lambda x: jnp.concatenate([x, x])),
                       (jax.ShapeDtypeStruct((8,), jnp.float32),))
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.ones(8, np.float32))
    for _ in range(3):
        cl.clEnqueueKernel("grow", ("x",), ("x",))
    cl.clFinish()
    assert np.asarray(cl.read_buffer("x")).shape == (64,)


def test_same_shape_h2d_keeps_signature_cache_warm():
    m = _monitor()
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    for i in range(3):
        cl.write_buffer("x", np.full(8, float(i), np.float32))
        cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    assert m.metrics["exec_sig_cache_hits"] >= 2


def test_donated_execute_roundtrip():
    """donate=True updates in place; values stay correct and the buffer
    survives evict/resume."""
    alloc = SliceAllocator("n0", 1)
    m = Monitor("t", alloc)
    m.vfpga_init(Program("double", lambda x: x * 2.0),
                 (jax.ShapeDtypeStruct((8,), jnp.float32),),
                 donate_argnums=(0,))
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.ones(8, np.float32))
    for _ in range(3):
        cl.clEnqueueKernel("double", ("x",), ("x",), donate=True)
    cl.clFinish()
    np.testing.assert_array_equal(np.asarray(cl.read_buffer("x")),
                                  np.full(8, 8.0, np.float32))
    # only the donate_argnums=(0,) variant was compiled (no double compile)
    keys = [(pid, d) for (pid, _, d, _dev) in m.programs._compiled.keys()]
    assert keys.count(("double", (0,))) == 1
    assert ("double", ()) not in keys
    m.evict()
    m.resume()
    cl2 = FunkyCL(m)
    cl2.clEnqueueKernel("double", ("x",), ("x",), donate=True)
    cl2.clFinish()
    np.testing.assert_array_equal(np.asarray(cl2.read_buffer("x")),
                                  np.full(8, 16.0, np.float32))


def test_program_cache_hit_is_warm():
    m = _monitor()
    stats0 = dict(m.programs.stats)
    cl = FunkyCL(m)
    cl.clCreateBuffer("x", jax.ShapeDtypeStruct((8,), jnp.float32))
    cl.write_buffer("x", np.ones(8, np.float32))
    for _ in range(3):
        cl.clEnqueueKernel("double", ("x",), ("x",))
    cl.clFinish()
    stats = m.programs.stats
    assert stats["misses"] == stats0["misses"]   # compiled at vfpga_init
    # first EXECUTE fingerprints once; the monitor's signature cache then
    # short-circuits the per-request abstract walk entirely
    assert stats["hits"] == stats0["hits"] + 1
    assert m.metrics["exec_sig_cache_hits"] >= 2
