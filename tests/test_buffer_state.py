"""Property-based tests of the buffer state machine (paper §3.4).

Invariants:
  I1  DIRTY buffers always hold a device value;
  I2  after ``evict_device_state`` no buffer holds a device value and none
      is DIRTY (everything saved or reproducible);
  I3  eviction saves exactly the DIRTY bytes and skips SYNC/INIT bytes;
  I4  evict -> restore round-trips every value bit-exactly;
  I5  versions increase monotonically with device writes.
"""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import settings
from hypothesis import strategies as st
from hypothesis.stateful import (RuleBasedStateMachine, invariant,
                                 precondition, rule)

from repro.core.state import BufferState, BufferTable


def _spec(i):
    return jax.ShapeDtypeStruct((4, 4), jnp.float32)


class BufferMachine(RuleBasedStateMachine):
    def __init__(self):
        super().__init__()
        self.table = BufferTable()
        self.counter = 0
        self.mirror = {}      # our model of what the host should hold

    @rule()
    def register(self):
        bid = f"b{self.counter}"
        self.counter += 1
        self.table.register(bid, _spec(bid))

    def _ids(self):
        return self.table.ids()

    @precondition(lambda self: self._ids())
    @rule(data=st.data())
    def h2d(self, data):
        bid = data.draw(st.sampled_from(self._ids()))
        val = np.full((4, 4), self.counter, np.float32)
        self.counter += 1
        self.table.on_h2d(bid, val, jnp.asarray(val))
        self.mirror[bid] = val

    @precondition(lambda self: any(
        self.table.get(i).device_value is not None for i in self._ids()))
    @rule(data=st.data())
    def execute_write(self, data):
        ids = [i for i in self._ids()
               if self.table.get(i).device_value is not None]
        bid = data.draw(st.sampled_from(ids))
        val = jnp.full((4, 4), self.counter, jnp.float32)
        self.counter += 1
        old_v = self.table.get(bid).version
        self.table.on_execute_write(bid, val)
        assert self.table.get(bid).version == old_v + 1          # I5
        self.mirror[bid] = np.asarray(val)

    @precondition(lambda self: any(
        self.table.get(i).state is BufferState.DIRTY
        for i in self._ids()))
    @rule(data=st.data())
    def d2h(self, data):
        ids = [i for i in self._ids()
               if self.table.get(i).state is BufferState.DIRTY]
        bid = data.draw(st.sampled_from(ids))
        host = self.table.on_d2h(bid)
        np.testing.assert_array_equal(np.asarray(host), self.mirror[bid])
        assert self.table.get(bid).state is BufferState.SYNC

    @rule()
    def evict_and_restore(self):
        dirty = set(self.table.dirty_ids())
        dirty_bytes = sum(self.table.get(i).nbytes for i in dirty)
        stats = self.table.evict_device_state()
        assert stats["saved_bytes"] == dirty_bytes               # I3
        assert stats["n_dirty"] == len(dirty)
        for i in self._ids():
            b = self.table.get(i)
            assert b.device_value is None                        # I2
            assert b.state is not BufferState.DIRTY
        self.table.restore_device_state(jax.devices()[0])
        for i, want in self.mirror.items():
            b = self.table.get(i)
            if b.host_value is not None:
                np.testing.assert_array_equal(                   # I4
                    np.asarray(jax.device_get(b.device_value)), want)

    @invariant()
    def dirty_implies_device(self):
        for i in self._ids():
            b = self.table.get(i)
            if b.state is BufferState.DIRTY:
                assert b.device_value is not None                # I1

TestBufferMachine = BufferMachine.TestCase
TestBufferMachine.settings = settings(
    max_examples=25, stateful_step_count=30, deadline=None)


def test_snapshot_roundtrip():
    t = BufferTable()
    t.register("params", _spec("p"))
    val = np.arange(16, dtype=np.float32).reshape(4, 4)
    t.on_h2d("params", val, jnp.asarray(val))
    t.on_execute_write("params", jnp.asarray(val * 2))
    t.evict_device_state()
    snap = t.host_snapshot()
    t2 = BufferTable()
    t2.load_snapshot(snap)
    t2.restore_device_state(jax.devices()[0])
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t2.get("params").device_value)), val * 2)
