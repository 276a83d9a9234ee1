"""Paged KV subsystem: BlockPool allocator invariants (hypothesis-backed),
pool pytree construction, and the traced gather/scatter/scrub helpers the
engine's kernels are built from."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.state import BufferState, BufferTable, tree_bytes
from repro.models.attention import _INVALID_POS
from repro.serve.kvcache import (BlockPool, BlockPoolError, cache_bytes,
                                 gather_lane_cache, pool_specs_from_lane_cache,
                                 scatter_pages, scatter_prefill, scrub_pages,
                                 token_axes_from_lengths)

from hypothesis import given, settings
from hypothesis import strategies as st
from hypothesis.stateful import (RuleBasedStateMachine, invariant,
                                 precondition, rule)


# ---------------------------------------------------------------------------
# BlockPool
# ---------------------------------------------------------------------------
def test_alloc_is_deterministic_lowest_first():
    pool = BlockPool(8, 4)
    assert pool.alloc(3) == [0, 1, 2]
    pool.free([1])
    assert pool.alloc(2) == [1, 3]      # freed low id reused first


def test_watermark_blocks_normal_but_not_urgent_alloc():
    pool = BlockPool(4, 4, reserve_pages=2)
    assert pool.can_admit(2) and not pool.can_admit(3)
    assert pool.alloc(3) is None        # would breach the reserve
    assert pool.alloc(2) == [0, 1]
    assert pool.alloc(1) is None        # reserve protects the last 2
    assert pool.alloc(1, urgent=True) == [2]   # append path may dip in
    assert pool.alloc(2, urgent=True) is None  # but never over-allocates


def test_double_free_raises():
    pool = BlockPool(4, 4)
    ids = pool.alloc(2)
    pool.free(ids)
    with pytest.raises(BlockPoolError):
        pool.free([ids[0]])


def test_compact_packs_used_pages_low():
    pool = BlockPool(8, 4)
    a = pool.alloc(6)
    pool.free([a[0], a[2], a[4]])       # used = {1, 3, 5}
    mapping = pool.compact()
    assert set(mapping) == {3, 5} and set(mapping.values()) == {0, 2}
    assert pool.used_span() == 3        # {0, 1, 2}
    pool.check_invariants()
    # every page still allocatable exactly once
    assert sorted(pool.alloc(5)) == [3, 4, 5, 6, 7]
    assert pool.alloc(1) is None


def test_pages_for_tokens_and_occupancy():
    pool = BlockPool(10, 4)
    assert pool.pages_for_tokens(1) == 1
    assert pool.pages_for_tokens(4) == 1
    assert pool.pages_for_tokens(5) == 2
    pool.alloc(5)
    assert pool.occupancy() == 0.5 and pool.free_count() == 5


def test_share_refcounts_and_symmetric_free():
    """Prefix-cache sharing: ``share`` adds references, ``free`` removes
    one, and a page only returns to the free heap at refcount zero."""
    pool = BlockPool(8, 4)
    a = pool.alloc(2)
    pool.share([a[0]])
    assert pool.refcount(a[0]) == 2 and pool.refcount(a[1]) == 1
    assert pool.shared_count() == 1
    assert pool.free(a) == [a[1]]       # shared page survives its owner
    assert pool.refcount(a[0]) == 1
    pool.check_invariants()
    assert pool.free([a[0]]) == [a[0]]  # last reference actually frees
    with pytest.raises(BlockPoolError):
        pool.share([a[0]])              # cannot share a free page
    pool.check_invariants()


def test_free_tail_unshares_shared_tail():
    """Speculative rollback over a shared tail page must not free it out
    from under the other owner — the reference drops, the page stays."""
    pool = BlockPool(8, 4)
    blocks = pool.alloc(4)
    pool.share([blocks[3]])
    freed = pool.free_tail(blocks, 2)
    assert freed == [blocks[2]]         # shared page survives the rollback
    assert pool.refcount(blocks[3]) == 1
    pool.check_invariants()
    assert pool.free([blocks[3]]) == [blocks[3]]


def test_compact_moves_refcounts_with_pages():
    pool = BlockPool(8, 4)
    a = pool.alloc(4)
    pool.share([a[3]])
    pool.free([a[0], a[1]])
    mapping = pool.compact()
    assert pool.refcount(mapping.get(a[3], a[3])) == 2
    assert pool.shared_count() == 1
    pool.check_invariants()


def test_free_tail_releases_only_the_orphaned_suffix():
    """The speculative-rollback primitive: only the pages past ``keep`` go
    back to the pool, and they are returned for event accounting."""
    pool = BlockPool(10, 4)
    blocks = pool.alloc(5)
    freed = pool.free_tail(blocks, 2)
    assert freed == blocks[2:]
    assert pool._used == set(blocks[:2])
    pool.check_invariants()
    assert pool.free_tail(blocks[:2], 2) == []      # nothing past keep
    with pytest.raises(ValueError):
        pool.free_tail(blocks[:2], -1)
    with pytest.raises(BlockPoolError):             # already freed
        pool.free_tail(blocks, 2)


class PoolMachine(RuleBasedStateMachine):
    """Random alloc/share/free/free_tail/compact sequences preserve the
    partition invariant (free ∪ used = all pages, disjoint), ownership
    (a live page is never re-allocated), and refcount semantics: a
    page with references outstanding is never freed (so it can never
    be scrubbed or handed to another owner), and compaction moves
    reference counts with their pages."""

    def __init__(self):
        super().__init__()
        self.pool = BlockPool(16, 4, reserve_pages=2)
        self.owned = {}             # owner -> ordered page list
        self.rc = {}                # page -> model refcount
        self.next_owner = 0

    def _drop_ref(self, p):
        self.rc[p] -= 1
        if self.rc[p] == 0:
            del self.rc[p]
            return True
        return False

    @rule(n=st.integers(1, 5), urgent=st.booleans())
    def alloc(self, n, urgent):
        got = self.pool.alloc(n, urgent=urgent)
        if got is not None:
            assert not (set(got) & set(self.rc)), \
                "live page re-allocated"
            self.owned[self.next_owner] = list(got)
            for p in got:
                self.rc[p] = 1
            self.next_owner += 1

    @precondition(lambda self: self.rc)
    @rule(data=st.data())
    def share_one(self, data):
        """A prefix-tree node (or second lane) pins a live page."""
        p = data.draw(st.sampled_from(sorted(self.rc)))
        self.pool.share([p])
        self.rc[p] += 1

    @precondition(lambda self: any(c > 1 for c in self.rc.values()))
    @rule(data=st.data())
    def unshare_one(self, data):
        """Dropping one of several references never frees the page."""
        p = data.draw(st.sampled_from(
            sorted(q for q, c in self.rc.items() if c > 1)))
        assert self.pool.free([p]) == []
        self._drop_ref(p)

    @precondition(lambda self: self.owned)
    @rule(data=st.data())
    def free_owner(self, data):
        """A retiring owner frees exactly its unshared pages."""
        owner = data.draw(st.sampled_from(sorted(self.owned)))
        pages = sorted(self.owned.pop(owner))
        freed = self.pool.free(pages)
        assert freed == [p for p in pages if self._drop_ref(p)]

    @precondition(lambda self: self.owned)
    @rule(data=st.data())
    def rollback_tail(self, data):
        """Speculative rollback: ``free_tail`` on a shared tail page
        unshares it — the surviving owner keeps its copy."""
        owner = data.draw(st.sampled_from(sorted(self.owned)))
        blocks = self.owned[owner]
        keep = data.draw(st.integers(0, len(blocks)))
        freed = self.pool.free_tail(blocks, keep)
        assert freed == [p for p in blocks[keep:]
                         if self._drop_ref(p)]
        self.owned[owner] = blocks[:keep]
        if not self.owned[owner]:
            del self.owned[owner]

    @rule()
    def compact(self):
        mapping = self.pool.compact()
        for owner, pages in self.owned.items():
            self.owned[owner] = [mapping.get(p, p) for p in pages]
        self.rc = {mapping.get(p, p): c for p, c in self.rc.items()}

    @invariant()
    def partition_holds(self):
        self.pool.check_invariants()
        assert set(self.rc) == self.pool._used
        for p, c in self.rc.items():
            assert self.pool.refcount(p) == c
        assert self.pool.free_count() == 16 - len(self.rc)

TestPoolMachine = PoolMachine.TestCase
TestPoolMachine.settings = settings(max_examples=30,
                                    deadline=None)

@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_free_tail_property(data):
    """Rollback frees exactly the orphaned tail: after interleaved
    allocations, ``free_tail(blocks, keep)`` leaves precisely the kept
    prefixes owned and the pool partition invariant intact."""
    pool = BlockPool(16, 4, reserve_pages=2)
    owners = []
    for _ in range(data.draw(st.integers(1, 4))):
        n = data.draw(st.integers(1, 4))
        got = pool.alloc(n, urgent=True)
        if got is not None:
            owners.append(got)
    kept = []
    for blocks in owners:
        keep = data.draw(st.integers(0, len(blocks)))
        freed = pool.free_tail(blocks, keep)
        assert freed == blocks[keep:]
        kept.extend(blocks[:keep])
    pool.check_invariants()
    assert pool._used == set(kept)
    assert pool.free_count() == 16 - len(kept)


# ---------------------------------------------------------------------------
# Pool pytree construction + traced helpers (no model needed)
# ---------------------------------------------------------------------------
PS = 4          # page size
NP_ = 6         # pool pages
MB = 3          # max blocks per lane


def _lane_cache(cap, layers=2, heads=2, hd=3):
    """Stacked-scan-style lane cache like the transformer backbone's."""
    return {
        "k": jnp.arange(layers * cap * heads * hd, dtype=jnp.float32
                        ).reshape(layers, 1, cap, heads, hd),
        "v": jnp.ones((layers, 1, cap, heads, hd), jnp.float32),
        "kv_pos": jnp.tile(jnp.arange(cap, dtype=jnp.int32), (layers, 1)),
    }


def _abs(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


@pytest.fixture(scope="module")
def axes():
    return token_axes_from_lengths(_abs(_lane_cache(5)),
                                   _abs(_lane_cache(8)), 5, 8)


def test_token_axes_discovery(axes):
    assert axes["k"] == 2 and axes["v"] == 2 and axes["kv_pos"] == 1


def test_token_axes_rejects_ring_caches():
    # a window-bounded ring cache keeps its shape across prompt lengths
    ring = {"k": jax.ShapeDtypeStruct((1, 4, 2, 3), jnp.float32)}
    with pytest.raises(ValueError):
        token_axes_from_lengths(ring, ring, 5, 8)


def test_token_axes_delta_mode_for_margined_caches():
    """exact=False matches on axis-size *delta* — the speculative-decode
    draft lane, whose capacity is prompt_len + a constant margin."""
    margin = 6
    a, b = _abs(_lane_cache(5 + margin)), _abs(_lane_cache(8 + margin))
    with pytest.raises(ValueError):
        token_axes_from_lengths(a, b, 5, 8)          # sizes are P + margin
    axes = token_axes_from_lengths(a, b, 5, 8, exact=False)
    assert axes["k"] == 2 and axes["kv_pos"] == 1
    with pytest.raises(ValueError):                  # delta must still match
        token_axes_from_lengths(a, b, 5, 9, exact=False)


def test_pool_specs_shapes(axes):
    pool = pool_specs_from_lane_cache(_abs(_lane_cache(8)), axes, NP_, PS)
    assert pool["k"].shape == (NP_, PS, 2, 1, 2, 3)
    assert pool["kv_pos"].shape == (NP_, PS, 2)
    # byte accounting goes through the one shared helper
    assert cache_bytes(pool) == tree_bytes(pool)


def test_prefill_scatter_gather_roundtrip(axes):
    """scatter_prefill + gather through the block table reassembles the
    lane cache exactly, INVALID-pads the tail, and masks unmapped pages."""
    cap = 5                              # ragged: 2 pages, 3 slots padding
    lane = _lane_cache(cap)
    pool_abs = pool_specs_from_lane_cache(_abs(_lane_cache(MB * PS)), axes,
                                          NP_, PS)
    pool = jax.tree_util.tree_map_with_path(
        lambda p, l: (jnp.full(l.shape, _INVALID_POS, jnp.int32)
                      if p[-1].key == "kv_pos"
                      else jnp.full(l.shape, 99.0, l.dtype)), pool_abs)
    page_ids = jnp.asarray([4, 1], jnp.int32)   # non-contiguous on purpose
    pool = scatter_prefill(pool, page_ids, lane, axes, page_size=PS,
                           prompt_len=cap)
    block_row = jnp.asarray([4, 1, -1], jnp.int32)
    got = gather_lane_cache(pool, block_row, axes, page_size=PS)
    L = MB * PS
    assert got["k"].shape == (2, 1, L, 2, 3)
    np.testing.assert_array_equal(np.asarray(got["k"][:, :, :cap]),
                                  np.asarray(lane["k"]))
    np.testing.assert_array_equal(np.asarray(got["kv_pos"][:, :cap]),
                                  np.asarray(lane["kv_pos"]))
    # tail of the last mapped page and the whole unmapped page: INVALID
    assert (np.asarray(got["kv_pos"][:, cap:]) == _INVALID_POS).all()


def test_scrub_invalidates_only_positions(axes):
    pool_abs = pool_specs_from_lane_cache(_abs(_lane_cache(MB * PS)), axes,
                                          NP_, PS)
    pool = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), pool_abs)
    ids = jnp.asarray([2, NP_], jnp.int32)       # NP_ = padding, dropped
    out = scrub_pages(pool, ids)
    assert (np.asarray(out["kv_pos"][2]) == _INVALID_POS).all()
    assert (np.asarray(out["kv_pos"][3]) == 0).all()
    assert (np.asarray(out["k"]) == 0).all()     # k/v untouched


def test_scatter_pages_drops_inactive_lanes(axes):
    pool_abs = pool_specs_from_lane_cache(_abs(_lane_cache(MB * PS)), axes,
                                          NP_, PS)
    pool = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), pool_abs)
    pages = jax.tree.map(
        lambda l: jnp.ones((2,) + l.shape[1:], l.dtype), pool_abs)
    phys = jnp.asarray([3, NP_], jnp.int32)      # lane 1 inactive -> drop
    out = scatter_pages(pool, phys, pages)
    assert (np.asarray(out["k"][3]) == 1).all()
    assert (np.asarray(out["k"][:3]) == 0).all()
    assert (np.asarray(out["k"][4:]) == 0).all()


# ---------------------------------------------------------------------------
# Page-granular dirtiness in the buffer state machine
# ---------------------------------------------------------------------------
def _pool_value(n_pages=4, ps=2):
    return {"k": jnp.arange(n_pages * ps * 3, dtype=jnp.float32
                            ).reshape(n_pages, ps, 3),
            "kv_pos": jnp.zeros((n_pages, ps), jnp.int32)}


def test_paged_buffer_evicts_only_dirty_pages():
    table = BufferTable()
    val = _pool_value()
    table.register("pool", jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), val), paged=True)
    table.on_execute_write("pool", val)          # no dirty_pages: all dirty
    s1 = table.evict_device_state()
    assert s1["paged_saved_pages"] == 4 and s1["paged_total_pages"] == 4
    table.restore_device_state(jax.devices()[0])

    new = jax.tree.map(lambda x: x + (x + 1) * 0, val)   # same values
    new["k"] = new["k"].at[2].set(-1.0)
    table.on_execute_write("pool", new, stable=True, dirty_pages=[2])
    s2 = table.evict_device_state()
    assert s2["paged_saved_pages"] == 1
    assert s2["saved_bytes"] == tree_bytes(val) // 4
    # the merged host copy is bit-exact: clean pages from the old copy,
    # dirty page from the device
    b = table.get("pool")
    np.testing.assert_array_equal(b.host_value["k"][2], np.full((2, 3), -1.))
    np.testing.assert_array_equal(b.host_value["k"][0],
                                  np.asarray(val["k"][0]))
    assert b.state is BufferState.SYNC


def test_paged_buffer_degrades_without_page_info():
    table = BufferTable()
    val = _pool_value()
    table.register("pool", jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), val), paged=True)
    table.on_execute_write("pool", val, dirty_pages=[0])
    table.evict_device_state()
    table.restore_device_state(jax.devices()[0])
    table.on_execute_write("pool", val, stable=True)     # unknown pages
    s = table.evict_device_state()
    assert s["paged_saved_pages"] == 4                   # conservative


def test_snapshot_not_corrupted_by_later_dirty_merge():
    """host_snapshot aliases the live host copies; a later dirty-page
    merge must copy-on-write instead of patching the snapshot's arrays."""
    table = BufferTable()
    val = _pool_value()
    table.register("pool", jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), val), paged=True)
    table.on_execute_write("pool", val)
    table.on_d2h("pool")                         # host copy current
    snap = table.host_snapshot()                 # checkpoint view (aliased)
    before = np.asarray(snap["pool"]["k"][1]).copy()

    new = jax.tree.map(lambda x: x, val)
    new["k"] = new["k"].at[1].set(-7.0)
    table.on_execute_write("pool", new, stable=True, dirty_pages=[1])
    table.on_d2h("pool")                         # merge: must not hit snap
    np.testing.assert_array_equal(np.asarray(snap["pool"]["k"][1]), before)
    np.testing.assert_array_equal(
        table.get("pool").host_value["k"][1], np.full((2, 3), -7.0))
