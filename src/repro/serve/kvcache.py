"""Paged vFPGA device-memory virtualization for KV caches (paper §3.4).

The serving engine used to reserve a worst-case ``prompt_len +
max_new_tokens`` KV stripe per decode lane at admission.  This module
virtualizes that memory behind an indirection layer, PagedAttention-style:

* ``BlockPool`` — the host-side allocator.  Device KV memory is a pool of
  fixed-size pages; lanes hold pages at *token* granularity (prompt pages
  at admission, one page at a time as decode crosses page boundaries) and
  free them the moment a request retires.  Admission is memory-based:
  admit while ``free_pages - need >= reserve_pages``, so the lane count can
  exceed what worst-case reservations would allow.
* **block table** — per-lane ``(max_blocks,)`` int32 rows mapping logical
  page index -> physical page id (-1 = unmapped).  The vmapped decode step
  gathers each lane's logical cache through its row; admission scatters the
  prefill cache into freshly allocated pages.
* traced helpers (``gather_lane_cache`` / ``extract_written_page`` /
  ``scatter_pages`` / ``scatter_prefill`` / ``scrub_pages`` /
  ``compact_pool``) — the kernel-side pieces the engine's programs are
  built from.  ``scrub_pages`` invalidates the position row of every page
  on (re)allocation, the paged analogue of the monitor zeroing freed device
  memory (§3.4 isolation): a new owner can never attend to a previous
  lane's tokens.
* ``BlockPool.compact`` — defragmentation: pack used pages into the lowest
  physical ids so the pool's high-water span (and therefore the worst-case
  dirty-page walk on evict) shrinks after churn.

Every leaf of the device pool has the page axis as axis 0, matching the
``BufferTable``'s page-granular dirtiness: evict/checkpoint serialize only
the pages written since the last sync plus the (tiny) block table.  A k/v
leaf stores its trailing ``(heads, head_dim)`` as one axis: a TPU then lays
the pool out page-major, one page one contiguous block, for any head_dim.
"""

from __future__ import annotations

import heapq
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.state import tree_bytes
from repro.models.attention import _INVALID_POS

# one exported byte-accounting helper (shared with the buffer state machine)
cache_bytes = tree_bytes


def init_caches_from_specs(specs):
    """Zeros for k/v/state leaves; INVALID sentinel for kv_pos leaves."""
    def mk(path, leaf):
        if _is_pos_leaf(path):
            return jnp.full(leaf.shape, _INVALID_POS, jnp.int32)
        return jnp.zeros(leaf.shape, leaf.dtype)

    return jax.tree_util.tree_map_with_path(mk, specs)


def _is_pos_leaf(path) -> bool:
    names = [k.key for k in path if hasattr(k, "key")]
    return bool(names) and names[-1] == "kv_pos"


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    return max(1, math.ceil(n_tokens / page_size))


# ---------------------------------------------------------------------------
# Host-side page allocator
# ---------------------------------------------------------------------------
class BlockPoolError(RuntimeError):
    pass


class BlockPool:
    """Fixed-size page allocator over the device KV pool.

    Deterministic by construction (lowest free id first) so paged decoding
    replays bit-exactly across evict/resume.  ``reserve_pages`` is the
    admission watermark: normal allocations keep that many pages free for
    in-flight decode appends; ``urgent=True`` (the append path) may dip
    into the reserve — when even that fails the engine preempts a lane.
    """

    def __init__(self, num_pages: int, page_size: int, *,
                 reserve_pages: int = 0):
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("need num_pages > 0 and page_size > 0")
        if reserve_pages >= num_pages:
            raise ValueError("reserve watermark leaves no usable pages")
        self.num_pages = num_pages
        self.page_size = page_size
        self.reserve_pages = reserve_pages
        self._free: List[int] = list(range(num_pages))
        heapq.heapify(self._free)
        self._used: set = set()
        # reference counts: a page may be owned by several lanes plus the
        # prefix cache at once.  ``free`` drops one reference; the page
        # only returns to the free heap when the last reference drops, so
        # a shared page can never be scrubbed or reallocated under a
        # surviving owner.
        self._rc: Dict[int, int] = {}

    # -- accounting ------------------------------------------------------
    def free_count(self) -> int:
        return len(self._free)

    def used_count(self) -> int:
        return len(self._used)

    def refcount(self, page_id: int) -> int:
        return self._rc.get(page_id, 0)

    def shared_count(self) -> int:
        """Pages currently referenced by more than one owner."""
        return sum(1 for c in self._rc.values() if c > 1)

    def occupancy(self) -> float:
        return len(self._used) / self.num_pages

    def used_span(self) -> int:
        """High-water mark: 1 + the highest physical id in use."""
        return max(self._used) + 1 if self._used else 0

    def pages_for_tokens(self, n_tokens: int) -> int:
        return pages_for_tokens(n_tokens, self.page_size)

    def can_admit(self, n_pages: int) -> bool:
        return self.free_count() - n_pages >= self.reserve_pages

    # -- alloc / free ----------------------------------------------------
    def alloc(self, n_pages: int, *, urgent: bool = False,
              ) -> Optional[List[int]]:
        """Allocate ``n_pages`` (lowest ids first), or None if the request
        would breach the watermark (``urgent`` ignores the watermark)."""
        avail = self.free_count() - (0 if urgent else self.reserve_pages)
        if n_pages > avail:
            return None
        out = [heapq.heappop(self._free) for _ in range(n_pages)]
        self._used.update(out)
        for p in out:
            self._rc[p] = 1
        return out

    def share(self, page_ids: Sequence[int]) -> None:
        """Add one reference to each (already used) page — a new owner
        mapping cached pages into its block table, or the prefix cache
        pinning a lane's pages."""
        for p in page_ids:
            if p not in self._used:
                raise BlockPoolError(f"share of free page {p}")
            self._rc[p] += 1

    def free(self, page_ids: Sequence[int]) -> List[int]:
        """Drop one reference per page; returns the pages whose *last*
        reference dropped (those actually returned to the free heap).
        Shared pages survive under their remaining owners."""
        out: List[int] = []
        for p in page_ids:
            if p not in self._used:
                raise BlockPoolError(f"double free of page {p}")
            self._rc[p] -= 1
            if self._rc[p] == 0:
                del self._rc[p]
                self._used.discard(p)
                heapq.heappush(self._free, p)
                out.append(p)
        return out

    def free_tail(self, page_ids: Sequence[int], keep: int) -> List[int]:
        """Drop this owner's reference on ``page_ids[keep:]`` and return
        the pages that actually freed — the speculative-decode rollback
        primitive: a rejected lookahead orphans the pages past the last
        committed token, and only those go back to the pool (the kept
        prefix still holds the lane's committed history).  A *shared* tail
        page is unshared rather than freed: the surviving owners (prefix
        cache, other lanes) keep their copy untouched."""
        if keep < 0:
            raise ValueError("keep must be >= 0")
        return self.free(list(page_ids[keep:]))

    # -- defragmentation -------------------------------------------------
    def compact(self) -> Dict[int, int]:
        """Pack used pages into the lowest physical ids.

        Returns {old_id: new_id} for every page that moves (destinations
        are free before the call, so a single gather+scatter applies the
        whole mapping without ordering hazards).  The caller must rewrite
        its block tables and move the device pages.
        """
        k = len(self._used)
        dests = [i for i in range(k) if i not in self._used]
        movers = [p for p in sorted(self._used) if p >= k]
        mapping = dict(zip(movers, dests))
        if mapping:
            self._used = (self._used - set(movers)) | set(mapping.values())
            self._free = [i for i in range(self.num_pages)
                          if i not in self._used]
            heapq.heapify(self._free)
            # reference counts travel with the page: every owner (lanes,
            # prefix-cache nodes) is remapped by the caller from the same
            # mapping, so a shared page stays shared at its new id
            for old, new in mapping.items():
                self._rc[new] = self._rc.pop(old)
        return mapping

    def check_invariants(self) -> None:
        free = set(self._free)
        if len(free) != len(self._free):
            raise BlockPoolError("duplicate ids in free list")
        if free & self._used:
            raise BlockPoolError("page both free and used")
        if free | self._used != set(range(self.num_pages)):
            raise BlockPoolError("pages leaked from the pool")
        if set(self._rc) != self._used:
            raise BlockPoolError("refcount map out of sync with used set")
        if any(c < 1 for c in self._rc.values()):
            raise BlockPoolError("used page with refcount < 1")


# ---------------------------------------------------------------------------
# Pool pytree construction
# ---------------------------------------------------------------------------
# Models differ in cache leaf layout: a scanned backbone stacks a layer
# axis in front ((L, 1, cap, H, hd) k/v, (L, cap) kv_pos), MLA keeps
# compressed latents, etc.  Rather than hard-coding layouts, the engine
# discovers each leaf's *token axis* once at setup by diffing the abstract
# prefill cache at two prompt lengths; every traced helper then normalizes
# a leaf by moving that axis to the front, so the pool layout is always
# ``(num_pages, page_size, *rest)`` with ``rest`` the per-token residue in
# original order (layer/batch/head axes included) — except that a k/v leaf
# merges the last two axes of ``rest`` (``(heads, head_dim)``) into one.
# With both in the tiled minor dims, a TPU's default layout pads head_dim
# to 128 lanes unless it puts the page axis minor-most instead; then one
# page is scattered across the whole leaf, a gather of pages crawls and
# every page scatter relayouts the pool.  Merged, the page axis stays
# major.  The merge pads nothing; position leaves keep their shape.

def token_axes_from_lengths(cache_a, cache_b, len_a: int, len_b: int, *,
                            exact: bool = True):
    """Per-leaf token-axis pytree: the unique axis whose size tracks the
    prompt length.  Raises for window-bounded ring caches (no axis moves)
    or exotic layouts (several axes move) — those need reserved mode.

    ``exact=False`` only requires the axis size *delta* to match the prompt
    length delta (rather than the sizes themselves) — the case for caches
    built with a constant decode margin, e.g. the speculative-decode draft
    lane whose capacity is ``prompt_len + margin``.
    """
    def ax(la, lb):
        diffs = [i for i, (x, y) in enumerate(zip(la.shape, lb.shape))
                 if x != y]
        bad = len(diffs) != 1
        if not bad:
            d = diffs[0]
            if exact:
                bad = la.shape[d] != len_a or lb.shape[d] != len_b
            else:
                bad = lb.shape[d] - la.shape[d] != len_b - len_a
        if bad:
            raise ValueError(
                f"cannot page cache leaf {la.shape} -> {lb.shape}: token "
                "axis is not uniquely prompt-length-sized (window-bounded "
                "ring cache?); run the engine with paged=False")
        return diffs[0]

    return jax.tree.map(ax, cache_a, cache_b)


def _token_first(leaf, axis):
    return jnp.moveaxis(leaf, axis, 0)


def _lane_rest(leaf, axis):
    """Per-token shape of a lane cache leaf: its shape less the token axis."""
    return leaf.shape[:axis] + leaf.shape[axis + 1:]


def _stored_rest(path, rest):
    """Per-token shape a pool leaf stores: ``rest`` with its last two axes
    merged, except for position leaves and leaves with fewer axes."""
    if _is_pos_leaf(path) or len(rest) < 2:
        return rest
    return rest[:-2] + (rest[-2] * rest[-1],)


def pool_specs_from_lane_cache(lane_cache_abs, token_axes, num_pages: int,
                               page_size: int):
    """Per-lane cache pytree -> page-pool pytree: each leaf becomes
    ``(num_pages, page_size, *rest)``, a k/v leaf with the last two axes of
    ``rest`` merged.  Structure (and the ``kv_pos`` leaf names the init
    helper keys on) is preserved."""
    def mk(path, leaf, axis):
        rest = _stored_rest(path, _lane_rest(leaf, axis))
        return jax.ShapeDtypeStruct((num_pages, page_size) + rest,
                                    leaf.dtype)

    return jax.tree_util.tree_map_with_path(mk, lane_cache_abs, token_axes)


def merged_pool_leaves(pool, lane_cache_abs):
    """The pool leaves stored with two per-token axes merged into one."""
    return [p for p, l in zip(jax.tree.leaves(pool),
                              jax.tree.leaves(lane_cache_abs))
            if p.ndim == l.ndim]


# ---------------------------------------------------------------------------
# Traced kernel-side helpers
# ---------------------------------------------------------------------------
def gather_lane_cache(pool, block_row, lane_cache_abs, token_axes, *,
                      page_size: int):
    """Reassemble one lane's logical cache from the pool through its block
    table row (traced, vmapped over lanes by the engine).  The lane cache
    the pool was built from gives each leaf's per-token shape, merged
    axes split back.

    Unmapped pages (id < 0) are clamped for the gather but their positions
    are forced to the INVALID sentinel, so attention masks them out no
    matter what the clamped page holds.
    """
    max_blocks = block_row.shape[0]
    cap = max_blocks * page_size

    def gk(path, leaf, lane, axis):
        safe = jnp.clip(block_row, 0, leaf.shape[0] - 1)
        # gather token rows, not whole pages: a page of a wide model (1.3 MB
        # for stablelm-3b) makes the TPU gather in slices of the merged axis
        # and stitch them back (a 40.6 ms decode step against 38.0 ms, v5e)
        rows = (safe[:, None] * page_size
                + jnp.arange(page_size, dtype=safe.dtype)).reshape(cap)
        flat = leaf.reshape((-1,) + leaf.shape[2:])[rows]
        flat = flat.reshape((cap,) + _lane_rest(lane, axis))
        if _is_pos_leaf(path):
            valid = jnp.repeat(block_row >= 0, page_size)
            flat = jnp.where(
                valid.reshape((cap,) + (1,) * (flat.ndim - 1)),
                flat, _INVALID_POS)
        return jnp.moveaxis(flat, 0, axis)       # original lane layout

    return jax.tree_util.tree_map_with_path(gk, pool, lane_cache_abs,
                                            token_axes)


def extract_written_page(new_lane_cache, logical_page, token_axes, *,
                         page_size: int):
    """Slice the page containing this step's single-token write back out of
    a lane's updated logical cache (traced; ``logical_page`` is dynamic)."""
    def ex(leaf, axis):
        tf = _token_first(leaf, axis)
        start = (logical_page * page_size,) + (0,) * (tf.ndim - 1)
        return jax.lax.dynamic_slice(tf, start,
                                     (page_size,) + tf.shape[1:])

    return jax.tree.map(ex, new_lane_cache, token_axes)


def scatter_pages(pool, phys_ids, pages):
    """Write per-lane updated pages (``extract_written_page`` layout, or
    the pool's own) back into the pool.  ``phys_ids`` is (lanes,);
    out-of-range ids (inactive lanes) are dropped.  Active lanes own
    disjoint pages, so the scatter is conflict-free."""
    return jax.tree.map(
        lambda pl, pg: pl.at[phys_ids].set(
            pg.reshape(pg.shape[:1] + pl.shape[1:]), mode="drop"),
        pool, pages)


def scatter_prefill(pool, page_ids, pf_cache, token_axes, *,
                    page_size: int, prompt_len: int):
    """Admission: distribute a prefill cache across freshly allocated pages.

    The tail page's unfilled slots get zeros / INVALID positions, so decode
    can write into them later without a scrub.
    """
    n_pp = page_ids.shape[0]
    pad = n_pp * page_size - prompt_len

    def sc(path, pool_leaf, pf_leaf, axis):
        vals = _token_first(pf_leaf, axis)       # (P, *rest)
        if pad:
            fill = (jnp.full((pad,) + vals.shape[1:], _INVALID_POS,
                             jnp.int32) if _is_pos_leaf(path)
                    else jnp.zeros((pad,) + vals.shape[1:], vals.dtype))
            vals = jnp.concatenate([vals, fill])
        vals = vals.reshape((n_pp,) + pool_leaf.shape[1:])
        return pool_leaf.at[page_ids].set(vals)

    return jax.tree_util.tree_map_with_path(sc, pool, pf_cache, token_axes)


def scrub_pages(pool, page_ids):
    """Invalidate the kv_pos rows of (re)allocated pages — freed-memory
    zeroing (§3.4): whatever k/v bytes the previous owner left behind are
    unreachable once their positions read INVALID.  Out-of-range ids in the
    fixed-width ``page_ids`` vector are padding and dropped."""
    def f(path, leaf):
        if _is_pos_leaf(path):
            return leaf.at[page_ids].set(_INVALID_POS, mode="drop")
        return leaf

    return jax.tree_util.tree_map_with_path(f, pool)


def extract_pool_pages(pool, page_ids):
    """Gather whole pages out of a pool by physical id into a fixed-width
    staging pytree ``(width, page_size, *rest)`` — the serialization side
    of a cross-pool KV handoff (prefill -> decode replica).  ``page_ids``
    is a fixed-width vector; out-of-range entries are padding (clamped for
    the gather, ignored by the host, dropped again at install)."""
    return jax.tree.map(
        lambda leaf: leaf[jnp.clip(page_ids, 0, leaf.shape[0] - 1)], pool)


def install_pool_pages(pool, staged, page_ids):
    """Scatter a staged page pytree (from ``extract_pool_pages`` on another
    replica's pool) into this pool at ``page_ids``.  Whole pages are
    overwritten, so the destination needs no scrub; padding ids point out
    of range and are dropped."""
    return jax.tree.map(
        lambda pl, pg: pl.at[page_ids].set(pg, mode="drop"), pool, staged)


def compact_pool(pool, src_ids, dst_ids):
    """Apply a ``BlockPool.compact`` mapping on-device: move page ``src``
    to ``dst`` for each pair (destinations were free, so gather-then-
    scatter is safe).  Padding entries point out of range and are dropped.
    """
    return jax.tree.map(
        lambda leaf: leaf.at[dst_ids].set(leaf[jnp.clip(
            src_ids, 0, leaf.shape[0] - 1)], mode="drop"), pool)


def apply_block_table_delta(block_table, delta):
    """Apply a fixed-width update vector to the device-resident block
    table (traced).  ``delta`` is ``(width, 3)`` int32 rows of
    ``(slot, logical_page, phys)``:

    * ``slot < 0`` — padding, ignored;
    * ``logical_page < 0`` — clear the whole row to -1 (retire/preempt);
    * otherwise — set one cell (append/COW remap; ``phys`` may be -1 for
      a speculative rollback clearing mapped tail cells).

    Rows apply in order inside one EXECUTE, so a row clear followed by a
    re-mapping of the same slot composes the way the host applied them.
    This replaces the host-authoritative full-table h2d rewrite on the
    decode hot path — only the handful of cells that changed ride along.
    """
    max_blocks = block_table.shape[1]

    def body(i, bt):
        s, lp, v = delta[i, 0], delta[i, 1], delta[i, 2]
        s_safe = jnp.clip(s, 0, bt.shape[0] - 1)
        row = bt[s_safe]
        cell = row.at[jnp.clip(lp, 0, max_blocks - 1)].set(v)
        cleared = jnp.full((max_blocks,), -1, jnp.int32)
        new_row = jnp.where(lp < 0, cleared, cell)
        new_row = jnp.where(s < 0, row, new_row)
        return bt.at[s_safe].set(new_row)

    return jax.lax.fori_loop(0, delta.shape[0], body, block_table)
