"""Buffer state machine and task snapshots (paper §3.4).

Each logical buffer (params, optimizer state, KV caches, input batches, ...)
is tracked with one of three states:

    INIT   allocated, no meaningful device contents
    SYNC   device contents mirrored by a host copy (or reproducible from one)
    DIRTY  device contents newer than any host copy

Eviction/checkpointing saves **only DIRTY buffers** — the paper's key
optimization for cheap preemption (Fig 7): input batches stay SYNC after
their H2D transfer and cost nothing to evict; params/optimizer become DIRTY
after every EXECUTE that writes them.

Buffers registered as **paged** refine dirtiness to page granularity: every
leaf of a paged buffer has the page axis as axis 0 (the serving engine's KV
page pool is the canonical case), and EXECUTE requests report which pages
they wrote.  Evict/checkpoint then serialize only the dirty pages, merging
them into the prior host copy — a decode iteration that touches 4 of 4096
pool pages costs 4 pages of d2h, not the whole pool.
"""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import numpy as np


class BufferState(enum.Enum):
    INIT = "init"
    SYNC = "sync"
    DIRTY = "dirty"


def tree_bytes(tree: Any) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += int(leaf.size) * np.dtype(leaf.dtype).itemsize
    return total


def to_host(tree: Any) -> Any:
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def same_avals(a: Any, b: Any) -> bool:
    """True when two pytrees have identical structure and leaf shape/dtype
    (values ignored) — the invariant the monitor's execute-signature cache
    keys on."""
    if a is None or b is None:
        return False
    if jax.tree.structure(a) != jax.tree.structure(b):
        return False
    return all(
        getattr(x, "shape", None) == getattr(y, "shape", None)
        and getattr(x, "dtype", None) == getattr(y, "dtype", None)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@dataclass
class Buffer:
    buff_id: str
    spec: Any                           # abstract pytree
    state: BufferState = BufferState.INIT
    device_value: Any = None            # pytree of jax arrays (or None)
    host_value: Any = None              # pytree of numpy arrays (or None)
    nbytes: int = 0
    version: int = 0                    # bumped on every device-side write
    spec_token: int = 0                 # bumped only when shapes may change
    # page-granular dirtiness (paged buffers only): every leaf's axis 0 is
    # the page axis; ``page_dirty`` holds ids written since the last host
    # sync, and ``None`` means "unknown — treat every page as dirty"
    paged: bool = False
    page_dirty: Optional[set] = None
    # True while host_value is aliased by a TaskSnapshot: the next merge
    # must copy-on-write instead of patching the snapshot's arrays
    host_shared: bool = False

    def __post_init__(self):
        if not self.nbytes:
            self.nbytes = tree_bytes(self.spec)

    @property
    def n_pages(self) -> int:
        leaves = jax.tree.leaves(
            self.device_value if self.device_value is not None else self.spec)
        return int(leaves[0].shape[0]) if leaves else 0

    def mark_pages_dirty(self, page_ids) -> None:
        if page_ids is None:
            self.page_dirty = None          # degraded to whole-buffer dirty
        elif self.page_dirty is not None:
            self.page_dirty.update(int(p) for p in page_ids)

    def merge_dirty_pages_to_host(self) -> int:
        """Pull only the dirty pages d2h, merging into the host copy.

        Returns the bytes actually saved; falls back to a full ``to_host``
        when no host copy exists or dirtiness is unknown.  Clears the dirty
        set — the host copy is current afterwards.
        """
        n = self.n_pages
        if (not self.paged or self.host_value is None
                or self.page_dirty is None or n == 0):
            self.host_value = to_host(self.device_value)
            self.host_shared = False    # fresh arrays, nothing aliased
            saved = self.nbytes
        elif not self.page_dirty:
            saved = 0
        else:
            ids = np.asarray(sorted(self.page_dirty), np.int64)
            cow = self.host_shared     # a snapshot aliases the host copy

            def merge(host_leaf, dev_leaf):
                out = np.asarray(host_leaf)
                # copy when a snapshot aliases us (COW) or when the leaf
                # is a read-only device_get view; afterwards the buffer
                # owns a writable array and merges patch it in place,
                # keeping steady-state evicts O(pages touched)
                if cow or not out.flags.writeable:
                    out = out.copy()
                out[ids] = np.asarray(jax.device_get(dev_leaf[ids]))
                return out

            self.host_value = jax.tree.map(merge, self.host_value,
                                           self.device_value)
            self.host_shared = False
            saved = int(round(self.nbytes * len(ids) / n))
        self.page_dirty = set() if self.paged else None
        return saved


class BufferTable:
    """Per-task buffer registry with state transitions (monitor-owned)."""

    def __init__(self):
        self._buffers: Dict[str, Buffer] = {}
        # buffers written (h2d or execute) since the last SYNC drain; the
        # monitor's SYNC blocks on exactly these instead of the whole table
        self._unsynced: set = set()

    # -- registry -------------------------------------------------------------
    def register(self, buff_id: str, spec: Any,
                 paged: bool = False) -> Buffer:
        if buff_id in self._buffers:
            raise KeyError(f"buffer {buff_id!r} already exists")
        b = Buffer(buff_id=buff_id, spec=spec, paged=paged)
        self._buffers[buff_id] = b
        return b

    def get(self, buff_id: str) -> Buffer:
        return self._buffers[buff_id]

    def __contains__(self, buff_id: str) -> bool:
        return buff_id in self._buffers

    def ids(self):
        return list(self._buffers)

    def total_bytes(self) -> int:
        return sum(b.nbytes for b in self._buffers.values())

    # -- transitions ----------------------------------------------------------
    def on_h2d(self, buff_id: str, host_value: Any, device_value: Any):
        b = self.get(buff_id)
        # same-shaped overwrites (streamed prompts/batches) keep the spec
        # token so downstream execute-signature cache entries stay warm
        if not same_avals(b.device_value, device_value):
            b.spec_token += 1
        b.host_value = host_value
        b.device_value = device_value
        b.state = BufferState.SYNC
        b.nbytes = tree_bytes(device_value)
        b.version += 1
        if b.paged:
            b.page_dirty = set()        # host copy just became current
            b.host_shared = False       # fresh reference replaced the alias
        self._unsynced.add(buff_id)

    def on_d2h(self, buff_id: str) -> Any:
        b = self.get(buff_id)
        if b.paged:
            b.merge_dirty_pages_to_host()
        else:
            b.host_value = to_host(b.device_value)
        b.state = BufferState.SYNC
        return b.host_value

    def on_execute_write(self, buff_id: str, device_value: Any,
                         stable: bool = False, dirty_pages=None):
        """``stable=True`` marks a write whose shapes are known to match the
        previous contents (same compiled program, same signature): the
        per-leaf byte walk is skipped and the spec token is preserved, so
        the monitor's execute-signature cache stays valid.  ``dirty_pages``
        names the pages a paged buffer's write touched; omitting it on a
        paged buffer degrades that buffer to whole-buffer dirtiness."""
        b = self.get(buff_id)
        b.device_value = device_value
        b.state = BufferState.DIRTY
        if not stable:
            b.nbytes = tree_bytes(device_value)
            b.spec_token += 1
        if b.paged:
            b.mark_pages_dirty(dirty_pages)
        b.version += 1
        self._unsynced.add(buff_id)

    # -- sync tracking --------------------------------------------------------
    def take_unsynced(self) -> list:
        """Ids written since the last drain; clears the pending set."""
        out = list(self._unsynced)
        self._unsynced.clear()
        return out

    def unsynced_count(self) -> int:
        return len(self._unsynced)

    # -- evict / restore --------------------------------------------------------
    def dirty_ids(self):
        return [i for i, b in self._buffers.items()
                if b.state is BufferState.DIRTY]

    def evict_device_state(self) -> dict:
        """Save DIRTY buffers to host, drop all device references.

        Paged buffers save only their dirty pages (merged into the prior
        host copy); the clean remainder counts as skipped, same as a SYNC
        buffer.  Returns stats {saved_bytes, skipped_bytes, n_dirty,
        paged_saved_pages, paged_total_pages}.
        """
        saved = skipped = n_dirty = 0
        paged_saved = paged_total = 0
        for b in self._buffers.values():
            if b.state is BufferState.DIRTY:
                if b.paged:
                    n = b.n_pages
                    n_dirty_pages = (n if b.page_dirty is None
                                     else len(b.page_dirty))
                    part = b.merge_dirty_pages_to_host()
                    saved += part
                    skipped += b.nbytes - part
                    paged_saved += n_dirty_pages
                    paged_total += n
                else:
                    b.host_value = to_host(b.device_value)
                    saved += b.nbytes
                b.state = BufferState.SYNC
                n_dirty += 1
            else:
                skipped += b.nbytes
            b.device_value = None
        self._unsynced.clear()          # every device ref was just dropped
        return {"saved_bytes": saved, "skipped_bytes": skipped,
                "n_dirty": n_dirty, "paged_saved_pages": paged_saved,
                "paged_total_pages": paged_total}

    def restore_device_state(self, device) -> dict:
        """Re-materialize device buffers from host copies onto ``device``."""
        restored = 0
        for b in self._buffers.values():
            if b.host_value is not None:
                b.device_value = jax.device_put(b.host_value, device)
                b.state = BufferState.SYNC
                if b.paged:
                    b.page_dirty = set()    # device mirrors the host copy
                restored += b.nbytes
                self._unsynced.add(b.buff_id)   # device_put is async
        return {"restored_bytes": restored}

    def host_snapshot(self) -> dict:
        """Host-side view for checkpointing: {buff_id: host pytree}.

        The snapshot aliases the live host copies (zero-copy); paged
        buffers are flagged so their next dirty-page merge copies on
        write instead of mutating the snapshot's arrays."""
        out = {}
        for i, b in self._buffers.items():
            if b.host_value is not None:
                out[i] = b.host_value
                if b.paged:
                    b.host_shared = True
        return out

    def versions(self) -> dict:
        return {i: b.version for i, b in self._buffers.items()}

    def spec_map(self) -> dict:
        """Abstract registry of every buffer (incl. INIT ones) — snapshots
        carry this so restore re-registers buffers that had no value yet."""
        return {i: b.spec for i, b in self._buffers.items()}

    def load_snapshot(self, snap: dict, specs: dict | None = None):
        for i, spec in (specs or {}).items():
            if i not in self._buffers:
                self._buffers[i] = Buffer(buff_id=i, spec=spec)
        for i, host_value in snap.items():
            if i not in self._buffers:
                self._buffers[i] = Buffer(buff_id=i, spec=None, nbytes=0)
            b = self._buffers[i]
            b.host_value = host_value
            b.state = BufferState.SYNC
            b.nbytes = tree_bytes(host_value)

    def zero_and_clear(self):
        """Release everything (monitor zeroes freed device memory, §3.4)."""
        self._buffers.clear()
        self._unsynced.clear()


@dataclass
class GuestState:
    """The "VM state" of a task: everything the guest needs to resume.

    Funky snapshots the unikernel's vCPU + dirty guest pages; our guests are
    step-wise resumable tasks, so the VM state is their explicit progress
    record (step counter, RNG seed, data-stream position, user dict).
    """
    step: int = 0
    seed: int = 0
    data_position: int = 0
    user: dict = field(default_factory=dict)

    def clone(self) -> "GuestState":
        return GuestState(self.step, self.seed, self.data_position,
                          dict(self.user))


@dataclass
class TaskSnapshot:
    """A full checkpoint: buffers + guest (VM) state + provenance."""
    task_id: str
    guest_state: GuestState
    buffers: dict                       # buff_id -> host pytree
    program_ids: tuple = ()
    created_at: float = field(default_factory=time.time)
    step: int = 0
    versions: dict = field(default_factory=dict)   # buff_id -> write version
    buffer_specs: dict = field(default_factory=dict)  # full registry

    def nbytes(self) -> int:
        return tree_bytes(self.buffers)
