"""Continuous-batching serving engine (vLLM/Orca-style iteration-level
scheduling on top of the Funky monitor) over **paged** vFPGA device memory.

The engine owns ``slots`` fixed decode lanes.  Each lane is an independent
sequence with its own position counter; one *iteration* advances every
occupied lane by one token through a single vmapped EXECUTE request.
Between iterations the engine retires finished sequences and backfills
freed lanes with prefills of waiting requests — admission happens at
iteration granularity, so a long-running batch never stalls behind a
straggler (the continuous-batching property).

KV memory comes in two modes:

* **paged** (default) — device KV memory is a ``BlockPool`` of fixed-size
  pages shared by every lane.  A per-lane *block table* row maps logical
  page index -> physical page; the vmapped decode step gathers each lane's
  cache through its row and scatters back only the page it wrote.  Lanes
  hold pages at token granularity: prompt pages at admission, one more
  page whenever decode crosses a page boundary, all freed the moment the
  request retires.  Admission is therefore **memory-based** — admit while
  ``free_pages - prompt_pages >= reserve_pages`` — so ``slots`` can exceed
  what worst-case reservations would allow.  If the pool exhausts
  mid-decode the youngest lane is OOM-preempted: its pages are freed and
  its request requeued for deterministic recomputation (greedy decode, so
  the client sees identical tokens).  Freed pages are scrubbed (positions
  invalidated) on reallocation — the §3.4 freed-memory-zeroing rule — so a
  new owner can never attend to a previous lane's tokens.
* **reserved** — the old worst-case layout: every lane owns a
  ``prompt_len + max_new_tokens`` stripe up front.  Kept as the fig15
  baseline the paged mode is measured against.

Paged mode also supports **prompt buckets**: 2-3 prefill lengths compiled
up front, with each admission routed to the smallest bucket that fits
instead of padding everything to one ``prompt_len``.

Paged mode additionally supports **speculative decoding** (``spec=``):
a draft model runs ``k`` lookahead steps per lane in one EXECUTE, then the
target model verifies all ``k+1`` positions in a single vmapped EXECUTE —
sequential in-kernel decode steps over the gathered lane cache, so the
logits at every position are bit-identical to plain greedy decode.  The
host commits the accepted prefix plus the target's own token at the first
mismatch (1..k+1 tokens per iteration), rolls the lane's ``pos`` back past
the rejected tail and frees the orphaned tail pages
(``BlockPool.free_tail``).  Rejected writes left in *kept* pages are
harmless by construction: their ``kv_pos`` exceeds every future query
position until the lane overwrites them in order, and causal masking hides
them until then — which is also why evict/resume mid-lookahead stays
bit-exact (the dirty-page report covers every page the verify wrote,
including partially-accepted ones).  Speculation lives entirely inside one
iteration, so token-boundary preemption, OOM preemption (deterministic
recompute) and drain semantics are unchanged.

The pool auto-defragments: when fragmentation (``1 - used/span``) crosses
``auto_compact_frag`` the engine runs ``compact()`` at the top of the next
iteration — never while pages are referenced by an in-flight EXECUTE.

Every device interaction is a Funky request through ``Monitor.submit``, so
serving stays preemptible at token boundaries: ``Monitor.evict`` between
iterations snapshots the dirty pages plus the (tiny) block table — the
``BufferTable`` tracks the pool at page granularity — and ``resume``
continues every in-flight ragged sequence bit-exactly.

Per-request latencies (TTFT, time-between-tokens, end-to-end) land in the
shared ``repro.scaling.metrics`` registry under the canonical service
schema, together with KV occupancy gauges the autoscaler reads as a memory
pressure signal.
"""

from __future__ import annotations

import heapq
import math
import os
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.guest import FunkyCL
from repro.core.programs import Program
from repro.models.attention import _INVALID_POS
from repro.scaling.autoscaler import (M_COMPLETIONS, M_KV_FREE_PAGES,
                                      M_KV_PAGES, M_PREEMPTIONS,
                                      M_PREFIX_HIT_RATE, M_QUEUE_DEPTH,
                                      M_SLO_VIOLATIONS, M_SPEC_ACCEPT_RATE,
                                      M_UTILIZATION)
from repro.scaling.metrics import MetricsRegistry
from repro.serve.kvcache import (BlockPool, _is_pos_leaf,
                                 apply_block_table_delta, cache_bytes,
                                 compact_pool, extract_pool_pages,
                                 extract_written_page, gather_lane_cache,
                                 init_caches_from_specs, install_pool_pages,
                                 merged_pool_leaves,
                                 pool_specs_from_lane_cache, scatter_pages,
                                 scatter_prefill, scrub_pages,
                                 token_axes_from_lengths)
from repro.serve.prefix_cache import PrefixCache

# Canonical per-request serving metrics (one schema across planes).
M_TTFT = "request_ttft_seconds"
M_TBT = "request_tbt_seconds"
M_E2E = "request_latency_seconds"
M_TOKENS = "engine_tokens_total"
M_ITERS = "engine_iterations_total"
M_SPEC_K = "spec_k"                 # live speculative lookahead per engine
# Host-overhead attribution (per engine): where a token's wall time went.
# device_us is the monitor-measured accelerator phase (compiled-program
# calls + transfer/sync blocking); host_us is everything else in the
# iteration loop (batching, commit/rollback, page bookkeeping); queue_wait
# is the mean monitor worker-queue wait per request.
M_HOST_US = "host_us_per_token"
M_DEVICE_US = "device_us_per_token"
M_QUEUE_WAIT_US = "queue_wait_us"
# k/v pool leaves stored with (heads, head_dim) merged, and their bytes
M_KV_MERGED_LEAVES = "kv_pool_merged_leaves"
M_KV_MERGED_BYTES = "kv_pool_merged_bytes"


@dataclass(frozen=True)
class SpecConfig:
    """Speculative-decode configuration.

    ``draft_arch=None`` self-drafts with the target architecture; combined
    with ``draft_seed=None`` (the engine seed) the draft params equal the
    target params, so every draft token is accepted — the forced-accept
    ceiling.  ``draft_mode="antigreedy"`` makes the draft argmin instead of
    argmax, guaranteeing rejection at every position — the forced-reject
    floor (1 committed token per iteration, like plain decode).  Committed
    token streams are bit-exact vs plain greedy decode for *any* draft.

    ``dynamic_k=True`` adapts the live lookahead between ``k_min`` and
    ``k`` from the engine's acceptance signal (the same accepted/offered
    ratio the ``spec_accept_rate`` gauge publishes): every
    ``adapt_window`` offered drafts the window rate is read — below
    ``shrink_below`` the lookahead shrinks one step (rejected verify work
    stops burning iterations); at/above ``grow_above`` for two consecutive
    windows it regrows one step.  Draft/verify programs are compiled per
    ``k`` value up front, so switching depth never recompiles mid-serve,
    and adaptation only changes throughput — never tokens.
    """
    k: int = 2                          # max lookahead tokens per iteration
    draft_arch: Optional[str] = None    # None -> target arch
    draft_seed: Optional[int] = None    # None -> engine seed
    draft_mode: str = "greedy"          # "greedy" | "antigreedy"
    dynamic_k: bool = False             # adapt live k from acceptance
    k_min: int = 1                      # floor for dynamic shrink
    adapt_window: int = 32              # offered drafts per adaptation step
    shrink_below: float = 0.4           # window accept rate -> shrink
    grow_above: float = 0.8             # sustained window rate -> regrow


@dataclass
class ServeRequest:
    """One generation request admitted into a decode slot."""
    rid: str
    prompt: np.ndarray                  # (P,) int32 token ids
    max_new_tokens: int = 8
    arrival_t: Optional[float] = None   # registry-clock timestamp
    slo_s: Optional[float] = None       # end-to-end SLO (None = untracked)
    # per-request trace (repro.obs.Trace), started by the router (or the
    # engine on direct submit) when a tracer is attached; trace_id == rid
    trace: Any = None
    # committed-token state: aliased to the decode slot's tokens list at
    # admit time, so the router sees exactly what the engine generated if
    # the replica crashes and the request is replayed (no copy per token)
    committed: Optional[List[int]] = None


@dataclass
class CompletedRequest:
    rid: str
    tokens: List[int]
    arrival_t: float
    admit_t: float
    first_token_t: float
    finish_t: float
    tbts: List[float] = field(default_factory=list)

    @property
    def ttft_s(self) -> float:
        return self.first_token_t - self.arrival_t

    @property
    def e2e_s(self) -> float:
        return self.finish_t - self.arrival_t


@dataclass
class _SlotState:
    req: ServeRequest
    slot: int
    tokens: List[int]
    admit_t: float
    first_token_t: float
    last_token_t: float
    tbts: List[float] = field(default_factory=list)
    # effective generation cap: min(request ask, engine cap) — the engine's
    # cache/pages are provisioned for max_new_tokens, so an over-cap ask is
    # clamped instead of walking past the block table / ring capacity
    limit: int = 1
    # paged mode
    bucket: int = 0                     # prompt bucket this lane prefetched
    pos: int = 0                        # absolute position of the next write
    blocks: List[int] = field(default_factory=list)
    span: Any = None                    # engine.decode span (tracing)
    # fused/pipelined decode: tokens whose generation has been *submitted*
    # (committed or riding an in-flight EXECUTE).  Greedy decode with
    # limit-only masking makes token counts deterministic at submit time,
    # so positions and page mapping advance here while token values land
    # at commit.  Kept equal to len(tokens) on the non-pipelined paths.
    submitted: int = 0
    # EXECUTEs in flight that reference this lane's pages — retire (which
    # frees pages) must wait until the count drains back to zero
    inflight: int = 0
    # the lane hit EOS mid-span: the device side froze (or the host rolled
    # it back) and later in-flight spans for this lane are no-ops
    eos_done: bool = False
    # prefix-cache insert deferred until the pipelined first-token read
    # commits: (bucket, flat_prompt, page_ids)
    deferred_insert: Any = None


class ContinuousBatchingEngine:
    def __init__(self, arch: str, cl: FunkyCL, *, slots: int = 4,
                 prompt_len: int = 16, max_new_tokens: int = 16,
                 service: str = "svc", engine_id: str = "engine0",
                 seed: int = 0, registry: Optional[MetricsRegistry] = None,
                 publish_gauges: bool = True, paged: bool = True,
                 page_size: int = 8, pool_pages: Optional[int] = None,
                 reserve_pages: int = 1,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 spec: Optional[SpecConfig] = None,
                 prefix_cache: bool = False,
                 prefix_cache_max_nodes: int = 4096,
                 auto_compact_frag: Optional[float] = 0.5,
                 auto_compact_min_pages: int = 4,
                 fuse_steps: int = 1, async_depth: int = 0,
                 role: str = "mixed", eos_id: Optional[int] = None,
                 tracer: Any = None):
        from repro.configs import get_arch
        from repro.models import build_model

        self.cl = cl
        self.slots = slots
        # disaggregated serving: a `prefill` replica admits prompts and
        # hands freshly prefilled lanes to a `decode` replica through a
        # TransferQueue; `mixed` is the classic aggregated engine.  Roles
        # need paged KV — the handoff moves whole pool pages.
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"unknown role {role!r}")
        if role != "mixed" and not paged:
            raise ValueError("role-disaggregated serving needs paged=True "
                             "(KV handoff moves pool pages)")
        if role != "mixed" and spec is not None:
            raise ValueError("speculative decode is host-authoritative and "
                             "does not survive a lane handoff; use "
                             "role='mixed'")
        self.role = role
        self.transfer = None            # TransferQueue, via attach_transfer
        # on-device stop-token detection: a lane that emits eos_id freezes
        # inside decode_multi (folded into the per-lane lim mask) instead
        # of decoding past EOS until the host window boundary
        if eos_id is not None and spec is not None:
            raise ValueError("eos_id does not compose with spec: verify "
                             "acceptance is host-decided, so EOS commits "
                             "host-side there anyway")
        self.eos_id = eos_id
        self.max_new_tokens = max_new_tokens   # per-request cap
        self.service = service
        self.engine_id = engine_id
        self.seed = seed
        self.cfg = get_arch(arch)
        self.paged = paged
        if spec is not None:
            if not paged:
                raise ValueError("speculative decode needs paged=True (the "
                                 "lookahead rolls back through block tables)")
            if spec.k < 1:
                raise ValueError("spec.k must be >= 1")
            if spec.draft_mode not in ("greedy", "antigreedy"):
                raise ValueError(f"unknown draft_mode {spec.draft_mode!r}")
            if spec.dynamic_k and not 1 <= spec.k_min <= spec.k:
                raise ValueError(
                    f"dynamic k needs 1 <= k_min <= k, got "
                    f"k_min={spec.k_min} k={spec.k}")
        self.spec = spec
        # host-out-of-the-loop decode: fuse_steps > 1 runs k greedy decode
        # steps per EXECUTE in one on-device fori_loop; async_depth > 0
        # lets step() submit iteration N+1's EXECUTE before reading back
        # iteration N's tokens (the monitor's FIFO queue serializes them)
        if fuse_steps < 1:
            raise ValueError("fuse_steps must be >= 1")
        if async_depth < 0:
            raise ValueError("async_depth must be >= 0")
        if (fuse_steps > 1 or async_depth > 0) and not paged:
            raise ValueError("fused/pipelined decode needs paged=True (the "
                             "multi-step program maps its write span "
                             "through block tables)")
        if spec is not None and (fuse_steps > 1 or async_depth > 0):
            raise ValueError(
                "fuse_steps/async_depth do not compose with spec: the "
                "verify program already fuses k+1 positions per EXECUTE "
                "and acceptance is host-decided, so the host cannot be "
                "taken out of that loop")
        self.fuse_steps = fuse_steps
        self.async_depth = async_depth
        # pipelined mode: EXECUTEs (decode spans AND admissions) are
        # committed at a later boundary instead of being waited at the
        # submit site — the host stays off the device hot path
        self._pipelined = fuse_steps > 1 or async_depth > 0
        # spec_k is the provisioning maximum (capacity, scrub width); the
        # *live* lookahead spec_k_now moves in spec_ks under dynamic_k
        self.spec_k = spec.k if spec is not None else 0
        self.spec_k_now = self.spec_k
        if spec is not None and spec.dynamic_k:
            self.spec_ks = tuple(range(spec.k_min, spec.k + 1))
        else:
            self.spec_ks = (self.spec_k,) if spec is not None else ()
        self._adapt_offered = 0
        self._adapt_accepted = 0
        self._grow_streak = 0
        self.auto_compact_frag = auto_compact_frag
        self.auto_compact_min_pages = auto_compact_min_pages
        if prompt_buckets and prompt_len > max(prompt_buckets):
            raise ValueError(
                f"prompt_len {prompt_len} exceeds the largest prompt "
                f"bucket {max(prompt_buckets)}: prompts would be silently "
                "truncated — add prompt_len as the largest bucket")
        if paged:
            self.buckets = tuple(sorted(set(prompt_buckets or (prompt_len,))))
            self.prompt_len = max(self.buckets)
            self.page_size = page_size
            # +headroom: verify (spec) writes up to k positions past the
            # commit horizon, and a fused decode's masked steps write up
            # to fuse_steps-1 positions past a retiring lane's limit —
            # those in-flight slots must never wrap the table
            self.max_ctx = (self.prompt_len + max_new_tokens
                            + max(self.spec_k, fuse_steps - 1))
            self.max_blocks = math.ceil(self.max_ctx / page_size)
            # default pool covers the worst case (no oversubscription);
            # benchmarks/servers pass a smaller pool to oversubscribe
            self.pool_pages = (pool_pages if pool_pages is not None
                               else slots * self.max_blocks)
            if self.pool_pages < self.max_blocks:
                raise ValueError(
                    f"pool of {self.pool_pages} pages cannot hold one "
                    f"worst-case request ({self.max_blocks} pages)")
            max_prompt_pages = math.ceil(self.prompt_len / page_size)
            if self.pool_pages - max_prompt_pages < reserve_pages:
                raise ValueError(
                    f"reserve watermark {reserve_pages} can never clear for "
                    f"a {max_prompt_pages}-page prompt in a "
                    f"{self.pool_pages}-page pool (admission would starve)")
            self.pool = BlockPool(self.pool_pages, page_size,
                                  reserve_pages=reserve_pages)
            # first-touch pages are born scrubbed (init_paged writes
            # INVALID positions pool-wide) — only reused pages need the
            # zeroing EXECUTE; populated at setup, emptied conservatively
            # on restore/evacuate
            self._virgin_pages: set = set()
            # benchmark baselines flip this before setup() to recreate
            # the staged 4-op admission (write + prefill + admit + read)
            # the single-EXECUTE prefill_admit path replaced
            self._legacy_admit = False
            if prefix_cache:
                # page-granular sharing needs every prompt bucket to land
                # on a page boundary: nodes key whole pages, and the
                # chunked prefill writes exactly one page per EXECUTE
                bad = [b for b in self.buckets if b % page_size]
                if bad:
                    raise ValueError(
                        f"prefix_cache needs page-aligned prompt buckets; "
                        f"{bad} not divisible by page_size {page_size}")
                self.prefix = PrefixCache(
                    self.pool, page_size,
                    max_nodes=prefix_cache_max_nodes)
            else:
                self.prefix = None
            self._prefix_max_nodes = prefix_cache_max_nodes
            # paged prefill writes exactly the prompt (margin 0); decode
            # headroom comes from pages appended at token granularity
            self.bundle = build_model(self.cfg, cache_margin=0)
            self._bt_host = np.full((slots, self.max_blocks), -1, np.int32)
            # device-resident block table: _bt_host is a host *mirror*
            # (dirty-page spans, spec rollback math); steady-state updates
            # ship as (slot, logical_page, phys) delta rows applied by the
            # bt_update EXECUTE.  _bt_full forces a full h2d rewrite
            # (setup/compact/evacuate, or delta overflow).
            self._bt_dirty = True
            self._bt_full = True
            self._bt_delta: List[Tuple[int, int, int]] = []
            self._bt_delta_width = max(16, 4 * slots)
            self.bt_delta_execs = 0     # delta-driven device updates
            self.bt_full_writes = 0     # full-table h2d rewrites
            self._first_token: Dict[str, float] = {}
            if spec is not None:
                self.draft_cfg = get_arch(spec.draft_arch or arch)
                # dense per-lane draft cache: capacity must reach the last
                # lookahead write, prompt_len + max_new_tokens + k - 1
                self.draft_bundle = build_model(
                    self.draft_cfg,
                    cache_margin=max_new_tokens + spec.k)
                self.draft_seed = (spec.draft_seed
                                   if spec.draft_seed is not None else seed)
                # host-authoritative lane state: the verify EXECUTE cannot
                # know acceptance, so toks/pos are committed here and
                # rewritten h2d (tiny) before each speculative iteration
                self._toks_host = np.zeros((slots, 1), np.int32)
                self._pos_host = np.zeros((slots,), np.int32)
        else:
            if prompt_buckets:
                raise ValueError("prompt buckets need paged=True (dense "
                                 "lanes are compiled to one prompt_len)")
            if prefix_cache:
                raise ValueError("prefix_cache needs paged=True (sharing "
                                 "maps pool pages through block tables)")
            self.prefix = None
            self.buckets = (prompt_len,)
            self.prompt_len = prompt_len
            # cache capacity = prompt_len + max_new_tokens: prefill reserves
            # the decode headroom so admission is a pure scatter
            self.bundle = build_model(self.cfg, cache_margin=max_new_tokens)
            self.pool = None
        self.registry = (registry if registry is not None
                         else cl._monitor.telemetry)
        self._clock = self.registry.clock
        self._publish_gauges = publish_gauges
        # tracing: explicit tracer wins; else share the monitor's, if any
        self.tracer = (tracer if tracer is not None
                       else getattr(cl._monitor, "tracer", None))
        self._it_root = None            # current iteration's root span
        self._step_completions: List = []
        # pipelined decode: batches of (exec_completion, read_completion,
        # [(slot_state, n_tokens)]) submitted but not yet committed; at
        # most async_depth stay outstanding while new work exists
        self._inflight: deque = deque()
        # set after a failed fused EXECUTE: device toks/pos must be
        # rewritten from the host-authoritative lane state before the next
        # submit (later pipelined EXECUTEs ran against the pre-failure
        # state, leaving the device scalars ahead of the rolled-back host)
        self._resync_lanes = False
        # host/device attribution accumulators (populated from the
        # monitor's per-request phase dicts, tracer or not)
        self._attr_host_s = 0.0
        self._attr_device_s = 0.0
        self._attr_queue_wait_s = 0.0
        self._attr_tokens = 0
        self._attr_execs = 0
        self._attr_reqs = 0
        # handles resolved once — the per-iteration loop never takes the
        # registry lock (same rule as the monitor's dispatch loop)
        self._h_ttft = self.registry.histogram(M_TTFT, service=service)
        self._h_tbt = self.registry.histogram(M_TBT, service=service)
        self._h_e2e = self.registry.histogram(M_E2E, service=service)
        self._c_tokens = self.registry.counter(M_TOKENS, service=service)
        self._c_iters = self.registry.counter(M_ITERS, service=service)
        self._c_completions = self.registry.counter(M_COMPLETIONS,
                                                    service=service)
        self._c_violations = self.registry.counter(M_SLO_VIOLATIONS,
                                                   service=service)
        self._c_preemptions = self.registry.counter(M_PREEMPTIONS,
                                                    service=service)
        if publish_gauges:
            self._g_queue = self.registry.gauge(
                M_QUEUE_DEPTH, service=service, engine=engine_id)
            self._g_util = self.registry.gauge(
                M_UTILIZATION, service=service, engine=engine_id)
            self._g_kv = self.registry.gauge(
                M_KV_PAGES, service=service, engine=engine_id)
            self._g_kv_free = self.registry.gauge(
                M_KV_FREE_PAGES, service=service, engine=engine_id)
            self._g_host_us = self.registry.gauge(
                M_HOST_US, service=service, engine=engine_id)
            self._g_device_us = self.registry.gauge(
                M_DEVICE_US, service=service, engine=engine_id)
            self._g_queue_wait_us = self.registry.gauge(
                M_QUEUE_WAIT_US, service=service, engine=engine_id)
            if spec is not None:
                self._g_spec = self.registry.gauge(
                    M_SPEC_ACCEPT_RATE, service=service, engine=engine_id)
                self._g_spec_k = self.registry.gauge(
                    M_SPEC_K, service=service, engine=engine_id)
                self._g_spec_k.set(self.spec_k_now)
            if self.prefix is not None:
                self._g_prefix = self.registry.gauge(
                    M_PREFIX_HIT_RATE, service=service, engine=engine_id)

        self.pending: deque = deque()
        self._free: List[int] = list(range(slots))
        heapq.heapify(self._free)
        self._active: Dict[int, _SlotState] = {}
        self.completed: Dict[str, CompletedRequest] = {}
        self._unreported: deque = deque()   # completions not yet drained
        self.iterations = 0
        self.peak_active = 0                # max concurrent in-flight lanes
        self.preemptions = 0
        self.auto_compactions = 0
        # prefix-cache accounting (all zero when the cache is off)
        self.prefix_hits = 0                # full-prompt hits (no prefill)
        self.prefix_partial_hits = 0        # suffix-only prefills
        self.prefix_misses = 0
        self.prefix_prompt_tokens = 0       # padded prompt tokens admitted
        self.prefix_cached_tokens = 0       # of those, served from cache
        self.cow_copies = 0                 # shared pages privatized
        # speculative-decode accounting (all zero when spec is off)
        self.spec_iterations = 0            # verify EXECUTEs issued
        self.spec_lane_iterations = 0       # active-lane verify passes
        self.spec_committed = 0             # tokens committed via verify
        self.spec_offered_drafts = 0        # draft tokens that could commit
        self.spec_accepted_drafts = 0
        self._mid_step = False              # pages in flight: no compaction
        self._setup_done = False
        # (program, abstract args, donate_argnums) per registered program,
        # replayed by ``reattach`` when the task lands on another slice
        self._registrations: List[tuple] = []

    # ------------------------------------------------------------------
    # Program/buffer setup (Funky guest-style, via FunkyCL only)
    # ------------------------------------------------------------------
    def setup(self, restore: bool = False) -> None:
        if self.paged:
            self._setup_paged(restore)
        else:
            self._setup_reserved(restore)
        self._setup_done = True

    def program_ids(self) -> tuple:
        return tuple(p.program_id for p, _, _ in self._registrations)

    def _register(self, cl, name, fn, abstracts, donate_argnums=()):
        program = Program(name, fn)
        cl.clCreateProgramWithBinary(program, abstracts,
                                     donate_argnums=donate_argnums)
        self._registrations.append((program, abstracts, donate_argnums))

    def reattach(self, cl: FunkyCL) -> None:
        """Continue on a new vSlice after a migration: the monitor has
        restored the device buffers onto the new slice's device and the
        host lane state travelled with the task, so only the programs are
        registered (compiled) again, for that device."""
        self.cl = cl
        for program, abstracts, donate_argnums in self._registrations:
            cl.clCreateProgramWithBinary(program, abstracts,
                                         donate_argnums=donate_argnums)

    def _prefill_fn(self):
        bundle = self.bundle

        def prefill_one(params, tokens):
            logits, cache = bundle.prefill_fn(params, {"tokens": tokens})
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        return prefill_one

    # -- paged layout ----------------------------------------------------
    def _setup_paged(self, restore: bool) -> None:
        bundle, B, ps = self.bundle, self.slots, self.page_size
        NP, max_blocks = self.pool_pages, self.max_blocks
        prefill_one = self._prefill_fn()

        def init_params(seed):
            return bundle.init(jax.random.PRNGKey(seed))

        params_abs = jax.eval_shape(lambda: init_params(0))
        pf_abs = {}
        for P in self.buckets:
            prompt_abs = jax.ShapeDtypeStruct((1, P), jnp.int32)
            pf_tok_abs, pf_cache_abs = jax.eval_shape(
                prefill_one, params_abs, prompt_abs)
            pf_abs[P] = (prompt_abs, pf_tok_abs, pf_cache_abs)
        # discover each cache leaf's token axis by diffing two prompt
        # lengths (rejects layouts paging cannot virtualize, e.g.
        # window-bounded rings) — buckets give the second length for free
        if len(self.buckets) > 1:
            alt, alt_cache = self.buckets[0], pf_abs[self.buckets[0]][2]
        else:
            alt = self.prompt_len - 1
            if alt < 1:
                raise ValueError("paged mode needs prompt_len >= 2")
            _, alt_cache = jax.eval_shape(
                prefill_one, params_abs,
                jax.ShapeDtypeStruct((1, alt), jnp.int32))
        token_axes = token_axes_from_lengths(
            alt_cache, pf_abs[self.prompt_len][2], alt, self.prompt_len)
        self._token_axes = token_axes
        lane_abs = pf_abs[self.prompt_len][2]
        pool_abs = pool_specs_from_lane_cache(lane_abs, token_axes, NP, ps)
        self._pool_abs = pool_abs
        self.pool_bytes = cache_bytes(pool_abs)
        self.page_bytes = self.pool_bytes // NP
        merged = merged_pool_leaves(pool_abs, lane_abs)
        self.kv_merged_leaves = len(merged)
        self.kv_merged_bytes = cache_bytes(merged)
        self.registry.gauge(M_KV_MERGED_LEAVES, service=self.service,
                            engine=self.engine_id).set(len(merged))
        self.registry.gauge(M_KV_MERGED_BYTES, service=self.service,
                            engine=self.engine_id).set(self.kv_merged_bytes)
        toks_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
        bt_abs = jax.ShapeDtypeStruct((B, max_blocks), jnp.int32)

        def init_paged():
            return (jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32),
                    init_caches_from_specs(pool_abs))

        def decode_step(params, toks, pos, bt, pool):
            def lane(tok, p, bt_row):
                caches = gather_lane_cache(pool, bt_row, lane_abs,
                                           token_axes, page_size=ps)
                logits, new_cache = bundle.decode_fn(params, tok, p, caches)
                new_tok = jnp.argmax(logits, -1).astype(jnp.int32)
                active = bt_row[0] >= 0
                lp = (p % (max_blocks * ps)) // ps
                pages = extract_written_page(new_cache, lp, token_axes,
                                             page_size=ps)
                # the bt_row[lp] >= 0 guard drops writes landing past the
                # lane's mapped span — a pipelined lane awaiting its final
                # commit keeps decoding (garbage, never committed) and may
                # walk onto a page that was never appended
                phys = jnp.where(active & (bt_row[lp] >= 0), bt_row[lp],
                                 jnp.int32(NP))
                new_p = jnp.where(active, p + jnp.int32(1), p)
                return new_tok, new_p, pages, phys

            toks2, pos2, pages, phys = jax.vmap(
                lane, in_axes=(0, 0, 0))(toks, pos, bt)
            return toks2, pos2, scatter_pages(pool, phys, pages)

        # fused multi-step decode: fuse_steps greedy steps per EXECUTE in
        # one on-device fori_loop.  Per-lane ``lim`` (a const arg — the
        # signature cache keys shapes, not values) masks token/pos updates
        # once a lane hits its limit; cache writes past the mask land at
        # positions every future query masks out (the same rejected-tail
        # argument as speculative decode) and unmapped span pages are
        # dropped by the scatter, so no masking of the KV write is needed.
        kf = self.fuse_steps
        eos = self.eos_id

        def decode_multi(params, toks, pos, bt, pool, lims, delta):
            # pending block-table rows ride the fused EXECUTE itself (a
            # const arg, all-sentinel when clean): in the steady state
            # the delta costs zero extra FIFO ops
            bt = apply_block_table_delta(bt, delta)
            n_span = (kf - 1) // ps + 2

            def lane(tok, p, bt_row, lim):
                cache = gather_lane_cache(pool, bt_row, lane_abs,
                                          token_axes, page_size=ps)
                on = bt_row[0] >= 0
                lim = jnp.clip(lim, 0, kf)
                # on-device stop-token detection: EOS folds into the same
                # per-lane mask as the limit, so a lane freezes mid-span —
                # its token stops updating, its position stops advancing,
                # and post-EOS cache writes land at masked-out positions
                # (the rejected-tail argument above).  Entering a span
                # whose input token is already EOS keeps the lane frozen
                # across EXECUTEs.
                done0 = (tok[0] == jnp.int32(eos)) if eos is not None \
                    else jnp.bool_(False)

                def body(i, carry):
                    cur, outs, c, adv, done = carry
                    logits, c2 = bundle.decode_fn(params, cur, p + i, c)
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                    step_on = on & (i < lim) & ~done
                    cur2 = jnp.where(step_on, nxt, cur)
                    if eos is not None:
                        done = done | (step_on & (cur2[0] == jnp.int32(eos)))
                    adv2 = adv + step_on.astype(jnp.int32)
                    return cur2, outs.at[i].set(cur2[0]), c2, adv2, done

                cur, outs, cache, adv, _ = jax.lax.fori_loop(
                    0, kf, body,
                    (tok, jnp.zeros((kf,), jnp.int32), cache,
                     jnp.int32(0), done0))
                lp0 = (p % (max_blocks * ps)) // ps
                pages, phys = [], []
                for j in range(n_span):
                    lp = jnp.minimum(lp0 + jnp.int32(j),
                                     jnp.int32(max_blocks - 1))
                    pages.append(extract_written_page(
                        cache, lp, token_axes, page_size=ps))
                    ok = on & (lp0 + j < max_blocks) & (bt_row[lp] >= 0)
                    phys.append(jnp.where(ok, bt_row[lp], jnp.int32(NP)))
                # adv == lim for active un-frozen lanes; a frozen lane's
                # device position stops at EOS so the host rollback at
                # commit time keeps both sides in lockstep
                new_p = jnp.where(on, p + adv, p)
                return cur, new_p, outs, tuple(pages), jnp.stack(phys)

            toks2, pos2, outs, pages, phys = jax.vmap(
                lane, in_axes=(0, 0, 0, 0))(toks, pos, bt, lims)
            n_span = (kf - 1) // ps + 2
            for j in range(n_span):
                pool = scatter_pages(pool, phys[:, j], pages[j])
            return outs, toks2, pos2, bt, pool

        def bt_update(bt, delta):
            return apply_block_table_delta(bt, delta)

        def scrub(pool, page_ids):
            return scrub_pages(pool, page_ids)

        def compact(pool, src_ids, dst_ids):
            return compact_pool(pool, src_ids, dst_ids)

        cl = self.cl
        self._register(cl, "init_params", init_params, (0,))
        self._register(cl, "init_paged", init_paged, ())
        slot_abs = jnp.int32(0)
        # one lookahead (or one fused k-step span) can append several
        # pages per lane, so the scrub vector is sized for the
        # worst-case per-iteration page growth — and, with the prefix
        # cache, for a whole prompt's fresh suffix pages scrubbed in one
        # EXECUTE before the chunked prefill
        self._scrub_width = B * (max(self.spec_k,
                                     self.fuse_steps - 1) // ps + 2)
        if self.prefix is not None:
            self._scrub_width = max(self._scrub_width, self.prompt_len // ps)
        ids_abs = jax.ShapeDtypeStruct((self._scrub_width,), jnp.int32)
        np_abs = jax.ShapeDtypeStruct((NP,), jnp.int32)
        if self.prefix is None:
            for P, (prompt_abs, pf_tok_abs, pf_cache_abs) in pf_abs.items():
                n_pp = self.pool.pages_for_tokens(P)
                pp_abs = jax.ShapeDtypeStruct((n_pp,), jnp.int32)

                # single-EXECUTE admission: prefill + first-token argmax +
                # lane install + page scatter in one op, the prompt a
                # const arg (shape-keyed signature: one compile per
                # bucket).  Four FIFO ops per admission collapse to one —
                # per-op monitor overhead is the dominant host cost the
                # fused decode path leaves behind.
                def prefill_admit(params, toks, pos, pool, prompt, slot,
                                  page_ids, P=P):
                    pf_tok, pf_cache = prefill_one(params, prompt)
                    slot = jnp.asarray(slot, jnp.int32)
                    toks = jax.lax.dynamic_update_slice(
                        toks, pf_tok[:, None], (slot, jnp.int32(0)))
                    pos = jax.lax.dynamic_update_slice(
                        pos, jnp.full((1,), P, jnp.int32), (slot,))
                    pool = scatter_prefill(pool, page_ids, pf_cache,
                                           token_axes, page_size=ps,
                                           prompt_len=P)
                    return pf_tok, toks, pos, pool

                self._register(
                    cl, f"prefill_admit_{P}", prefill_admit,
                    (params_abs, toks_abs, pos_abs, pool_abs, prompt_abs,
                     slot_abs, pp_abs),
                    donate_argnums=(1, 2, 3))
                if self.spec is None and not self._legacy_admit:
                    continue
                # speculative admission keeps the staged path: the draft
                # prefill reads the same pf_prompt buffer, and the host
                # needs the first token synchronously for its lane mirror
                # (benchmark baselines recreate it via _legacy_admit)
                self._register(cl, f"prefill_{P}", prefill_one,
                               (params_abs, prompt_abs))

                def admit(toks, pos, pool, pf_tok, pf_cache, slot, page_ids,
                          P=P):
                    slot = jnp.asarray(slot, jnp.int32)
                    toks = jax.lax.dynamic_update_slice(
                        toks, pf_tok[:, None], (slot, jnp.int32(0)))
                    pos = jax.lax.dynamic_update_slice(
                        pos, jnp.full((1,), P, jnp.int32), (slot,))
                    pool = scatter_prefill(pool, page_ids, pf_cache,
                                           token_axes, page_size=ps,
                                           prompt_len=P)
                    return toks, pos, pool

                self._register(
                    cl, f"admit_{P}", admit,
                    (toks_abs, pos_abs, pool_abs, pf_tok_abs, pf_cache_abs,
                     slot_abs, pp_abs),
                    donate_argnums=(0, 1, 2))
        else:
            # Prefix-cache mode replaces the fused per-bucket prefill with
            # ONE page-granular chunk program shared by every bucket: each
            # EXECUTE feeds page ``lp``'s tokens sequentially through the
            # decode step over the lane's gathered cache and scatters
            # exactly that page back.  Cold admissions run every chunk; a
            # prefix hit skips the covered ones — and because hit and cold
            # paths run the *same* compiled program over the same inputs,
            # prefix-hit decode is bit-exact vs. a cold run by
            # construction (sequential decode is NOT bitwise identical to
            # fused prefill, so mixing the two paths would break the
            # equivalence gate).
            pf_tok_abs = pf_abs[self.prompt_len][1]
            chunk_abs = jax.ShapeDtypeStruct((ps,), jnp.int32)
            row_abs = jax.ShapeDtypeStruct((max_blocks,), jnp.int32)

            def prefill_chunk(params, pool, chunk_toks, lp, bt_row):
                lp = jnp.asarray(lp, jnp.int32)
                cache = gather_lane_cache(pool, bt_row, lane_abs,
                                          token_axes, page_size=ps)
                pos0 = lp * jnp.int32(ps)
                logits = None
                for i in range(ps):
                    logits, cache = bundle.decode_fn(
                        params, chunk_toks[i][None],
                        pos0 + jnp.int32(i), cache)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                page = extract_written_page(cache, lp, token_axes,
                                            page_size=ps)
                phys = bt_row[lp][None]
                pool = scatter_pages(pool, phys,
                                     jax.tree.map(lambda x: x[None], page))
                return tok, pool

            self._register(cl, "prefill_chunk", prefill_chunk,
                           (params_abs, pool_abs, chunk_abs, slot_abs,
                            row_abs),
                           donate_argnums=(1,))

            def admit_tok(toks, pos, pf_tok, slot, p_end):
                slot = jnp.asarray(slot, jnp.int32)
                toks = jax.lax.dynamic_update_slice(
                    toks, pf_tok[:, None], (slot, jnp.int32(0)))
                pos = jax.lax.dynamic_update_slice(
                    pos, jnp.asarray(p_end, jnp.int32)[None], (slot,))
                return toks, pos

            self._register(cl, "admit_tok", admit_tok,
                           (toks_abs, pos_abs, pf_tok_abs, slot_abs,
                            slot_abs),
                           donate_argnums=(0, 1))
        self._register(cl, "scrub", scrub, (pool_abs, ids_abs),
                       donate_argnums=(0,))
        self._register(cl, "compact_pool", compact,
                       (pool_abs, np_abs, np_abs), donate_argnums=(0,))
        self._register(cl, "decode_step", decode_step,
                       (params_abs, toks_abs, pos_abs, bt_abs, pool_abs),
                       donate_argnums=(1, 2, 4))
        delta_abs = jax.ShapeDtypeStruct((self._bt_delta_width, 3),
                                         jnp.int32)
        self._register(cl, "bt_update", bt_update, (bt_abs, delta_abs),
                       donate_argnums=(0,))
        if kf > 1:
            lims_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
            self._register(cl, "decode_multi", decode_multi,
                           (params_abs, toks_abs, pos_abs, bt_abs, pool_abs,
                            lims_abs, delta_abs),
                           donate_argnums=(1, 2, 3, 4))
        if self.role != "mixed":
            # cross-replica KV handoff: a prefill replica gathers a lane's
            # pages into a fixed-width staging buffer (d2h read follows), a
            # decode replica scatters the staged pages into freshly
            # allocated pages of its own pool and installs the lane
            # scalars.  Out-of-range ids are padding on both sides.
            xfer_ids_abs = jax.ShapeDtypeStruct((max_blocks,), jnp.int32)
            xfer_abs = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((max_blocks,) + l.shape[1:],
                                               l.dtype), pool_abs)
            self._xfer_abs = xfer_abs

            def xfer_extract(pool, page_ids):
                return extract_pool_pages(pool, page_ids)

            def xfer_install(pool, staged, page_ids):
                return install_pool_pages(pool, staged, page_ids)

            def lane_set(toks, pos, tok, p, slot):
                slot = jnp.asarray(slot, jnp.int32)
                toks = jax.lax.dynamic_update_slice(
                    toks, jnp.asarray(tok, jnp.int32).reshape(1, 1),
                    (slot, jnp.int32(0)))
                pos = jax.lax.dynamic_update_slice(
                    pos, jnp.asarray(p, jnp.int32)[None], (slot,))
                return toks, pos

            self._register(cl, "xfer_extract", xfer_extract,
                           (pool_abs, xfer_ids_abs))
            self._register(cl, "xfer_install", xfer_install,
                           (pool_abs, xfer_abs, xfer_ids_abs),
                           donate_argnums=(0,))
            self._register(cl, "lane_set", lane_set,
                           (toks_abs, pos_abs, slot_abs, slot_abs, slot_abs),
                           donate_argnums=(0, 1))
        if self.spec is not None:
            self._setup_spec(params_abs, toks_abs, pos_abs, bt_abs, pool_abs,
                             lane_abs, token_axes)
        if not restore:
            cl.clCreateBuffer("params", params_abs)
            cl.clCreateBuffer("toks", toks_abs)
            cl.clCreateBuffer("pos", pos_abs)
            cl.clCreateBuffer("block_table", bt_abs)
            cl.clCreateBuffer("kv_pool", pool_abs, paged=True)
            cl.clCreateBuffer("pf_tok", pf_abs[self.prompt_len][1])
            if self.role != "mixed":
                cl.clCreateBuffer("xfer_pages", self._xfer_abs)
            if kf > 1:
                cl.clCreateBuffer(
                    "fused_toks", jax.ShapeDtypeStruct((B, kf), jnp.int32))
            for P, (prompt_abs, _, pf_cache_abs) in pf_abs.items():
                # plain paged admission is a single EXECUTE taking the
                # prompt as a const arg (like the prefix cache's chunked
                # path), so the staging prompt/cache buffers only exist
                # for speculative engines: the draft prefill reads the
                # prompt buffer, and the staged admit hands the prefill
                # cache across ops
                if self.spec is not None or self._legacy_admit:
                    cl.clCreateBuffer(f"pf_prompt_{P}", prompt_abs)
                    if self.prefix is None:
                        cl.clCreateBuffer(f"pf_cache_{P}", pf_cache_abs)
            cl.clEnqueueKernel("init_params", (), ("params",),
                               const_args=(self.seed,))
            cl.clEnqueueKernel("init_paged", (),
                               ("toks", "pos", "kv_pool"))
            # the freshly-initialized pool is all-INVALID: every page is
            # clean until its first allocation (restore keeps the set
            # empty — snapshot pool contents are a previous life's)
            self._virgin_pages = set(range(self.pool_pages))
            cl.write_buffer("block_table", self._bt_host.copy())
            if self.spec is not None:
                cl.clCreateBuffer("draft_params", self._draft_params_abs)
                cl.clCreateBuffer("draft_caches", self._draft_caches_abs)
                for v in self.spec_ks:
                    cl.clCreateBuffer(f"draft_toks_k{v}",
                                      self._draft_toks_abs[v])
                    cl.clCreateBuffer(f"verify_toks_k{v}",
                                      self._verify_toks_abs[v])
                for P, (_, dpf_cache_abs) in self._draft_pf_abs.items():
                    cl.clCreateBuffer(f"pf_draft_cache_{P}", dpf_cache_abs)
                cl.clEnqueueKernel("init_draft_params", (),
                                   ("draft_params",),
                                   const_args=(self.draft_seed,))
                cl.clEnqueueKernel("init_draft", (), ("draft_caches",))
            cl.clFinish()
            self._bt_dirty = False
            self._bt_full = False
            self._bt_delta.clear()

    # -- speculative decode: draft + verify programs ---------------------
    def _setup_spec(self, params_abs, toks_abs, pos_abs, bt_abs, pool_abs,
                    lane_abs, token_axes) -> None:
        spec, bundle, dbundle = self.spec, self.bundle, self.draft_bundle
        B, ps, k = self.slots, self.page_size, self.spec_k
        NP, max_blocks = self.pool_pages, self.max_blocks
        argfn = jnp.argmax if spec.draft_mode == "greedy" else jnp.argmin

        def init_draft_params(seed):
            return dbundle.init(jax.random.PRNGKey(seed))

        def draft_prefill_one(dparams, tokens):
            _, cache = dbundle.prefill_fn(dparams, {"tokens": tokens})
            return cache

        dparams_abs = jax.eval_shape(lambda: init_draft_params(0))
        dpf_abs = {}
        for P in self.buckets:
            prompt_abs = jax.ShapeDtypeStruct((1, P), jnp.int32)
            dpf_abs[P] = (prompt_abs, jax.eval_shape(
                draft_prefill_one, dparams_abs, prompt_abs))
        # draft lane capacity is prompt + constant margin, so the token
        # axis is found by size *delta* (exact=False), not size equality
        if len(self.buckets) > 1:
            alt = self.buckets[0]
            alt_cache = dpf_abs[alt][1]
        else:
            alt = self.prompt_len - 1
            alt_cache = jax.eval_shape(
                draft_prefill_one, dparams_abs,
                jax.ShapeDtypeStruct((1, alt), jnp.int32))
        d_axes = token_axes_from_lengths(
            alt_cache, dpf_abs[self.prompt_len][1], alt, self.prompt_len,
            exact=False)
        lane_abs = dpf_abs[self.prompt_len][1]   # largest bucket = stripe
        dcaches_abs = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((B,) + l.shape, l.dtype),
            lane_abs)
        self._draft_params_abs = dparams_abs
        self._draft_caches_abs = dcaches_abs
        # one draft/verify program pair per allowed lookahead depth: a
        # dynamic-k engine switches between precompiled depths (bitstream
        # library), never recompiling mid-serve
        self._draft_toks_abs = {
            v: jax.ShapeDtypeStruct((B, v), jnp.int32)
            for v in self.spec_ks}
        self._verify_toks_abs = {
            v: jax.ShapeDtypeStruct((B, v + 1), jnp.int32)
            for v in self.spec_ks}
        self._draft_pf_abs = dpf_abs

        def init_draft():
            return init_caches_from_specs(dcaches_abs)

        def make_draft_lookahead(v):
            def draft_lookahead(dparams, toks, pos, dcaches):
                # v+1 steps for v offered drafts: the extra step feeds the
                # last draft token back so its KV lands in the draft cache
                # — under full acceptance the commit advances v+1
                # positions, and without it the draft state would grow one
                # hole per iteration (degrading acceptance, never
                # correctness)
                def lane(tok, p, cache):
                    cur, outs = tok, []
                    for i in range(v + 1):
                        logits, cache = dbundle.decode_fn(
                            dparams, cur, p + jnp.int32(i), cache)
                        cur = argfn(logits, -1).astype(jnp.int32)
                        if i < v:
                            outs.append(cur)
                    return jnp.concatenate(outs), cache

                return jax.vmap(lane)(toks, pos, dcaches)
            return draft_lookahead

        def make_verify_step(v):
            # pages one v+1-token write window can span
            n_span = v // ps + 2

            def verify_step(params, toks, d_toks, pos, bt, pool):
                def lane(tok, drafts, p, bt_row):
                    cache = gather_lane_cache(pool, bt_row, lane_abs,
                                              token_axes, page_size=ps)
                    cur, outs = tok, []
                    for i in range(v + 1):
                        logits, cache = bundle.decode_fn(
                            params, cur, p + jnp.int32(i), cache)
                        outs.append(jnp.argmax(logits, -1).astype(jnp.int32))
                        if i < v:
                            cur = drafts[i][None]
                    active = bt_row[0] >= 0
                    lp0 = (p % (max_blocks * ps)) // ps
                    pages, phys = [], []
                    for j in range(n_span):
                        lp = jnp.minimum(lp0 + j, jnp.int32(max_blocks - 1))
                        pages.append(extract_written_page(
                            cache, lp, token_axes, page_size=ps))
                        ok = active & (lp0 + j < max_blocks) \
                            & (bt_row[lp] >= 0)
                        phys.append(jnp.where(ok, bt_row[lp], jnp.int32(NP)))
                    return jnp.concatenate(outs), tuple(pages), \
                        jnp.stack(phys)

                outs, pages, phys = jax.vmap(lane)(toks, d_toks, pos, bt)
                # per-lane pages are disjoint (inactive/unmapped dropped)
                for j in range(n_span):
                    pool = scatter_pages(pool, phys[:, j], pages[j])
                return outs, pool
            return verify_step

        cl = self.cl
        self._register(cl, "init_draft_params", init_draft_params, (0,))
        self._register(cl, "init_draft", init_draft, ())
        for P, (prompt_abs, dpf_cache_abs) in dpf_abs.items():
            self._register(cl, f"draft_prefill_{P}", draft_prefill_one,
                           (dparams_abs, prompt_abs))

            def admit_draft(dcaches, pf_cache, slot):
                slot = jnp.asarray(slot, jnp.int32)

                def upd(path, lane_all, new, axis):
                    tf = jnp.moveaxis(new, axis, 0)
                    pad = lane_all.shape[axis + 1] - tf.shape[0]
                    if pad:
                        fill = (jnp.full((pad,) + tf.shape[1:],
                                         _INVALID_POS, jnp.int32)
                                if _is_pos_leaf(path)
                                else jnp.zeros((pad,) + tf.shape[1:],
                                               tf.dtype))
                        tf = jnp.concatenate([tf, fill])
                    row = jnp.moveaxis(tf, 0, axis)
                    return jax.lax.dynamic_update_slice(
                        lane_all, row[None],
                        (slot,) + (jnp.int32(0),) * row.ndim)

                return jax.tree_util.tree_map_with_path(
                    upd, dcaches, pf_cache, d_axes)

            self._register(cl, f"admit_draft_{P}", admit_draft,
                           (dcaches_abs, dpf_cache_abs, jnp.int32(0)),
                           donate_argnums=(0,))
        for v in self.spec_ks:
            self._register(cl, f"draft_lookahead_k{v}",
                           make_draft_lookahead(v),
                           (dparams_abs, toks_abs, pos_abs, dcaches_abs),
                           donate_argnums=(3,))
            self._register(cl, f"verify_step_k{v}", make_verify_step(v),
                           (params_abs, toks_abs, self._draft_toks_abs[v],
                            pos_abs, bt_abs, pool_abs),
                           donate_argnums=(5,))

    # -- reserved (worst-case stripe) layout -----------------------------
    def _setup_reserved(self, restore: bool) -> None:
        bundle, B, P = self.bundle, self.slots, self.prompt_len
        prefill_one = self._prefill_fn()

        def init_params(seed):
            return bundle.init(jax.random.PRNGKey(seed))

        def decode_step(params, toks, pos, caches):
            def lane(tok, p, cache):
                logits, new_cache = bundle.decode_fn(params, tok, p, cache)
                return (jnp.argmax(logits, -1).astype(jnp.int32),
                        p + jnp.int32(1), new_cache)
            return jax.vmap(lane)(toks, pos, caches)

        def admit_slot(toks, pos, caches, pf_tok, pf_cache, slot):
            slot = jnp.asarray(slot, jnp.int32)
            toks = jax.lax.dynamic_update_slice(
                toks, pf_tok[:, None], (slot, jnp.int32(0)))
            pos = jax.lax.dynamic_update_slice(
                pos, jnp.full((1,), P, jnp.int32), (slot,))
            caches = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_slice(
                    c, n[None], (slot,) + (jnp.int32(0),) * n.ndim),
                caches, pf_cache)
            return toks, pos, caches

        params_abs = jax.eval_shape(lambda: init_params(0))
        prompt_abs = jax.ShapeDtypeStruct((1, P), jnp.int32)
        pf_tok_abs, pf_cache_abs = jax.eval_shape(
            prefill_one, params_abs, prompt_abs)
        caches_abs = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((B,) + l.shape, l.dtype),
            pf_cache_abs)
        toks_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
        self._caches_abs = caches_abs
        self.pool_bytes = cache_bytes(caches_abs)

        def init_slots():
            return (jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32),
                    init_caches_from_specs(caches_abs))

        cl = self.cl
        self._register(cl, "init_params", init_params, (0,))
        self._register(cl, "init_slots", init_slots, ())
        self._register(cl, f"prefill_{P}", prefill_one,
                       (params_abs, prompt_abs))
        slot_abs = jnp.int32(0)
        self._register(
            cl, "admit_slot", admit_slot,
            (toks_abs, pos_abs, caches_abs, pf_tok_abs, pf_cache_abs,
             slot_abs),
            donate_argnums=(0, 1, 2))
        self._register(
            cl, "decode_step", decode_step,
            (params_abs, toks_abs, pos_abs, caches_abs),
            donate_argnums=(1, 2, 3))
        if not restore:
            cl.clCreateBuffer("params", params_abs)
            cl.clCreateBuffer("toks", toks_abs)
            cl.clCreateBuffer("pos", pos_abs)
            cl.clCreateBuffer("caches", caches_abs)
            cl.clCreateBuffer(f"pf_prompt_{P}", prompt_abs)
            cl.clCreateBuffer("pf_tok", pf_tok_abs)
            cl.clCreateBuffer(f"pf_cache_{P}", pf_cache_abs)
            cl.clEnqueueKernel("init_params", (), ("params",),
                               const_args=(self.seed,))
            cl.clEnqueueKernel("init_slots", (), ("toks", "pos", "caches"))
            cl.clFinish()

    # ------------------------------------------------------------------
    # Request intake
    # ------------------------------------------------------------------
    # -- tracked device-op helpers ---------------------------------------
    # Every device op in the serving loop goes through these so the step
    # can fold the monitor's per-request phase dicts (queue wait, device
    # run, transfer bytes) into the engine's host/device attribution.
    def _exec(self, *args, span=None, **kw):
        c = self.cl.clEnqueueKernel(*args, span=span, **kw)
        self._step_completions.append(c)
        return c

    def _write(self, buff_id, host_value, span=None):
        c = self.cl.write_buffer(buff_id, host_value, span=span)
        self._step_completions.append(c)
        return c

    def _read(self, buff_id, span=None):
        c = self.cl.clEnqueueMigrateMemObjects(buff_id, to_device=False,
                                               span=span)
        self._step_completions.append(c)
        try:
            return c.wait()
        except BaseException:
            # the completion stays in _step_completions for phase folding;
            # mark the error surfaced so the step-boundary sweep doesn't
            # raise it a second time
            c.error_seen = True
            raise

    def _read_async(self, buff_id, span=None):
        """d2h read whose wait is deferred to the commit site (pipelined
        decode) — tracked like every other completion."""
        c = self.cl.clEnqueueMigrateMemObjects(buff_id, to_device=False,
                                               span=span)
        self._step_completions.append(c)
        return c

    def submit(self, req: ServeRequest) -> None:
        if req.arrival_t is None:
            req.arrival_t = self._clock()
        if self.tracer is not None and req.trace is None:
            req.trace = self.tracer.start_trace("request", trace_id=req.rid,
                                                service=self.service)
        if req.trace is not None:
            req._eng_queue_span = req.trace.span("engine.queue",
                                                 engine=self.engine_id)
        self.pending.append(req)

    @property
    def idle(self) -> bool:
        return not self._active and not self.pending

    @property
    def active_count(self) -> int:
        return len(self._active)

    def _pick_bucket(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        return self.buckets[-1]         # over-long prompts truncate

    def _pad_prompt(self, prompt: np.ndarray, bucket: int) -> np.ndarray:
        p = np.asarray(prompt, np.int32).reshape(-1)[:bucket]
        if p.shape[0] < bucket:
            p = np.pad(p, (0, bucket - p.shape[0]))
        return p.reshape(1, bucket)

    def kv_stats(self) -> dict:
        """Cache-memory occupancy in the shared byte accounting."""
        if not self.paged:
            return {"paged": False, "pool_bytes": self.pool_bytes,
                    "bytes_in_use": self.pool_bytes, "occupancy": 1.0}
        used = self.pool.used_count()
        return {"paged": True, "pool_bytes": self.pool_bytes,
                "page_bytes": self.page_bytes,
                "merged_leaves": self.kv_merged_leaves,
                "merged_bytes": self.kv_merged_bytes,
                "pages_used": used, "pages_free": self.pool.free_count(),
                "bytes_in_use": used * self.page_bytes,
                "occupancy": self.pool.occupancy(),
                "used_span": self.pool.used_span()}

    # ------------------------------------------------------------------
    # One iteration: admit into free lanes, decode all occupied lanes
    # ------------------------------------------------------------------
    def _admit(self) -> int:
        admitted = 0
        while self._free and self.pending:
            req = self.pending[0]
            bucket = self._pick_bucket(
                np.asarray(req.prompt).reshape(-1).shape[0])
            page_ids = None
            padded = None
            match = None
            if self.paged and self.prefix is not None:
                padded = self._pad_prompt(req.prompt, bucket)
                n_pp = self.pool.pages_for_tokens(bucket)
                match = self.prefix.match(bucket, padded.reshape(-1))
                if len(match.pages) == n_pp and match.next_token is None:
                    # every page matched but the continuation after the
                    # prompt is unknown (pages donated at retire without a
                    # following token) — recompute the last chunk so its
                    # argmax yields the first token
                    match.pages.pop()
                    match.tokens -= self.page_size
                need = n_pp - len(match.pages)
                if not self.pool.can_admit(need):
                    # admission pressure: reclaim cold cache (LRU
                    # subtrees) before refusing — the match just bumped
                    # its own pages' recency, so they are evicted last
                    short = (need + self.pool.reserve_pages
                             - self.pool.free_count())
                    if short > 0:
                        self.prefix.evict_pages(short)
                    if not self.pool.can_admit(need):
                        break
                    # eviction ran: re-match against the surviving tree
                    match = self.prefix.match(bucket, padded.reshape(-1))
                    if (len(match.pages) == n_pp
                            and match.next_token is None):
                        match.pages.pop()
                        match.tokens -= self.page_size
                    need = n_pp - len(match.pages)
                    if not self.pool.can_admit(need):
                        break
                new_ids = self.pool.alloc(need) if need else []
                if new_ids is None:
                    break
                self.pool.share(match.pages)    # this lane's references
                page_ids = list(match.pages) + [int(p) for p in new_ids]
            elif self.paged:
                n_pp = self.pool.pages_for_tokens(bucket)
                if not self.pool.can_admit(n_pp):
                    break               # memory-based admission gate
                page_ids = self.pool.alloc(n_pp)
                # the monolithic prefill scatters these pages whole — no
                # scrub needed, but they are no longer first-touch clean
                self._virgin_pages.difference_update(page_ids)
            self.pending.popleft()
            slot = heapq.heappop(self._free)
            qsp = getattr(req, "_eng_queue_span", None)
            if qsp is not None:
                qsp.end()
                req._eng_queue_span = None
            with obs.span("engine.admit",
                          parent=(req.trace.root if req.trace is not None
                                  else None),
                          engine=self.engine_id, slot=slot,
                          bucket=bucket) as adm:
                admit_cs = []
                read_c = None
                first_tok = None
                deferred_insert = None
                if self.paged and self.prefix is not None:
                    first_tok, read_c, deferred_insert = self._admit_prefix(
                        req, bucket, padded, match, page_ids, slot, adm)
                elif (self.paged and self.spec is None
                        and not self._legacy_admit):
                    # one-EXECUTE admission: prompt rides as a const arg, the
                    # program prefills, installs the lane and scatters the
                    # prompt pages in a single FIFO op
                    admit_cs.append(self._exec(
                        f"prefill_admit_{bucket}",
                        ("params", "toks", "pos", "kv_pool"),
                        ("pf_tok", "toks", "pos", "kv_pool"),
                        const_args=(self._pad_prompt(req.prompt, bucket),
                                    np.int32(slot),
                                    np.asarray(page_ids, np.int32)),
                        donate=True,
                        dirty_pages={"kv_pool": tuple(page_ids)}, span=adm))
                    self._bt_set_row(slot, page_ids)
                    if self._pipelined:
                        # host-out-of-the-loop admission: the first token's
                        # d2h read is deferred to the commit site — the host
                        # never stalls behind the prefill EXECUTE, which now
                        # overlaps this step's decode submit and commit work
                        read_c = self._read_async("pf_tok", span=adm)
                    else:
                        first_tok = int(np.asarray(self._read("pf_tok",
                                                              span=adm))[0])
                else:
                    admit_cs.append(self._write(
                        f"pf_prompt_{bucket}",
                        self._pad_prompt(req.prompt, bucket), span=adm))
                    admit_cs.append(self._exec(
                        f"prefill_{bucket}",
                        ("params", f"pf_prompt_{bucket}"),
                        ("pf_tok", f"pf_cache_{bucket}"), span=adm))
                    if self.paged:
                        admit_cs.append(self._exec(
                            f"admit_{bucket}",
                            ("toks", "pos", "kv_pool", "pf_tok",
                             f"pf_cache_{bucket}"),
                            ("toks", "pos", "kv_pool"),
                            const_args=(np.int32(slot),
                                        np.asarray(page_ids, np.int32)),
                            donate=True,
                            dirty_pages={"kv_pool": tuple(page_ids)},
                            span=adm))
                        self._bt_set_row(slot, page_ids)
                        if self.spec is not None:
                            self._exec(
                                f"draft_prefill_{bucket}",
                                ("draft_params", f"pf_prompt_{bucket}"),
                                (f"pf_draft_cache_{bucket}",), span=adm)
                            self._exec(
                                f"admit_draft_{bucket}",
                                ("draft_caches", f"pf_draft_cache_{bucket}"),
                                ("draft_caches",),
                                const_args=(np.int32(slot),), donate=True,
                                span=adm)
                    else:
                        self._exec(
                            "admit_slot",
                            ("toks", "pos", "caches", "pf_tok",
                             f"pf_cache_{bucket}"),
                            ("toks", "pos", "caches"),
                            const_args=(np.int32(slot),), donate=True,
                            span=adm)
                    # staged path (spec / reserved): the host mirror needs the
                    # first token synchronously
                    first_tok = int(np.asarray(self._read("pf_tok",
                                                          span=adm))[0])
            if self.spec is not None:
                self._toks_host[slot, 0] = first_tok
                self._pos_host[slot] = bucket
            now = self._clock()
            st = _SlotState(req=req, slot=slot,
                            tokens=[] if read_c is not None
                            else [first_tok],
                            submitted=1,
                            admit_t=now, first_token_t=now,
                            last_token_t=now,
                            limit=max(1, min(req.max_new_tokens,
                                             self.max_new_tokens)),
                            bucket=bucket, pos=bucket,
                            blocks=list(page_ids) if page_ids else [],
                            span=(req.trace.span("engine.decode",
                                                 engine=self.engine_id,
                                                 slot=slot)
                                  if req.trace is not None else None))
            st.deferred_insert = deferred_insert
            req.committed = st.tokens   # alias: crash-replay bookkeeping
            self.registry.record_event("engine_admit", rid=req.rid,
                                       slot=slot, engine=self.engine_id)
            if (read_c is None and self.eos_id is not None
                    and first_tok == self.eos_id):
                st.limit = 1            # prompt's continuation IS the stop
            if read_c is not None:
                # deferred admission: the lane decodes in this step's
                # fused EXECUTE (its device state is set by the admit
                # EXECUTE ahead of it in the FIFO); only the first token's
                # *value* and the TTFT observation wait for the commit
                self._active[slot] = st
                self._inflight.append(("admit", st, read_c,
                                       tuple(admit_cs)))
                continue
            st.first_token_t = self._observe_first_token(req, now)
            self._c_tokens.inc()
            admitted += 1
            if len(st.tokens) >= st.limit:
                self._retire(st, now)       # degenerate 1-token request
            else:
                self._active[slot] = st
        return admitted

    def _observe_first_token(self, req, now: float) -> float:
        """TTFT bookkeeping at first-token delivery; returns the moment
        the client first saw a token for this rid (an OOM-preempted
        request recomputes, but keeps its original TTFT)."""
        if self.paged:
            prior = self._first_token.get(req.rid)
            if prior is not None:
                return prior
            self._first_token[req.rid] = now
        self._h_ttft.observe(now - req.arrival_t)
        return now

    def _admit_prefix(self, req, bucket, padded, match, page_ids, slot,
                      adm):
        """Admission over the prefix cache: map the matched pages, chunk-
        prefill only the uncovered suffix.  A full-prompt match skips
        device compute entirely — the tree's stored greedy continuation IS
        the first token, delivered host-side while the (tiny) lane-state
        update rides the queue.  Finally the prompt's pages are donated to
        the tree so same-prefix requests (including this request's own OOM
        recompute) hit.

        Returns ``(first_tok, read_c, deferred_insert)``: on a pipelined
        engine the suffix prefill rides the async pipeline like plain
        paged admits — ``first_tok`` is None, the deferred ``read_c``
        commits later, and the tree insert (which needs the first token)
        is parked on the lane until then.  Prompt buckets are page-aligned
        in prefix mode, so decode writes can never land in a prompt page
        before the deferred insert happens."""
        ps = self.page_size
        n_pp = len(page_ids)
        flat = padded.reshape(-1)
        n_hit = len(match.pages)
        full_hit = n_hit == n_pp and match.next_token is not None
        self._bt_set_row(slot, page_ids)
        self.prefix_prompt_tokens += bucket
        self.prefix_cached_tokens += bucket if full_hit else n_hit * ps
        if full_hit:
            self.prefix_hits += 1
            first_tok = int(match.next_token)
            self._write("pf_tok", np.asarray([first_tok], np.int32),
                        span=adm)
            if adm is not None:
                adm.annotate(prefix_hit="full", cached_pages=n_hit)
        else:
            self.prefix_partial_hits += 1 if n_hit else 0
            self.prefix_misses += 0 if n_hit else 1
            new_ids = page_ids[n_hit:]
            # §3.4 freed-memory zeroing: the chunk gather must see INVALID
            # positions in the fresh suffix pages, never a previous
            # owner's tokens (first-touch pages already read INVALID)
            scrub_new = self._scrub_needed(new_ids)
            if scrub_new:
                ids = np.full((self._scrub_width,), self.pool_pages,
                              np.int32)
                ids[:len(scrub_new)] = scrub_new
                self._exec("scrub", ("kv_pool",), ("kv_pool",),
                           const_args=(ids,), donate=True,
                           dirty_pages={"kv_pool": tuple(scrub_new)},
                           span=adm)
            row = self._bt_host[slot].copy()
            for c in range(n_hit, n_pp):
                self._exec(
                    "prefill_chunk", ("params", "kv_pool"),
                    ("pf_tok", "kv_pool"),
                    const_args=(flat[c * ps:(c + 1) * ps].astype(np.int32),
                                np.int32(c), row),
                    donate=True,
                    dirty_pages={"kv_pool": (int(page_ids[c]),)},
                    span=adm)
            first_tok = None
            if adm is not None:
                adm.annotate(prefix_hit="partial" if n_hit else "miss",
                             cached_pages=n_hit, chunks=n_pp - n_hit)
        self._exec("admit_tok", ("toks", "pos", "pf_tok"),
                   ("toks", "pos"),
                   const_args=(np.int32(slot), np.int32(bucket)),
                   donate=True, span=adm)
        if self.spec is not None:
            # the draft lane has no paging: its dense prefill always runs
            # in full (throughput only — draft state never changes tokens)
            self._write(f"pf_prompt_{bucket}", padded, span=adm)
            self._exec(f"draft_prefill_{bucket}",
                       ("draft_params", f"pf_prompt_{bucket}"),
                       (f"pf_draft_cache_{bucket}",), span=adm)
            self._exec(f"admit_draft_{bucket}",
                       ("draft_caches", f"pf_draft_cache_{bucket}"),
                       ("draft_caches",),
                       const_args=(np.int32(slot),), donate=True, span=adm)
        if first_tok is None and self._pipelined and n_hit:
            # prefix-HIT lanes ride the pipeline: the suffix prefill's
            # first-token read defers to the commit site and the tree
            # insert (which needs that token as the continuation hint) is
            # parked on the lane.  MISS lanes keep the synchronous read:
            # their insert seeds the tree, and a same-step sibling with
            # the same prompt must be able to full-match it — parking the
            # miss insert would cost that hit, and dropping the hint
            # would downgrade it to a re-derived partial.
            read_c = self._read_async("pf_tok", span=adm)
            return None, read_c, (bucket, flat.copy(), list(page_ids))
        if first_tok is None:
            first_tok = int(np.asarray(self._read("pf_tok", span=adm))[0])
        self.prefix.insert(bucket, flat, page_ids, first_tok)
        return first_tok, None, None

    def _retire(self, st: _SlotState, now: float) -> None:
        rec = CompletedRequest(
            rid=st.req.rid, tokens=st.tokens, arrival_t=st.req.arrival_t,
            admit_t=st.admit_t, first_token_t=st.first_token_t,
            finish_t=now, tbts=st.tbts)
        self.completed[st.req.rid] = rec
        self._unreported.append(rec)
        self._active.pop(st.slot, None)
        heapq.heappush(self._free, st.slot)
        if self.paged:
            if self.prefix is not None and st.blocks:
                # donate every fully *committed* page (prompt + generated)
                # to the tree before dropping the lane's references: a
                # later request sharing this sequence as its prompt prefix
                # maps the pages instead of recomputing them.  The page
                # holding positions >= pos is excluded — it may hold
                # rejected speculative writes past the commit horizon.
                ps = self.page_size
                flat = self._pad_prompt(st.req.prompt,
                                        st.bucket).reshape(-1)
                full = np.concatenate(
                    [flat, np.asarray(st.tokens, np.int32)])
                n_complete = min(st.pos // ps, len(st.blocks))
                if n_complete:
                    nxt = (int(full[n_complete * ps])
                           if n_complete * ps < len(full) else None)
                    self.prefix.insert(st.bucket,
                                       full[:n_complete * ps],
                                       st.blocks[:n_complete], nxt)
            # the lane's references return to the pool the moment the
            # request retires (pages the prefix cache pinned survive); the
            # cleared row deactivates the lane for the next decode gather
            self.pool.free(st.blocks)
            self._bt_clear_row(st.slot)
            self._first_token.pop(st.req.rid, None)
        self._h_e2e.observe(rec.e2e_s)
        self._c_completions.inc()
        if st.req.slo_s is not None and rec.e2e_s > st.req.slo_s:
            self._c_violations.inc()
        self.registry.record_event("engine_retire", rid=st.req.rid,
                                   slot=st.slot, tokens=len(st.tokens),
                                   engine=self.engine_id)
        if st.span is not None:
            st.span.annotate(tokens=len(st.tokens)).end()
        if st.req.trace is not None:
            st.req.trace.finish(tokens=len(st.tokens),
                                engine=self.engine_id)

    # -- paged-mode page lifecycle ---------------------------------------
    def _pick_victim(self) -> _SlotState:
        """Youngest admission loses (its recomputation is cheapest); the
        oldest lane always keeps making progress, so the engine never
        livelocks as long as the pool holds one worst-case request."""
        return max(self._active.values(), key=lambda s: (s.admit_t, s.slot))

    def _preempt(self, st: _SlotState) -> None:
        self.pool.free(st.blocks)
        self._bt_clear_row(st.slot)
        self._active.pop(st.slot)
        heapq.heappush(self._free, st.slot)
        self.pending.appendleft(st.req)     # deterministic recompute
        self.preemptions += 1
        self._c_preemptions.inc()
        self.registry.record_event("engine_oom_preempt", rid=st.req.rid,
                                   slot=st.slot, engine=self.engine_id)
        if st.span is not None:
            st.span.annotate(preempted=True,
                             tokens_discarded=len(st.tokens)).end()
        if st.req.trace is not None:
            # requeued whole: a fresh queue span covers the wait until the
            # deterministic re-admission
            st.req._eng_queue_span = st.req.trace.span(
                "engine.queue", engine=self.engine_id, requeued=True)

    def _scrub_needed(self, ids) -> List[int]:
        """Split freshly-allocated pages into the subset that needs the
        freed-memory zeroing EXECUTE: first-touch pages already read
        INVALID (init_paged), only pages a previous owner wrote must be
        scrubbed.  Removes ``ids`` from the virgin set either way."""
        need = [p for p in ids if p not in self._virgin_pages]
        self._virgin_pages.difference_update(ids)
        return need

    def _alloc_urgent(self) -> Optional[List[int]]:
        """One-page urgent allocation; when the pool is dry, cold prefix
        cache is reclaimed before the caller escalates to preemption —
        dropping cached pages never costs a running request its work."""
        got = self.pool.alloc(1, urgent=True)
        if got is None and self.prefix is not None \
                and self.prefix.evict_pages(1):
            got = self.pool.alloc(1, urgent=True)
        return got

    def _cow_pages(self, st: _SlotState, lp_first: int,
                   lp_last: int) -> bool:
        """Privatize shared pages in the lane's write window [lp_first,
        lp_last]: allocate a fresh page, copy the shared page's bytes
        on-device (the copy is reported newly dirty so evict/checkpoint
        stays crash-consistent), swap the block-table entry, and drop this
        lane's shared reference.  Returns False if the lane preempted
        itself acquiring the copy."""
        for lp in range(lp_first, min(lp_last + 1, len(st.blocks))):
            old = st.blocks[lp]
            if self.pool.refcount(old) <= 1:
                continue
            got = self._alloc_urgent()
            while got is None:
                victim = self._pick_victim()
                self._preempt(victim)
                if victim is st:
                    return False
                got = self._alloc_urgent()
            new = got[0]
            self._virgin_pages.discard(new)     # copied into whole
            src = np.full((self.pool_pages,), self.pool_pages, np.int32)
            dst = np.full((self.pool_pages,), self.pool_pages, np.int32)
            src[0], dst[0] = old, new
            self._exec("compact_pool", ("kv_pool",), ("kv_pool",),
                       const_args=(src, dst), donate=True,
                       dirty_pages={"kv_pool": (new,)},
                       span=self._it_root)
            self.pool.free([old])       # drop this lane's shared reference
            st.blocks[lp] = new
            self._bt_set_cell(st.slot, lp, new)
            self.cow_copies += 1
            self.registry.record_event("engine_cow", rid=st.req.rid,
                                       slot=st.slot, page_from=old,
                                       page_to=new, engine=self.engine_id)
        return True

    def _append_pages(self) -> None:
        """Token-granularity growth: map the page(s) each lane's next write
        window lands in — one page for plain decode, up to the ``k+1``-token
        lookahead span for speculative decode (capped at the tokens the lane
        can still commit) — preempting the youngest lane(s) when the pool
        runs dry.  A lane preempted here mid-lookahead is requeued whole and
        recomputes deterministically."""
        scrub_ids: List[int] = []
        for slot in sorted(self._active):
            st = self._active.get(slot)
            if st is None:
                continue                # preempted by an earlier append
            if self.spec is not None:
                span_tok = min(self.spec_k_now + 1,
                               st.limit - len(st.tokens))
            elif self.fuse_steps > 1 or self.async_depth > 0:
                # fused decode: pre-map the whole k-step span (same
                # lookahead-span mapping as speculative decode)
                span_tok = min(self.fuse_steps, st.limit - st.submitted)
                if span_tok <= 0:
                    continue    # fully submitted: awaiting pipeline commit
            else:
                span_tok = 1
            lp_first = st.pos // self.page_size
            lp_last = (st.pos + span_tok - 1) // self.page_size
            # copy-on-write guard: a mapped page inside the imminent write
            # window that is still shared (prefix cache / another lane)
            # gets a private copy before any write can land in it
            if self.prefix is not None and not self._cow_pages(
                    st, lp_first, lp_last):
                continue                # st preempted itself during COW
            dead = False
            for lp in range(len(st.blocks), lp_last + 1):
                got = self._alloc_urgent()
                while got is None:
                    victim = self._pick_victim()
                    self._preempt(victim)
                    if victim is st:
                        dead = True     # st preempted itself: all freed
                        break
                    got = self._alloc_urgent()
                if dead:
                    break
                assert lp == len(st.blocks), (lp, st.blocks)
                st.blocks.append(got[0])
                self._bt_set_cell(slot, lp, got[0])
                scrub_ids.append(got[0])
        scrub_ids = self._scrub_needed(scrub_ids)
        if scrub_ids:
            assert len(scrub_ids) <= self._scrub_width
            ids = np.full((self._scrub_width,), self.pool_pages, np.int32)
            ids[:len(scrub_ids)] = scrub_ids
            self._exec(
                "scrub", ("kv_pool",), ("kv_pool",), const_args=(ids,),
                donate=True, dirty_pages={"kv_pool": tuple(scrub_ids)},
                span=self._it_root)

    def compact(self) -> dict:
        """Defragment the pool: pack used pages into the lowest physical
        ids (shrinks the evict-time dirty-page span after churn).  Call
        between iterations only."""
        if not self.paged:
            return {"moved": 0}
        if self._mid_step:
            raise RuntimeError(
                "compact() while pages are in flight: an iteration's "
                "EXECUTEs reference physical page ids — compaction is only "
                "legal between engine iterations")
        if self._inflight:
            # commit every pipelined batch first: their EXECUTEs were
            # submitted against pre-compaction physical page ids
            self._drain_pipeline()
        mapping = self.pool.compact()
        if mapping:
            # move targets receive a whole page's bytes; move sources
            # keep their stale content and were never virgin anyway
            self._virgin_pages.difference_update(mapping.values())
            src = np.full((self.pool_pages,), self.pool_pages, np.int32)
            dst = np.full((self.pool_pages,), self.pool_pages, np.int32)
            src[:len(mapping)] = list(mapping.keys())
            dst[:len(mapping)] = list(mapping.values())
            self._exec(
                "compact_pool", ("kv_pool",), ("kv_pool",),
                const_args=(src, dst), donate=True,
                dirty_pages={"kv_pool": tuple(mapping.values())},
                span=self._it_root)
            for st in self._active.values():
                st.blocks = [mapping.get(p, p) for p in st.blocks]
                self._bt_host[st.slot, :len(st.blocks)] = st.blocks
            if self.prefix is not None:
                # share-aware compaction: every owner of a moved page is
                # remapped from the same mapping — lanes above, tree here
                self.prefix.remap(mapping)
            self._bt_mark_full()
        return {"moved": len(mapping), "span": self.pool.used_span()}

    def _should_auto_compact(self) -> bool:
        if self.auto_compact_frag is None:
            return False
        used, span = self.pool.used_count(), self.pool.used_span()
        if used == 0 or span - used < self.auto_compact_min_pages:
            return False
        return 1.0 - used / span >= self.auto_compact_frag

    def _maybe_auto_compact(self) -> None:
        """Threshold-triggered defragmentation, fired at the top of an
        iteration — the only point where no EXECUTE holds page ids."""
        if not self._should_auto_compact():
            return
        used, span = self.pool.used_count(), self.pool.used_span()
        with obs.span("engine.pages"):
            self.compact()
        self.auto_compactions += 1
        self.registry.record_event("engine_auto_compact",
                                   engine=self.engine_id, used=used,
                                   span_before=span)

    # -- device-resident block table -------------------------------------
    def _bt_set_row(self, slot: int, page_ids) -> None:
        self._bt_host[slot, :] = -1
        self._bt_host[slot, :len(page_ids)] = page_ids
        self._bt_delta.append((slot, -1, -1))
        self._bt_delta.extend(
            (slot, lp, int(p)) for lp, p in enumerate(page_ids))
        self._bt_dirty = True

    def _bt_clear_row(self, slot: int) -> None:
        self._bt_host[slot, :] = -1
        self._bt_delta.append((slot, -1, -1))
        self._bt_dirty = True

    def _bt_set_cell(self, slot: int, lp: int, phys: int) -> None:
        self._bt_host[slot, lp] = phys
        self._bt_delta.append((slot, lp, int(phys)))
        self._bt_dirty = True

    def _bt_mark_full(self) -> None:
        """Bulk rewrites (compact/evacuate/restore) skip the delta path."""
        self._bt_full = True
        self._bt_delta.clear()
        self._bt_dirty = True

    def _bt_take_delta(self) -> np.ndarray:
        """Claim pending block-table rows for in-program application by
        the fused decode EXECUTE — in the steady state the delta rides
        an EXECUTE the iteration issues anyway, costing zero extra FIFO
        ops.  Forced rewrites (compact/restore) and overflowing deltas
        still take the full h2d write here; the returned delta is then
        all-sentinel, a no-op for ``apply_block_table_delta``."""
        if self._bt_dirty and (self._bt_full or
                               len(self._bt_delta) > self._bt_delta_width):
            self._flush_block_table()
        delta = np.full((self._bt_delta_width, 3), -1, np.int32)
        if self._bt_delta:
            delta[:len(self._bt_delta)] = self._bt_delta
            self._bt_delta.clear()
            self.bt_delta_execs += 1
        self._bt_dirty = False
        self._bt_full = False
        return delta

    def _flush_block_table(self) -> None:
        """Ship pending block-table changes to the device: a small
        bt_update EXECUTE applying the accumulated delta rows in the
        steady state, a full h2d rewrite when one was forced (or the
        delta outgrew its fixed-width buffer)."""
        if not self._bt_dirty:
            return
        with obs.span("engine.pages"):
            if self._bt_full or len(self._bt_delta) > self._bt_delta_width:
                self._write("block_table", self._bt_host.copy(),
                            span=self._it_root)
                self.bt_full_writes += 1
            else:
                delta = np.full((self._bt_delta_width, 3), -1, np.int32)
                if self._bt_delta:
                    delta[:len(self._bt_delta)] = self._bt_delta
                self._exec("bt_update", ("block_table",), ("block_table",),
                           const_args=(delta,), donate=True,
                           span=self._it_root)
                self.bt_delta_execs += 1
            self._bt_full = False
            self._bt_delta.clear()
            self._bt_dirty = False

    def _commit_tokens(self, st: _SlotState, tokens, now: float, *,
                       advance: bool = True) -> int:
        """Append committed tokens to a lane; the first token carries the
        inter-token gap, the rest arrived in the same burst (TBT 0).
        Retirement stays at the call site — the speculative path must roll
        back the page tail first.  ``advance=False`` (pipelined decode)
        skips the position/submitted bump: it already happened at submit
        time, when the token count was determined."""
        for i, t in enumerate(tokens):
            st.tokens.append(int(t))
            tbt = (now - st.last_token_t) if i == 0 else 0.0
            st.tbts.append(tbt)
            self._h_tbt.observe(tbt)
        st.last_token_t = now
        if advance:
            st.pos += len(tokens)
            st.submitted = len(st.tokens)
        return len(tokens)

    # -- host-out-of-the-loop decode: fused multi-step + async pipeline --
    def _fused_iteration(self) -> int:
        """Submit one fused EXECUTE covering up to ``fuse_steps`` greedy
        tokens per lane, then commit the oldest in-flight batch(es).

        With ``async_depth > 0`` the submit goes to the monitor's FIFO
        queue *before* the previous iteration's tokens are read back, so
        host commit work overlaps device execution.  Token counts are
        deterministic at submit time (greedy sampling; the only early
        exit is the per-lane limit), so positions, ``submitted`` counters
        and page mapping advance at submit — only the token *values*
        arrive at commit."""
        kf, ps = self.fuse_steps, self.page_size
        # lanes finished by an earlier commit but kept active while later
        # in-flight EXECUTEs still referenced their pages (EOS mid-span,
        # or a dropped pipeline) retire here once the references drained
        for slot in sorted(self._active):
            st = self._active[slot]
            if (st.tokens and len(st.tokens) >= st.limit
                    and st.inflight == 0):
                self._retire(st, self._clock())
        entries: List[Tuple[_SlotState, int]] = []
        lims = np.zeros((self.slots,), np.int32)
        for slot in sorted(self._active):
            st = self._active[slot]
            n = min(kf, st.limit - st.submitted)
            if n > 0:
                entries.append((st, n))
                lims[slot] = n
        decoded = 0
        if entries:
            if self._resync_lanes:
                # a dropped pipeline left the device's toks/pos scalars
                # ahead of the host's rolled-back commit horizon — rewrite
                # them from the host-authoritative lane state (KV pages
                # need no repair: greedy decode rewrites the same values
                # at the same positions on resubmit).  Deferred admissions
                # from this step must commit first so every active lane
                # has a host-known last token to resync from.
                while self._inflight and self._inflight[0][0] == "admit":
                    decoded += self._commit_fused()
                toks_h = np.zeros((self.slots, 1), np.int32)
                pos_h = np.zeros((self.slots,), np.int32)
                for slot, st in self._active.items():
                    toks_h[slot, 0] = st.tokens[-1]
                    pos_h[slot] = st.pos
                self._write("toks", toks_h, span=self._it_root)
                self._write("pos", pos_h, span=self._it_root)
                self._resync_lanes = False
            delta = self._bt_take_delta() if kf > 1 else None
            if kf == 1:
                self._flush_block_table()
            # every active lane's write window is dirty — masked steps
            # past a lane's limit still write its mapped tail page
            dirty = set()
            for st in self._active.values():
                for lp in range(st.pos // ps,
                                min((st.pos + kf - 1) // ps,
                                    self.max_blocks - 1) + 1):
                    pid = int(self._bt_host[st.slot, lp])
                    if pid >= 0:
                        dirty.add(pid)
            if kf > 1:
                exec_c = self._exec(
                    "decode_multi",
                    ("params", "toks", "pos", "block_table", "kv_pool"),
                    ("fused_toks", "toks", "pos", "block_table", "kv_pool"),
                    donate=True,
                    const_args=(lims, delta),
                    dirty_pages={"kv_pool": tuple(sorted(dirty))},
                    span=self._it_root)
                read_c = self._read_async("fused_toks", span=self._it_root)
            else:
                exec_c = self._exec(
                    "decode_step",
                    ("params", "toks", "pos", "block_table", "kv_pool"),
                    ("toks", "pos", "kv_pool"), donate=True,
                    dirty_pages={"kv_pool": tuple(sorted(dirty))},
                    span=self._it_root)
                read_c = self._read_async("toks", span=self._it_root)
            for st, n in entries:
                st.submitted += n
                st.pos += n
                st.inflight += 1
            self._inflight.append(("batch", exec_c, read_c, entries))
        # only decode batches count against the pipeline depth: a deferred
        # admission commits when it reaches the head naturally — popping it
        # in its own step would stall the host on the prefill EXECUTE it
        # just enqueued, re-serializing exactly what the deferral hides
        if entries:
            while sum(1 for r in self._inflight
                      if r[0] == "batch") > self.async_depth:
                decoded += self._commit_fused()
        else:
            decoded += self._drain_pipeline()
        return decoded

    def _commit_fused(self) -> int:
        """Read back and commit the oldest in-flight record — a fused
        decode batch or a deferred admission.  A failed EXECUTE drops the
        whole pipeline and rolls the submit-time advance back: the
        monitor raises *before* any output buffer is written, so the
        failed span's device state is untouched and the next iteration
        resubmits it — bit-exact, since greedy decode recomputes the
        same tokens."""
        with obs.span("engine.commit"):
            rec = self._inflight.popleft()
            kind, read_c = rec[0], rec[2]
            err = None
            try:
                val = np.asarray(read_c.wait())
            except BaseException as e:  # noqa: BLE001 - surfaced below
                read_c.error_seen = True
                err = e
            if err is None:
                # FIFO: the read completing proves every EXECUTE ahead of
                # it was processed — surface their failures instead of
                # committing stale bytes (a failed prefill leaves pf_tok
                # untouched, and the read of those stale bytes itself
                # succeeds)
                for c in ((rec[1],) if kind == "batch" else rec[3]):
                    if c.error is not None:
                        c.error_seen = True
                        err = c.error
                        break
            if err is not None:
                self._fail_pipeline([rec] + list(self._inflight))
                raise err
            now = self._clock()
            if kind == "admit":
                st = rec[1]
                if self._active.get(st.slot) is not st:
                    return 0    # preempted since submit: recompute replays it
                tok = int(val[0])
                st.first_token_t = self._observe_first_token(st.req, now)
                st.tokens.append(tok)
                st.last_token_t = now
                self._c_tokens.inc()
                if st.deferred_insert is not None:
                    # prefix insert parked at admission: the tree needs the
                    # first token, which only just arrived
                    b, flat, ids = st.deferred_insert
                    self.prefix.insert(b, flat, ids, tok)
                    st.deferred_insert = None
                if self.eos_id is not None and tok == self.eos_id:
                    self._mark_eos(st)
                if len(st.tokens) >= st.limit and st.inflight == 0:
                    self._retire(st, now)   # degenerate 1-token request
                return 1
            decoded = 0
            for st, n in rec[3]:
                if self._active.get(st.slot) is not st:
                    continue    # preempted since submit: recompute replays it
                st.inflight -= 1
                if st.eos_done:
                    # the device lane was frozen for this whole span: nothing
                    # to commit, and pos/submitted were restored at EOS time
                    if len(st.tokens) >= st.limit and st.inflight == 0:
                        self._retire(st, now)
                    continue
                toks = np.asarray(val[st.slot, :n])
                if self.eos_id is not None:
                    hit = np.nonzero(toks == self.eos_id)[0]
                    if hit.size:
                        toks = toks[:int(hit[0]) + 1]
                decoded += self._commit_tokens(st, toks, now, advance=False)
                if (self.eos_id is not None and st.tokens
                        and st.tokens[-1] == self.eos_id):
                    self._mark_eos(st)
                if len(st.tokens) >= st.limit and st.inflight == 0:
                    self._retire(st, now)
            self._c_tokens.inc(decoded)
            return decoded

    def _mark_eos(self, st: _SlotState) -> None:
        """The lane's newest committed token is the stop token.  Clamp the
        limit so the lane retires, and restore the authoritative position
        invariant ``pos == bucket + len(tokens) - 1``: any submit-time
        advance still riding later in-flight spans is undone here, since
        the device lane froze at EOS (fused path) or retires before its
        slot is reused (single-step path, whose over-runs only ever write
        positions past the commit horizon)."""
        st.eos_done = True
        st.limit = len(st.tokens)
        st.submitted = len(st.tokens)
        if self.paged:
            st.pos = st.bucket + len(st.tokens) - 1

    def _owned_by_inflight(self, c) -> bool:
        """True while an in-flight record will still wait on ``c`` at its
        commit (its EXECUTE, its read, or a deferred admission's ops)."""
        for rec in self._inflight:
            owned = (rec[1], rec[2]) if rec[0] == "batch" else (rec[2],
                                                               *rec[3])
            if any(o is c for o in owned):
                return True
        return False

    def _fail_pipeline(self, records) -> None:
        """Drop every in-flight record after a failed EXECUTE: later
        pipelined EXECUTEs ran against the pre-failure state, so their
        results belong to the *failed* span.  Batch records roll their
        submit-time advances back; deferred admissions un-admit — the
        request is requeued whole and replays deterministically."""
        self._inflight.clear()
        # reversed so appendleft restores the admissions' arrival order
        for rec in reversed(records):
            if rec[0] == "admit":
                st = rec[1]
                if self._active.get(st.slot) is not st:
                    continue
                self.pool.free(st.blocks)
                self._bt_clear_row(st.slot)
                self._active.pop(st.slot)
                heapq.heappush(self._free, st.slot)
                self.pending.appendleft(st.req)
                self.registry.record_event("engine_unadmit",
                                           rid=st.req.rid, slot=st.slot,
                                           engine=self.engine_id)
                if st.span is not None:
                    st.span.annotate(unadmitted=True).end()
                if st.req.trace is not None:
                    st.req._eng_queue_span = st.req.trace.span(
                        "engine.queue", engine=self.engine_id,
                        requeued=True)
            else:
                for st, n in rec[3]:
                    if self._active.get(st.slot) is st:
                        st.inflight -= 1
                        if not st.eos_done:
                            # an EOS'd lane's pos/submitted were already
                            # restored to the authoritative values
                            st.submitted -= n
                            st.pos -= n
        self._resync_lanes = True
        # a failed fused EXECUTE never applied the delta rows it carried:
        # the device block table may be behind the host mirror, so the
        # next iteration rewrites it whole (host-authoritative)
        self._bt_mark_full()

    def _drain_pipeline(self) -> int:
        """Commit every in-flight batch (compaction / explicit flush)."""
        decoded = 0
        while self._inflight:
            decoded += self._commit_fused()
        return decoded

    # -- one speculative iteration: draft k, verify k+1, commit/rollback -
    def _spec_iteration(self) -> int:
        k, ps = self.spec_k_now, self.page_size
        self._flush_block_table()
        # host-authoritative lane state (acceptance is decided here)
        self._write("toks", self._toks_host.copy(), span=self._it_root)
        self._write("pos", self._pos_host.copy(), span=self._it_root)
        self._exec(
            f"draft_lookahead_k{k}",
            ("draft_params", "toks", "pos", "draft_caches"),
            (f"draft_toks_k{k}", "draft_caches"), donate=True,
            span=self._it_root)
        # every page the verify can write is dirty — including pages whose
        # acceptance is later partial; evict must serialize them whole
        dirty = set()
        for st in self._active.values():
            for lp in range(st.pos // ps,
                            min((st.pos + k) // ps, self.max_blocks - 1) + 1):
                pid = int(self._bt_host[st.slot, lp])
                if pid >= 0:
                    dirty.add(pid)
        self._exec(
            f"verify_step_k{k}",
            ("params", "toks", f"draft_toks_k{k}", "pos", "block_table",
             "kv_pool"),
            (f"verify_toks_k{k}", "kv_pool"), donate=True,
            dirty_pages={"kv_pool": tuple(sorted(dirty))},
            span=self._it_root)
        # token delivery doubles as the iteration's sync point
        target = np.asarray(self._read(f"verify_toks_k{k}",
                                       span=self._it_root))
        drafts = np.asarray(self._read(f"draft_toks_k{k}",
                                       span=self._it_root))
        now = self._clock()
        decoded = 0
        self.spec_iterations += 1
        for st in list(self._active.values()):
            remaining = st.limit - len(st.tokens)
            g, d = target[st.slot], drafts[st.slot]
            m = 0
            while m < k and int(d[m]) == int(g[m]):
                m += 1
            ncommit = min(m + 1, remaining)
            offered = min(k, remaining - 1)
            self.spec_offered_drafts += offered
            self.spec_accepted_drafts += min(m, offered)
            self._adapt_offered += offered
            self._adapt_accepted += min(m, offered)
            self.spec_lane_iterations += 1
            self.spec_committed += ncommit
            self._commit_tokens(st, g[:ncommit], now)
            self._toks_host[st.slot, 0] = st.tokens[-1]
            self._pos_host[st.slot] = st.pos
            decoded += ncommit
            # rollback: free the orphaned lookahead tail — pages wholly
            # past the last committed entry (the kept tail page may still
            # hold rejected writes; causal masking hides them until the
            # lane overwrites them in order)
            keep = (st.pos + ps - 1) // ps
            if len(st.blocks) > keep:
                freed = self.pool.free_tail(st.blocks, keep)
                for lp in range(keep, len(st.blocks)):
                    self._bt_set_cell(st.slot, lp, -1)
                del st.blocks[keep:]
                self.registry.record_event(
                    "engine_spec_rollback", rid=st.req.rid, slot=st.slot,
                    freed=len(freed), engine=self.engine_id)
            if len(st.tokens) >= st.limit:
                self._retire(st, now)
        self._c_tokens.inc(decoded)
        if self._publish_gauges and self.spec_offered_drafts:
            self._g_spec.set(self.spec_accepted_drafts
                             / self.spec_offered_drafts)
        self._adapt_spec_k()
        return decoded

    def _adapt_spec_k(self) -> None:
        """Dynamic lookahead: every ``adapt_window`` offered drafts, read
        the window's acceptance (the delta the ``spec_accept_rate`` gauge
        moved by) and resize the live ``k`` — shrink one step below
        ``shrink_below`` so rejected verify work stops burning iterations,
        regrow one step after two consecutive windows at/above
        ``grow_above``.  Only throughput changes; committed tokens are
        bit-exact at every depth."""
        spec = self.spec
        if spec is None or not spec.dynamic_k:
            return
        if self._adapt_offered < spec.adapt_window:
            return
        rate = self._adapt_accepted / self._adapt_offered
        self._adapt_offered = self._adapt_accepted = 0
        prev = self.spec_k_now
        if rate < spec.shrink_below:
            self._grow_streak = 0
            self.spec_k_now = max(spec.k_min, self.spec_k_now - 1)
        elif rate >= spec.grow_above:
            self._grow_streak += 1
            if self._grow_streak >= 2:
                self._grow_streak = 0
                self.spec_k_now = min(self.spec_k, self.spec_k_now + 1)
        else:
            self._grow_streak = 0
        if self.spec_k_now != prev:
            if self._publish_gauges:
                self._g_spec_k.set(self.spec_k_now)
            self.registry.record_event(
                "engine_spec_k_adapt", engine=self.engine_id,
                k_from=prev, k_to=self.spec_k_now, window_rate=rate)

    def spec_stats(self) -> dict:
        """Speculation throughput accounting (zeros when spec is off)."""
        lane_iters = max(self.spec_lane_iterations, 1)
        offered = max(self.spec_offered_drafts, 1)
        return {
            "k": self.spec_k,
            "k_now": self.spec_k_now,
            "iterations": self.spec_iterations,
            "lane_iterations": self.spec_lane_iterations,
            "committed_tokens": self.spec_committed,
            "tokens_per_lane_iteration": self.spec_committed / lane_iters,
            "accept_rate": self.spec_accepted_drafts / offered,
        }

    def prefix_stats(self) -> dict:
        """Prefix-cache effectiveness (zeros when the cache is off)."""
        out = {"hits": self.prefix_hits,
               "partial_hits": self.prefix_partial_hits,
               "misses": self.prefix_misses,
               "prompt_tokens": self.prefix_prompt_tokens,
               "cached_tokens": self.prefix_cached_tokens,
               "hit_rate": (self.prefix_cached_tokens
                            / max(self.prefix_prompt_tokens, 1)),
               "cow_copies": self.cow_copies}
        if self.prefix is not None:
            out.update(self.prefix.stats())
        return out

    def prefix_match_len(self, prompt) -> int:
        """Router probe: how many of this prompt's (padded) tokens the
        engine's tree would serve from cache.  Read-only and lock-guarded,
        so any router thread may call it against any replica."""
        if self.prefix is None:
            return 0
        bucket = self._pick_bucket(
            np.asarray(prompt).reshape(-1).shape[0])
        padded = self._pad_prompt(prompt, bucket).reshape(-1)
        return self.prefix.match_len(bucket, padded)

    # -- one iteration ---------------------------------------------------
    def step(self) -> dict:
        """One engine iteration; returns counts for the caller's pacing.

        On an unexpected exception the flight recorder is dumped to a JSON
        file (``funky_flight_<engine>.json`` in the temp dir) before the
        error propagates — the event ring is the post-mortem."""
        if not self._setup_done:
            raise RuntimeError("engine.setup() has not run")
        try:
            with obs.span("engine.step"):
                return self._step_inner()
        except BaseException as e:  # noqa: BLE001 - dump, then re-raise
            try:
                path = os.path.join(
                    tempfile.gettempdir(),
                    f"funky_flight_{self.engine_id}.json")
                self.registry.flight_record_to_file(
                    path, engine=self.engine_id, error=repr(e),
                    iteration=self.iterations)
            except Exception:  # noqa: BLE001 - never mask the original
                pass
            raise

    def _step_inner(self) -> dict:
        t_step0 = time.perf_counter()
        it_tr = None
        if self.tracer is not None and (self._active or self.pending):
            it_tr = self.tracer.start_trace(
                "engine.step", trace_id=f"{self.engine_id}:it"
                f"{self.iterations}", engine=self.engine_id)
            self._it_root = it_tr.root
        preempts0 = self.preemptions
        compacts0 = self.auto_compactions
        decoded = 0
        if self.paged:
            if self._inflight and self._should_auto_compact():
                # compaction remaps physical pages; commit the pipelined
                # batches first (their EXECUTEs were submitted against the
                # pre-move ids)
                decoded += self._drain_pipeline()
            self._maybe_auto_compact()
        self._mid_step = True
        try:
            admitted = self._admit()
            self.peak_active = max(self.peak_active, len(self._active))
            if self._active and self.paged:
                with obs.span("engine.pages"):
                    self._append_pages()
            if self._active and self.spec is not None:
                decoded += self._spec_iteration()
            elif self.paged and (self.fuse_steps > 1
                                 or self.async_depth > 0):
                if self._active or self._inflight:
                    decoded += self._fused_iteration()
            elif self._active:
                if self.paged:
                    self._flush_block_table()
                    dirty = sorted({int(self._bt_host[
                        s.slot, s.pos // self.page_size])
                        for s in self._active.values()})
                    self._exec(
                        "decode_step",
                        ("params", "toks", "pos", "block_table", "kv_pool"),
                        ("toks", "pos", "kv_pool"), donate=True,
                        dirty_pages={"kv_pool": tuple(dirty)},
                        span=self._it_root)
                else:
                    self._exec(
                        "decode_step", ("params", "toks", "pos", "caches"),
                        ("toks", "pos", "caches"), donate=True,
                        span=self._it_root)
                # token delivery doubles as the iteration's sync point —
                # the d2h TRANSFER drains the queue, landing on a token
                # boundary
                with obs.span("engine.commit"):
                    toks = np.asarray(self._read("toks", span=self._it_root))
                    now = self._clock()
                    for st in list(self._active.values()):
                        decoded += self._commit_tokens(
                            st, toks[st.slot], now)
                        if (self.eos_id is not None and st.tokens
                                and st.tokens[-1] == self.eos_id):
                            self._mark_eos(st)
                        if len(st.tokens) >= st.limit:
                            self._retire(st, now)
                    self._c_tokens.inc(decoded)
        finally:
            self._mid_step = False
        self.iterations += 1
        self._c_iters.inc()
        # -- host/device attribution: wall minus the monitor-measured
        #    device phases is host overhead (batching, commit, paging)
        wall = time.perf_counter() - t_step0
        device_s = queue_wait_s = 0.0
        execs = 0
        carry: List = []
        for c in self._step_completions:
            if not c.done:
                # a pipelined EXECUTE (or a prefix-hit admit's lane write)
                # may still be in flight at this boundary: carry it to the
                # next step so a late failure — and its phase attribution —
                # surfaces exactly once instead of being dropped
                carry.append(c)
                continue
            # async EXECUTEs may only ever be awaited via a later read's
            # FIFO sync — surface their failures here instead of silently
            # committing stale tokens.  error_seen marks completions whose
            # failure already raised at a wait()/commit site.
            if c.error is not None:
                if c.error_seen:
                    continue
                if self._owned_by_inflight(c):
                    # its commit site rolls the pipeline back and raises
                    # it; raising here too would surface it twice and
                    # leave the failed span's advance in place
                    carry.append(c)
                    continue
                c.error_seen = True
                raise c.error
            ph = c.phases or {}
            device_s += ph.get("device_s", 0.0)
            queue_wait_s += ph.get("queue_wait_s", 0.0)
            if ph.get("kind") == "EXECUTE":
                execs += 1
        tokens = decoded + admitted       # each admit emits a first token
        if tokens:
            self._attr_host_s += max(0.0, wall - device_s)
            self._attr_device_s += device_s
            self._attr_queue_wait_s += queue_wait_s
            self._attr_tokens += tokens
            self._attr_execs += execs
            # queue-wait denominator: EXECUTE completions only — counting
            # writes/reads/syncs inflated the denominator and diluted the
            # queue_wait_us gauge
            self._attr_reqs += execs
            if self._publish_gauges:
                self._g_host_us.set(
                    self._attr_host_s / self._attr_tokens * 1e6)
                self._g_device_us.set(
                    self._attr_device_s / self._attr_tokens * 1e6)
                self._g_queue_wait_us.set(
                    self._attr_queue_wait_s
                    / max(self._attr_reqs, 1) * 1e6)
        self._step_completions = carry
        if it_tr is not None:
            it_tr.finish(admitted=admitted, decoded=decoded,
                         active=len(self._active),
                         preemptions=self.preemptions - preempts0,
                         auto_compactions=(self.auto_compactions
                                           - compacts0),
                         device_s=device_s)
            self._it_root = None
        if self._publish_gauges:
            self._g_queue.set(len(self.pending))
            self._g_util.set(len(self._active) / self.slots)
            if self.paged:
                self._g_kv.set(self.pool.occupancy())
                if self.prefix is not None:
                    # tree-only pages are one eviction away from free:
                    # advertising them keeps KV-aware routing from
                    # penalizing a warm cache as memory pressure
                    self._g_kv_free.set(self.pool.free_count()
                                        + self.prefix.reclaimable_pages())
                    if self.prefix_prompt_tokens:
                        self._g_prefix.set(self.prefix_cached_tokens
                                           / self.prefix_prompt_tokens)
                else:
                    self._g_kv_free.set(self.pool.free_count())
        return {"admitted": admitted, "decoded": decoded,
                "active": len(self._active), "pending": len(self.pending)}

    def host_device_split(self) -> dict:
        """Cumulative host-vs-device attribution for the serving loop —
        the baseline the host-out-of-the-loop decode tentpole is measured
        against.  All times come from the monitor's per-request phase
        dicts, so the split is available with tracing off."""
        toks = max(self._attr_tokens, 1)
        return {"tokens": self._attr_tokens,
                "execs": self._attr_execs,
                "host_us_per_token": self._attr_host_s / toks * 1e6,
                "device_us_per_token": self._attr_device_s / toks * 1e6,
                "queue_wait_us_mean": (self._attr_queue_wait_s
                                       / max(self._attr_reqs, 1) * 1e6),
                "host_s_total": self._attr_host_s,
                "device_s_total": self._attr_device_s}

    def drain_completions(self) -> List[CompletedRequest]:
        out = list(self._unreported)
        self._unreported.clear()
        return out

    def evacuate(self) -> List[ServeRequest]:
        """Hand back every un-finished request (kill / drain path) and
        reset the lanes.  Finished-but-unreported completions stay
        available via ``drain_completions`` — report those first so the
        caller's in-flight accounting stays exact."""
        reqs = ([st.req for st in self._active.values()]
                + list(self.pending))
        for st in self._active.values():
            if st.span is not None:
                st.span.annotate(evacuated=True).end()
        for req in reqs:
            qsp = getattr(req, "_eng_queue_span", None)
            if qsp is not None:
                qsp.annotate(evacuated=True).end()
                req._eng_queue_span = None
            if req.trace is not None:
                # keep a handle for the router to span-link the
                # post-requeue trace back to this one (recovery timeline)
                req._prev_trace = req.trace
                req.trace.finish(evacuated=True, engine=self.engine_id)
                req.trace = None        # re-traced on resubmission
        self._active.clear()
        self.pending.clear()
        # in-flight pipelined tokens die with the lanes: the requests are
        # requeued whole and recompute deterministically elsewhere
        self._inflight.clear()
        self._resync_lanes = False
        self._free = list(range(self.slots))
        heapq.heapify(self._free)
        if self.paged:
            self.pool = BlockPool(self.pool_pages, self.page_size,
                                  reserve_pages=self.pool.reserve_pages)
            # the device pool keeps the dead lanes' bytes: nothing is
            # first-touch clean for whoever reuses this engine
            self._virgin_pages = set()
            if self.prefix is not None:
                # the old pool (and every tree reference into it) dies
                # with the evacuation; the index restarts cold
                self.prefix = PrefixCache(
                    self.pool, self.page_size,
                    max_nodes=self._prefix_max_nodes)
            self._bt_host[:] = -1
            self._bt_mark_full()
            self._first_token.clear()
            if self.spec is not None:
                self._toks_host[:] = 0
                self._pos_host[:] = 0
            if self._publish_gauges:
                # a killed replica must not pin the service-level pressure
                # signal at its last (hot) value — the aggregator keeps
                # gauges of dead engines forever.  kv_free advertises 0
                # (not the fresh pool's capacity): a dead engine must never
                # outrank live replicas in KV-aware routing, and the spec
                # gauge becomes a NaN tombstone the service-mean fold skips
                self._g_kv.set(0.0)
                self._g_kv_free.set(0.0)
                if self.spec is not None:
                    self._g_spec.set(float("nan"))
                    self._g_spec_k.set(float("nan"))   # same tombstone rule
                if self.prefix is not None:
                    self._g_prefix.set(float("nan"))   # same tombstone rule
        return reqs

    # ------------------------------------------------------------------
    # Disaggregated serving: live KV handoff between role replicas
    # ------------------------------------------------------------------
    def attach_transfer(self, queue) -> None:
        """Join a ``TransferQueue``: the prefill side offers freshly
        prefilled lanes, the decode side drains them.  Needs a declared
        role — mixed engines never hand lanes off."""
        if self.role == "mixed":
            raise ValueError("attach_transfer needs role='prefill' or "
                             "'decode'")
        self.transfer = queue
        queue.register(self)

    def exportable_lanes(self) -> List[_SlotState]:
        """Active lanes a prefill replica could hand off right now: the
        first token is committed, nothing is in flight against the lane's
        pages, and the request still has tokens to generate.  A lane that
        missed the transfer window simply keeps decoding here (TTFT-aware
        fallback) and is offered again at the next step boundary."""
        out = []
        for slot in sorted(self._active):
            st = self._active[slot]
            if (st.tokens and st.inflight == 0
                    and st.submitted == len(st.tokens)
                    and len(st.tokens) < st.limit
                    and st.deferred_insert is None):
                out.append(st)
        return out

    def export_lane(self, st: _SlotState):
        """Serialize an in-flight lane for handoff to a decode replica:
        gather its pages into the staging buffer (one EXECUTE), read them
        back d2h, then release the lane — pages return to this pool
        (prefix donation first, exactly like retire) and the slot frees.
        The request is NOT completed here; the importer continues it
        mid-decode, bit-exact because the gather reassembles the logical
        cache independent of physical page ids."""
        from repro.serve.disagg import KVHandoff
        rid = st.req.rid
        ids = np.full((self.max_blocks,), self.pool_pages, np.int32)
        ids[:len(st.blocks)] = st.blocks
        xsp = (st.req.trace.span("engine.handoff_out",
                                 engine=self.engine_id, slot=st.slot,
                                 pages=len(st.blocks))
               if st.req.trace is not None else None)
        self._exec("xfer_extract", ("kv_pool",), ("xfer_pages",),
                   const_args=(ids,), span=xsp)
        staged = self._read("xfer_pages", span=xsp)
        pages = jax.tree.map(np.asarray, staged)
        if xsp is not None:
            xsp.end()
        handoff = KVHandoff(
            req=st.req, rid=rid, tokens=st.tokens, tbts=st.tbts,
            pos=st.pos, bucket=st.bucket, limit=st.limit,
            n_pages=len(st.blocks), pages=pages, admit_t=st.admit_t,
            first_token_t=self._first_token.get(rid, st.first_token_t),
            last_token_t=st.last_token_t,
            src_engine=self.engine_id, export_t=self._clock())
        if self.prefix is not None and st.blocks:
            # donate committed pages to the tree before dropping the
            # lane's references — same rule as retire, so the handed-off
            # request's own OOM recompute (or a sibling prompt) still hits
            ps = self.page_size
            flat = self._pad_prompt(st.req.prompt, st.bucket).reshape(-1)
            full = np.concatenate([flat, np.asarray(st.tokens, np.int32)])
            n_complete = min(st.pos // ps, len(st.blocks))
            if n_complete:
                nxt = (int(full[n_complete * ps])
                       if n_complete * ps < len(full) else None)
                self.prefix.insert(st.bucket, full[:n_complete * ps],
                                   st.blocks[:n_complete], nxt)
        self.pool.free(st.blocks)
        self._bt_clear_row(st.slot)
        self._active.pop(st.slot, None)
        heapq.heappush(self._free, st.slot)
        self._first_token.pop(rid, None)
        if st.span is not None:
            st.span.annotate(handed_off=True, tokens=len(st.tokens)).end()
        self.registry.record_event("engine_handoff_out", rid=rid,
                                   slot=st.slot, engine=self.engine_id,
                                   pages=handoff.n_pages)
        return handoff

    def import_lane(self, handoff) -> bool:
        """Install a handed-off lane: allocate pages, upload + scatter the
        staged pages (whole-page overwrite — no scrub needed), install the
        lane scalars, and resume decode mid-request.  Returns False
        without side effects when there is no slot or page headroom."""
        if not self._free:
            return False
        n = handoff.n_pages
        if n > self.max_blocks or not self.pool.can_admit(n):
            return False
        page_ids = self.pool.alloc(n)
        if page_ids is None:
            return False
        page_ids = [int(p) for p in page_ids]
        self._virgin_pages.difference_update(page_ids)
        slot = heapq.heappop(self._free)
        self._bt_set_row(slot, page_ids)
        try:
            imp = (handoff.req.trace.span("engine.handoff_in",
                                          engine=self.engine_id, slot=slot,
                                          pages=n)
                   if handoff.req.trace is not None else None)
            W = self.max_blocks

            def fit(leaf):
                # replicas may be provisioned with different max_blocks;
                # pad/trim the staging width (padding never installs —
                # its ids point out of range)
                leaf = np.asarray(leaf)
                if leaf.shape[0] == W:
                    return leaf
                if leaf.shape[0] > W:
                    return leaf[:W]
                pad = np.zeros((W - leaf.shape[0],) + leaf.shape[1:],
                               leaf.dtype)
                return np.concatenate([leaf, pad], 0)

            staged = jax.tree.map(fit, handoff.pages)
            ids = np.full((W,), self.pool_pages, np.int32)
            ids[:n] = page_ids
            self._write("xfer_pages", staged, span=imp)
            self._exec("xfer_install", ("kv_pool", "xfer_pages"),
                       ("kv_pool",), const_args=(ids,), donate=True,
                       dirty_pages={"kv_pool": tuple(page_ids)}, span=imp)
            self._exec("lane_set", ("toks", "pos"), ("toks", "pos"),
                       const_args=(np.int32(handoff.tokens[-1]),
                                   np.int32(handoff.pos), np.int32(slot)),
                       donate=True, span=imp)
            if imp is not None:
                imp.end()
        except BaseException:
            self.pool.free(page_ids)
            self._bt_clear_row(slot)
            heapq.heappush(self._free, slot)
            raise
        st = _SlotState(req=handoff.req, slot=slot, tokens=handoff.tokens,
                        tbts=handoff.tbts, admit_t=handoff.admit_t,
                        first_token_t=handoff.first_token_t,
                        last_token_t=handoff.last_token_t,
                        limit=handoff.limit, bucket=handoff.bucket,
                        pos=handoff.pos, blocks=page_ids,
                        submitted=len(handoff.tokens),
                        span=(handoff.req.trace.span(
                            "engine.decode", engine=self.engine_id,
                            slot=slot, imported=True)
                            if handoff.req.trace is not None else None))
        handoff.req.committed = st.tokens   # re-alias: crash replay
        # seed the TTFT ledger so neither this engine's commits nor an
        # OOM-preempt recompute here observe TTFT a second time
        self._first_token[handoff.req.rid] = handoff.first_token_t
        self._active[slot] = st
        self.registry.record_event("engine_handoff_in",
                                   rid=handoff.req.rid, slot=slot,
                                   engine=self.engine_id, pages=n)
        return True

    def run_until_drained(self, max_iterations: int = 100000) -> None:
        while not self.idle:
            self.step()
            if self.iterations >= max_iterations:
                raise RuntimeError("engine did not drain "
                                   f"in {max_iterations} iterations")

    # ------------------------------------------------------------------
    # Router integration (live plane): pull admissible work, push results
    # ------------------------------------------------------------------
    def pump(self, router, admit: bool = True) -> bool:
        """One iteration against a ``RequestRouter``; True if work moved.
        ``admit=False`` (a draining replica) pulls nothing new and only
        finishes what it already holds.  The pop is engine-tagged so a
        KV-aware router can steer work toward the replica with the most
        free pages."""
        if self.prefix is not None and admit:
            # advertise this replica's prefix-cache warmth so the router
            # can steer repeat prefixes here (idempotent re-registration)
            router.register_prefix_probe(self.engine_id,
                                         self.prefix_match_len)
        reg_role = getattr(router, "register_engine_role", None)
        if reg_role is not None and admit:
            # declare this replica's role so the router sends fresh
            # prompts to prefill replicas only (idempotent)
            reg_role(self.engine_id, self.role, self.buckets)
        if self.transfer is not None and self.role == "decode":
            # drain admitted handoffs into free slots before stepping
            self.transfer.pump_dest(self)
        if admit:
            for req in router.pop(len(self._free), engine_id=self.engine_id):
                self.submit(req)
        moved = bool(self._active or self.pending)
        if moved:
            self.step()
        if self.transfer is not None and self.role == "prefill":
            # offer freshly prefilled lanes at the step boundary; lanes
            # the queue rejects keep decoding here (aggregated fallback)
            self.transfer.pump_source(self)
        for rec in self.drain_completions():
            router.complete(rec)
        return moved
