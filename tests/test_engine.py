"""Continuous-batching engine over paged KV memory: slot admission/backfill
ordering, paged-vs-reserved bit-exactness, mid-batch preemption (evict ->
resume resumes every in-flight sequence bit-exactly, serializing only dirty
pages), pool-exhaustion OOM preemption, block-table reuse without
stale-page leakage, compaction, prompt buckets, draining, per-request
latency metrics, and router integration."""

import numpy as np
import pytest

from repro.core import FunkyCL, Monitor, SliceAllocator
from repro.scaling.autoscaler import M_KV_FREE_PAGES
from repro.scaling.metrics import MetricsRegistry
from repro.scaling.serving import RequestRouter
from repro.serve.engine import (M_TBT, M_TTFT, ContinuousBatchingEngine,
                                ServeRequest)

ARCH = "yi-9b-smoke"
PROMPT_LEN = 8
PAGE = 4


def make_engine(slots=2, max_new=8, registry=None, **kw):
    reg = registry if registry is not None else MetricsRegistry()
    mon = Monitor("eng-test", SliceAllocator("n0", 1), telemetry=reg)
    eng = ContinuousBatchingEngine(ARCH, FunkyCL(mon), slots=slots,
                                   prompt_len=PROMPT_LEN,
                                   max_new_tokens=max_new, registry=reg,
                                   page_size=PAGE, **kw)
    eng.setup()
    return mon, eng, reg


def make_requests(spec, seed=0):
    """spec: list of max_new_tokens; prompts drawn deterministically."""
    rng = np.random.Generator(np.random.Philox(seed))
    return [ServeRequest(rid=f"r{i}", prompt=rng.integers(0, 100, PROMPT_LEN),
                         max_new_tokens=n)
            for i, n in enumerate(spec)]


@pytest.fixture(scope="module")
def engine_run():
    """One shared engine run: 5 ragged requests over 2 slots."""
    mon, eng, reg = make_engine(slots=2, max_new=8)
    spec = [2, 6, 2, 3, 2]
    for r in make_requests(spec):
        eng.submit(r)
    eng.run_until_drained()
    mon.vfpga_exit()
    return eng, reg, spec


def test_all_requests_complete(engine_run):
    eng, _, spec = engine_run
    assert sorted(eng.completed) == [f"r{i}" for i in range(len(spec))]
    for i, n in enumerate(spec):
        assert len(eng.completed[f"r{i}"].tokens) == n


def test_admission_fifo_and_backfill(engine_run):
    """Admissions happen in arrival order; a freed slot is backfilled by the
    next pending request while the rest of the batch keeps decoding."""
    eng, reg, _ = engine_run
    events = [(e[1], e[2]) for e in reg.flight_record()["events"]
              if e[1] in ("engine_admit", "engine_retire")]
    admits = [f for k, f in events if k == "engine_admit"]
    assert [a["rid"] for a in admits] == ["r0", "r1", "r2", "r3", "r4"]
    # r0 (2 tokens) retires before r1 (6 tokens); r2 backfills r0's slot
    order = [(k, f["rid"]) for k, f in events]
    assert order.index(("engine_retire", "r0")) \
        < order.index(("engine_admit", "r2"))
    slot_of = {a["rid"]: a["slot"] for a in admits}
    retired_first = next(f for k, f in events if k == "engine_retire")
    assert slot_of["r2"] == retired_first["slot"]
    # r1 was never interrupted: it retired after every backfill admission
    assert order.index(("engine_retire", "r1")) \
        > order.index(("engine_admit", "r3"))


def test_latency_metrics_schema(engine_run):
    """Per-request TTFT/TBT/e2e land in the shared registry schema."""
    eng, reg, spec = engine_run
    snap = reg.snapshot()
    n, total = len(spec), sum(spec)
    assert snap["histograms"][f"{M_TTFT}{{service=svc}}"]["count"] == n
    assert (snap["histograms"][f"{M_TBT}{{service=svc}}"]["count"]
            == total - n)
    assert (snap["histograms"]["request_latency_seconds{service=svc}"]
            ["count"] == n)
    assert snap["counters"]["completions_total{service=svc}"] == n
    assert snap["counters"]["engine_tokens_total{service=svc}"] == total
    for rec in eng.completed.values():
        assert rec.ttft_s >= 0 and rec.e2e_s >= rec.ttft_s
        assert len(rec.tbts) == len(rec.tokens) - 1


def test_decode_and_admit_are_donated(engine_run):
    """The paged KV update path compiles with buffer donation (in-place
    pool update, no per-token copy of the pool)."""
    eng, _, _ = engine_run
    mon_keys = [(pid, d) for (pid, _, d, _dev) in
                eng.cl._monitor.programs._compiled.keys()]
    assert ("decode_step", (1, 2, 4)) in mon_keys          # toks, pos, pool
    assert (f"prefill_admit_{PROMPT_LEN}", (1, 2, 3)) in mon_keys
    assert ("scrub", (0,)) in mon_keys


SPEC = [3, 6, 4, 5]


@pytest.fixture(scope="module")
def dense_ref():
    """Worst-case-reservation (non-paged) reference tokens for SPEC."""
    mon, eng, _ = make_engine(slots=2, max_new=8, paged=False)
    for r in make_requests(SPEC, seed=3):
        eng.submit(r)
    eng.run_until_drained()
    ref = {rid: rec.tokens for rid, rec in eng.completed.items()}
    mon.vfpga_exit()
    return ref


def test_paged_evict_resume_bit_exact_vs_dense(dense_ref):
    """Mid-batch evict -> resume of the paged engine: every in-flight
    ragged sequence continues bit-exactly vs the dense baseline, and the
    second evict serializes only the pages dirtied since the first."""
    mon, eng, _ = make_engine(slots=2, max_new=8)
    for r in make_requests(SPEC, seed=3):
        eng.submit(r)
    for _ in range(2):
        eng.step()
    assert eng.active_count > 0            # genuinely mid-batch
    stats = mon.evict()
    assert stats["n_dirty"] > 0
    # first evict has no prior host copy of the pool: full save
    assert stats["paged_saved_pages"] == stats["paged_total_pages"] > 0
    mon.resume()
    eng.step()
    assert eng.active_count > 0
    stats2 = mon.evict()
    # page-granular dirtiness: one iteration touches at most one page per
    # active lane (plus appends), nowhere near the whole pool
    assert 0 < stats2["paged_saved_pages"] < stats2["paged_total_pages"]
    mon.resume()
    eng.run_until_drained()
    got = {rid: rec.tokens for rid, rec in eng.completed.items()}
    mon.vfpga_exit()
    assert got == dense_ref


def test_oom_preemption_compaction_and_resume(dense_ref):
    """A pool too small for every lane's worst case forces OOM preemption;
    preempted requests recompute deterministically, compaction mid-flight
    is invisible, and a mid-run evict/resume still lands bit-exactly."""
    mon, eng, _ = make_engine(slots=2, max_new=8, pool_pages=6,
                              reserve_pages=1)
    for r in make_requests(SPEC, seed=3):
        eng.submit(r)
    for _ in range(3):
        eng.step()
    eng.compact()
    eng.pool.check_invariants()
    stats = mon.evict()
    assert stats["n_dirty"] > 0
    mon.resume()
    eng.run_until_drained()
    got = {rid: rec.tokens for rid, rec in eng.completed.items()}
    assert eng.preemptions > 0             # the pool genuinely exhausted
    eng.pool.check_invariants()
    mon.vfpga_exit()
    assert got == dense_ref


def test_block_table_reuse_no_stale_page_leakage(dense_ref):
    """Pages freed by one wave of requests are reused by the next; the
    scrub-on-alloc rule means the new owners never attend to the previous
    wave's tokens (their results match a fresh dense engine)."""
    mon, eng, _ = make_engine(slots=2, max_new=8)
    for r in make_requests([8, 7, 8, 6], seed=11):      # wave A: fill pool
        eng.submit(r)
    eng.run_until_drained()
    assert eng.pool.used_count() == 0      # everything freed at retirement
    wave_b = make_requests(SPEC, seed=3)
    for r in wave_b:
        r.rid = "b-" + r.rid
        eng.submit(r)
    eng.run_until_drained()
    got = {rid[2:]: rec.tokens for rid, rec in eng.completed.items()
           if rid.startswith("b-")}
    mon.vfpga_exit()
    assert got == dense_ref


def test_memory_based_admission_and_watermark():
    """Admission is gated on free pages, not lane count: with a pool that
    holds one prompt (plus reserve), only one of two free lanes admits."""
    mon, eng, _ = make_engine(slots=2, max_new=8, pool_pages=4,
                              reserve_pages=1)
    for r in make_requests([4, 4], seed=5):
        eng.submit(r)
    out = eng.step()
    # prompt needs 2 pages; after one admission 2 free - 2 < 1 reserve
    assert out["admitted"] == 1 and out["pending"] == 1
    eng.run_until_drained()
    assert len(eng.completed) == 2
    mon.vfpga_exit()


def test_prompt_buckets_route_admissions():
    """Ragged prompts route to the smallest fitting prefill bucket instead
    of all padding to one prompt_len."""
    mon, eng, _ = make_engine(slots=2, max_new=6, prompt_buckets=(4, 8))
    assert eng._pick_bucket(3) == 4
    assert eng._pick_bucket(4) == 4
    assert eng._pick_bucket(5) == 8
    assert eng._pick_bucket(99) == 8       # over-long prompts truncate
    mon_keys = [pid for (pid, _, _, _dev) in
                eng.cl._monitor.programs._compiled.keys()]
    assert {"prefill_admit_4", "prefill_admit_8"} <= set(mon_keys)
    rng = np.random.Generator(np.random.Philox(9))
    eng.submit(ServeRequest(rid="short", prompt=rng.integers(0, 100, 3),
                            max_new_tokens=5))
    eng.submit(ServeRequest(rid="long", prompt=rng.integers(0, 100, 8),
                            max_new_tokens=4))
    # an over-cap ask is clamped to the engine's provisioned cap instead
    # of walking past the block table (cache is sized for max_new_tokens)
    eng.submit(ServeRequest(rid="over", prompt=rng.integers(0, 100, 8),
                            max_new_tokens=99))
    eng.run_until_drained()
    assert len(eng.completed["short"].tokens) == 5
    assert len(eng.completed["long"].tokens) == 4
    assert len(eng.completed["over"].tokens) == 6
    # the short request was admitted at bucket width 4: its lane freed
    # ceil((4 + 5) / PAGE) pages at retirement, not the bucket-8 worst case
    admits = [e for e in eng.registry.flight_record()["events"]
              if e[1] == "engine_admit"]
    assert {a[2]["rid"] for a in admits} == {"short", "long", "over"}
    mon.vfpga_exit()


def test_drain_before_kill_live_replica():
    """Scale-in prelude through the runtime: ``drain`` flips the replica
    into its draining state, the driver finishes the held sequences and
    exits at the request boundary — nothing is requeued for recomputation."""
    import time

    from repro.core import TaskImage, TaskStatus, make_cluster
    from repro.scaling.serving import reset_router

    img = TaskImage(name="drain-svc", kind="engine-serve", arch=ARCH,
                    prompt_len=PROMPT_LEN, global_batch=2,
                    total_steps=10 ** 9, max_new_tokens=6, page_size=PAGE)
    cluster = make_cluster(num_nodes=1, slices_per_node=1,
                           images={"drain-svc": img})
    router = reset_router("drain-svc")
    try:
        rt = cluster.nodes["node0"].runtime
        rt.create("d1", img)
        rt.start("d1")
        for r in make_requests([4, 4, 4], seed=13):
            router.submit(r)
        deadline = time.time() + 300
        while not router.in_flight and time.time() < deadline:
            time.sleep(0.01)               # wait until the engine has work
        stats = rt.drain("d1", timeout_s=300)
        assert stats["drained"]
        assert rt.wait("d1", timeout=60) == TaskStatus.DONE
        assert router.in_flight == 0       # held work finished, not requeued
        assert len(router.completed) + router.pending_count() == 3
        assert len(router.completed) >= 2
        rt.kill("d1")                      # the follow-up remove is a no-op
        assert router.pending_count() + len(router.completed) == 3
    finally:
        cluster.stop()


def test_drain_stops_admissions_and_finishes_lanes():
    """pump(admit=False): a draining replica pulls nothing new from the
    router and retires what it already holds (drain-before-kill)."""
    mon, eng, reg = make_engine(slots=2, max_new=4)
    router = RequestRouter("svc", registry=reg)
    for r in make_requests([3, 3, 3, 3], seed=7):
        router.submit(r)
    eng.pump(router)                       # pulls 2 into the lanes
    assert eng.active_count == 2 and router.pending_count() == 2
    while eng.pump(router, admit=False):
        pass
    assert eng.idle and len(eng.completed) == 2
    assert router.pending_count() == 2     # untouched by the drained engine
    assert router.in_flight == 0           # completions reported back
    mon.vfpga_exit()


def test_router_pump_and_requeue():
    """pump() pulls only what free slots allow; requeue puts killed work
    back at the head with in-flight accounting intact."""
    router = RequestRouter("svc")
    for r in make_requests([2, 2, 2, 2], seed=5):
        router.submit(r)
    assert router.pending_count() == 4
    popped = router.pop(2)
    assert [r.rid for r in popped] == ["r0", "r1"]
    assert router.in_flight == 2 and router.outstanding() == 4
    router.requeue(popped)
    assert router.in_flight == 0
    assert [r.rid for r in router.pop(4)] == ["r0", "r1", "r2", "r3"]

    mon, eng, reg = make_engine(slots=2, max_new=4)
    router2 = RequestRouter("svc", registry=reg)
    for r in make_requests([2, 3, 2], seed=6):
        router2.submit(r)
    while router2.outstanding() > 0:
        if not eng.pump(router2):
            break
    assert len(router2.completed) == 3
    assert router2.in_flight == 0
    mon.vfpga_exit()


# ---------------------------------------------------------------------------
# KV-aware routing (per-engine kv_free_pages gauges, synthetic)
# ---------------------------------------------------------------------------
def _routing_setup(free_a, free_b, n_req=3):
    reg = MetricsRegistry()
    router = RequestRouter("svc", registry=reg)
    reg.gauge(M_KV_FREE_PAGES, service="svc", engine="eA").set(free_a)
    reg.gauge(M_KV_FREE_PAGES, service="svc", engine="eB").set(free_b)
    for r in make_requests([2] * n_req, seed=21):
        router.submit(r)
    return reg, router


def test_kv_aware_routing_prefers_max_free_pages():
    """The replica with the most free KV pages is served first; a
    non-preferred replica is held back for exactly one pop."""
    _, router = _routing_setup(free_a=10, free_b=2)
    assert router.pop(2, engine_id="eB") == []          # deferred once
    assert [r.rid for r in router.pop(2, engine_id="eA")] == ["r0", "r1"]
    # liveness: the deferred replica is served on its next pop even while
    # still non-preferred — preference is a head start, not starvation
    assert [r.rid for r in router.pop(2, engine_id="eB")] == ["r2"]


def test_kv_aware_routing_ties_round_robin():
    """Equal free pages: every replica is preferred, so pops alternate in
    pump order (round-robin) with no deferrals."""
    _, router = _routing_setup(free_a=5, free_b=5)
    assert [r.rid for r in router.pop(1, engine_id="eB")] == ["r0"]
    assert [r.rid for r in router.pop(1, engine_id="eA")] == ["r1"]
    assert [r.rid for r in router.pop(1, engine_id="eB")] == ["r2"]


def test_killed_replica_never_captures_routing_preference():
    """evacuate() (the kill path) advertises 0 free pages — a dead
    replica's immortal gauge must not outrank loaded live replicas."""
    mon, eng, reg = make_engine(slots=2, max_new=8)
    for r in make_requests([3, 3], seed=33):
        eng.submit(r)
    eng.step()                             # publishes engine0 gauges
    assert reg.gauge(M_KV_FREE_PAGES, service="svc",
                     engine="engine0").value > 0
    eng.evacuate()
    assert reg.gauge(M_KV_FREE_PAGES, service="svc",
                     engine="engine0").value == 0.0
    # a live replica holding pages (low but nonzero free count) is still
    # preferred over the dead engine: its first pop succeeds
    reg.gauge(M_KV_FREE_PAGES, service="svc", engine="live").set(1.0)
    router = RequestRouter("svc", registry=reg)
    for r in make_requests([2], seed=34):
        router.submit(r)
    assert [r.rid for r in router.pop(1, engine_id="live")] == ["r0"]
    mon.vfpga_exit()


def test_prefix_aware_routing_prefers_warmest_replica():
    """With warmth probes registered, the replica whose prefix tree
    matches the head request wins the pop; a cold replica is held back
    exactly once (the single-deferral liveness rule still applies)."""
    _, router = _routing_setup(free_a=10, free_b=10)
    router.register_prefix_probe("eA", lambda p: 0)
    router.register_prefix_probe("eB", lambda p: 8)
    assert router.pop(1, engine_id="eA") == []          # cold: deferred
    assert [r.rid for r in router.pop(1, engine_id="eB")] == ["r0"]
    # liveness: the deferred cold replica is served on its next pop even
    # though eB is still warmer — preference is a head start, never
    # starvation
    assert [r.rid for r in router.pop(1, engine_id="eA")] == ["r1"]


def test_prefix_preference_capped_by_free_page_headroom():
    """Hit-skew starvation fix: a warm replica whose free pages fell
    below half the best replica's loses its preference, and routing
    falls back to the free-page load balance."""
    _, router = _routing_setup(free_a=10, free_b=4)
    router.register_prefix_probe("eA", lambda p: 0)
    router.register_prefix_probe("eB", lambda p: 8)     # warm but starving
    # eB is below the headroom bar (10/2): kv preference rules, eA wins
    assert [r.rid for r in router.pop(1, engine_id="eA")] == ["r0"]
    assert router.pop(1, engine_id="eB") == []          # deferred once
    assert [r.rid for r in router.pop(1, engine_id="eB")] == ["r1"]
    # with headroom restored (>= half the best), warmth wins again even
    # though eB still has fewer free pages than eA
    router.registry.gauge(M_KV_FREE_PAGES, service="svc",
                          engine="eB").set(6)
    assert router.pop(1, engine_id="eA") == []
    assert [r.rid for r in router.pop(1, engine_id="eB")] == ["r2"]


def test_failed_engine_probe_dropped():
    """A crashed replica's warmth probe must not keep attracting traffic
    (mirrors the NaN gauge tombstone rule)."""
    _, router = _routing_setup(free_a=10, free_b=10)
    router.register_prefix_probe("eB", lambda p: 8)
    router.fail_engine("eB")
    assert [r.rid for r in router.pop(1, engine_id="eA")] == ["r0"]


def test_engine_pump_registers_prefix_probe():
    """A prefix-cache engine advertises its warmth probe through pump();
    repeat prompts then route back to the replica that cached them."""
    mon, eng, reg = make_engine(slots=2, max_new=4, prefix_cache=True)
    router = RequestRouter("svc", registry=reg)
    rng = np.random.Generator(np.random.Philox(41))
    prompt = rng.integers(0, 100, PROMPT_LEN)
    router.submit(ServeRequest(rid="w0", prompt=prompt, max_new_tokens=2))
    while router.outstanding() or not eng.idle:
        if not eng.pump(router):
            break
    assert eng.engine_id in router._prefix_probes
    # the served prompt's pages are in the tree: the probe reports warmth
    assert router._prefix_probes[eng.engine_id](prompt) > 0
    mon.vfpga_exit()


def test_kv_aware_routing_untagged_and_unknown_pops_unaffected():
    """Pops without an engine tag (or from engines with no gauge yet) are
    never deferred; kv_aware=False disables the preference entirely."""
    _, router = _routing_setup(free_a=10, free_b=2)
    assert [r.rid for r in router.pop(1)] == ["r0"]
    assert [r.rid for r in router.pop(1, engine_id="newcomer")] == ["r1"]
    reg, router2 = _routing_setup(free_a=10, free_b=2)
    router2.kv_aware = False
    assert [r.rid for r in router2.pop(1, engine_id="eB")] == ["r0"]


# ---------------------------------------------------------------------------
# Auto-compaction (threshold-triggered, iteration-boundary only)
# ---------------------------------------------------------------------------
def test_auto_compaction_fires_at_threshold():
    """Fragmentation (1 - used/span) at/above the threshold triggers
    compact() at the top of the next iteration; below it, never."""
    mon, eng, _ = make_engine(slots=2, max_new=8, pool_pages=12,
                              auto_compact_frag=0.5,
                              auto_compact_min_pages=4)
    a = eng.pool.alloc(4)
    eng.pool.alloc(4)
    eng.pool.free(a)                   # used {4..7}: span 8, frag 0.5
    eng._maybe_auto_compact()
    assert eng.auto_compactions == 1
    assert eng.pool.used_span() == eng.pool.used_count() == 4
    eng.pool.check_invariants()
    events = [e for e in eng.registry.flight_record()["events"]
              if e[1] == "engine_auto_compact"]
    assert len(events) == 1
    eng._maybe_auto_compact()          # frag now 0: no refire
    assert eng.auto_compactions == 1
    mon.vfpga_exit()


def test_auto_compaction_respects_min_gap_and_threshold():
    mon, eng, _ = make_engine(slots=2, max_new=8, pool_pages=12,
                              auto_compact_frag=0.5,
                              auto_compact_min_pages=4)
    a = eng.pool.alloc(2)
    eng.pool.alloc(4)
    eng.pool.free(a)                   # gap 2 < min_pages 4
    eng._maybe_auto_compact()
    assert eng.auto_compactions == 0
    mon.vfpga_exit()
    mon, eng, _ = make_engine(slots=2, max_new=8, pool_pages=12,
                              auto_compact_frag=0.9,
                              auto_compact_min_pages=1)
    a = eng.pool.alloc(4)
    eng.pool.alloc(4)
    eng.pool.free(a)                   # frag 0.5 < threshold 0.9
    eng._maybe_auto_compact()
    assert eng.auto_compactions == 0
    mon.vfpga_exit()


def test_auto_compaction_live_churn_is_invisible(dense_ref):
    """Under retirement churn with an aggressive threshold the engine
    auto-compacts mid-workload and the token streams are untouched."""
    mon, eng, _ = make_engine(slots=2, max_new=8, auto_compact_frag=0.2,
                              auto_compact_min_pages=1)
    for r in make_requests([8, 7, 8, 6], seed=11):      # churn wave
        eng.submit(r)
    eng.run_until_drained()
    wave_b = make_requests(SPEC, seed=3)
    for r in wave_b:
        r.rid = "b-" + r.rid
        eng.submit(r)
    eng.run_until_drained()
    got = {rid[2:]: rec.tokens for rid, rec in eng.completed.items()
           if rid.startswith("b-")}
    assert eng.auto_compactions > 0
    eng.pool.check_invariants()
    mon.vfpga_exit()
    assert got == dense_ref


def test_compact_refuses_while_pages_in_flight():
    """compact() is only legal between iterations — with an iteration's
    EXECUTEs holding physical page ids it must refuse."""
    mon, eng, _ = make_engine(slots=2, max_new=4)
    eng._mid_step = True
    with pytest.raises(RuntimeError, match="in flight"):
        eng.compact()
    eng._mid_step = False
    eng.compact()                      # boundary: fine
    mon.vfpga_exit()


# ---------------------------------------------------------------------------
# Host-out-of-the-loop decode: fused multi-step EXECUTEs + async pipelining
# ---------------------------------------------------------------------------
def _fused_factory(**kw):
    def make():
        mon, eng, _ = make_engine(slots=2, max_new=8, **kw)
        return mon, eng
    return make


def _ragged_requests(spec=(6, 8, 4, 7, 5, 8), seed=2):
    def make():
        return make_requests(list(spec), seed=seed)
    return make


def test_fused_decode_bit_exact_vs_single_step():
    """k decode steps fused into one EXECUTE commit the same tokens the
    one-step-per-EXECUTE engine commits, and the block table is updated
    through on-device delta EXECUTEs, not full host rewrites."""
    from repro.serve.equivalence import check_equivalence

    eng, base = check_equivalence(
        _fused_factory(fuse_steps=4, async_depth=1), _fused_factory(),
        _ragged_requests(), context="fused vs single-step")
    assert eng.bt_delta_execs > 0
    # steady-state block-table maintenance is delta-driven; the only full
    # rewrites allowed are resync paths (evict/resume, delta overflow)
    assert eng.bt_full_writes == 0
    # k-step fusion must actually shrink EXECUTE count per token
    assert eng.host_device_split()["execs"] < \
        base.host_device_split()["execs"]


def test_fused_decode_evict_resume_mid_span():
    """Monitor-level evict/resume between iterations — with fused spans in
    flight the resumed device state must continue bit-exactly."""
    from repro.serve.equivalence import check_equivalence, evict_resume_every

    check_equivalence(
        _fused_factory(fuse_steps=4, async_depth=1), _fused_factory(),
        _ragged_requests(), step_hook=evict_resume_every(3),
        context="fused + evict/resume")


def test_fused_decode_oom_preemption_mid_span():
    """A pool too small for every lane's k-step lookahead span: lanes are
    preempted mid-span, recomputed, and the stream stays bit-exact."""
    from repro.serve.equivalence import check_equivalence

    eng, _ = check_equivalence(
        _fused_factory(fuse_steps=4, async_depth=1, pool_pages=6),
        _fused_factory(), _ragged_requests(),
        context="fused + OOM preemption")
    assert eng.preemptions > 0, "pool was not tight enough to preempt"
    eng.pool.check_invariants()


def test_async_pipeline_without_fusion_bit_exact():
    """async_depth alone (k=1): iteration N+1's EXECUTE is submitted
    before N's tokens are read back, and commits are unchanged."""
    from repro.serve.equivalence import check_equivalence

    eng, _ = check_equivalence(
        _fused_factory(fuse_steps=1, async_depth=2), _fused_factory(),
        _ragged_requests(), context="async pipeline")
    assert eng.bt_delta_execs > 0


def test_fused_decode_invalid_configs_rejected():
    reg = MetricsRegistry()
    mon = Monitor("fused-bad", SliceAllocator("n0", 1), telemetry=reg)
    cl = FunkyCL(mon)
    mk = lambda **kw: ContinuousBatchingEngine(
        ARCH, cl, slots=2, prompt_len=PROMPT_LEN, max_new_tokens=4,
        registry=reg, page_size=PAGE, **kw)
    with pytest.raises(ValueError):
        mk(fuse_steps=0)
    with pytest.raises(ValueError):
        mk(async_depth=-1)
    with pytest.raises(ValueError):
        mk(paged=False, fuse_steps=4)
    from repro.serve.engine import SpecConfig
    with pytest.raises(ValueError):
        mk(fuse_steps=4, spec=SpecConfig(k=2))
    mon.vfpga_exit()


def test_fused_decode_with_compaction_drains_pipeline():
    """compact() remaps physical pages, so it must first drain in-flight
    fused EXECUTEs that hold the old ids; compacting every iteration of a
    pipelined run stays bit-exact."""
    from repro.serve.equivalence import check_equivalence

    def hook(eng, mon, i):
        eng.compact()

    eng, _ = check_equivalence(
        _fused_factory(fuse_steps=4, async_depth=1), _fused_factory(),
        _ragged_requests(), step_hook=hook,
        context="fused + compaction")
    eng.pool.check_invariants()
