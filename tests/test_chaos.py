"""Deterministic fault injection + end-to-end recovery (chaos soak).

Unit layer: FaultPlan determinism/bounds, retry backoff accounting, and
monitor EXECUTE retry bit-exactness.

Soak layer: five seeded fault schedules through the live cluster — node
crash mid-decode, transient EXECUTE faults, a torn checkpoint write, a
corrupted snapshot, and a failing restore — each asserting *request
conservation* (every request completes exactly once, zero duplicates,
zero replay mismatches) and *bit-exact* tokens against the fault-free
baseline run.
"""

import time

import numpy as np
import pytest

from repro.chaos import (FaultPlan, FaultSpec, InjectedCrash, InjectedFault,
                         RetryPolicy, retry_call)
from repro.core import TaskImage, make_cluster
from repro.scaling.metrics import MetricsRegistry
from repro.scaling.serving import reset_router, wait_for_service
from repro.serve.engine import ServeRequest

ARCH = "yi-9b-smoke"
PROMPT_LEN = 8
PAGE = 4
MAX_NEW = 6
SLOTS = 2
SPEC = [4, 6, 3, 5, 4, 6]              # max_new_tokens per request


def make_requests(seed=17):
    rng = np.random.Generator(np.random.Philox(seed))
    return [ServeRequest(rid=f"r{i}",
                         prompt=rng.integers(0, 100, PROMPT_LEN),
                         max_new_tokens=n)
            for i, n in enumerate(SPEC)]


# ---------------------------------------------------------------------------
# FaultPlan / retry unit layer
# ---------------------------------------------------------------------------
def _drive(plan, n=40):
    return [plan.check("monitor.execute", key=f"t:{i}") is not None
            for i in range(n)]


def test_fault_plan_deterministic():
    """Same seed + specs over the same event sequence -> identical fires."""
    mk = lambda: FaultPlan([FaultSpec(site="monitor.execute", prob=0.3,
                                      max_fires=5)], seed=42)
    a, b = _drive(mk()), _drive(mk())
    assert a == b and sum(a) == 5          # max_fires bounds total fires
    c = _drive(FaultPlan([FaultSpec(site="monitor.execute", prob=0.3,
                                    max_fires=5)], seed=43))
    assert a != c                          # and the seed actually matters


def test_fault_plan_at_every_match():
    plan = FaultPlan([
        FaultSpec(site="agent.deploy", at=2),
        FaultSpec(site="monitor.execute", every=3, max_fires=2,
                  match="svc-a"),
    ])
    fires = [plan.check("agent.deploy", key=f"n{i}") is not None
             for i in range(4)]
    assert fires == [False, True, False, False]
    # match filters the event count too: svc-b events don't advance svc-a
    assert plan.check("monitor.execute", key="svc-b:p") is None
    hits = [plan.check("monitor.execute", key="svc-a:p") is not None
            for i in range(9)]
    assert hits == [False, False, True] * 2 + [False, False, False]
    assert [f[0] for f in plan.fired] == ["agent.deploy",
                                          "monitor.execute",
                                          "monitor.execute"]


def test_fault_plan_records_registry_events():
    reg = MetricsRegistry()
    plan = FaultPlan([FaultSpec(site="ckpt.save", at=1, kind="torn")],
                     registry=reg)
    with pytest.raises(InjectedCrash):
        plan.raise_if("ckpt.save", key="/ck/p:buf")
    kinds = [e[1] for e in reg.flight_record()["events"]]
    assert "fault_injected" in kinds


def test_retry_call_backoff_and_deadline():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise InjectedFault("boom")
        return "ok"

    pol = RetryPolicy(max_attempts=4, base_backoff_s=0.1, max_backoff_s=1.0)
    assert retry_call(flaky, pol, sleep=sleeps.append) == "ok"
    assert sleeps == [0.1, 0.2]            # exponential
    # exhaustion re-raises the transient; non-retryable passes through
    with pytest.raises(InjectedFault):
        retry_call(lambda: (_ for _ in ()).throw(InjectedFault("x")),
                   RetryPolicy(max_attempts=2, base_backoff_s=0),
                   sleep=lambda s: None)
    with pytest.raises(ValueError):
        retry_call(lambda: (_ for _ in ()).throw(ValueError("v")), pol,
                   sleep=sleeps.append)


# ---------------------------------------------------------------------------
# Monitor EXECUTE retry: injected transient faults cost a backoff, not
# correctness — the transcript stays bit-exact vs the fault-free run
# ---------------------------------------------------------------------------
def _engine_factory(chaos=None, registry=None, retries=3, **eng_kw):
    from repro.core import FunkyCL, Monitor, SliceAllocator
    from repro.serve.engine import ContinuousBatchingEngine

    reg = registry if registry is not None else MetricsRegistry()
    mon = Monitor("eng-chaos", SliceAllocator("n0", 1), telemetry=reg,
                  chaos=chaos,
                  retry=RetryPolicy(max_attempts=retries,
                                    base_backoff_s=0.001,
                                    max_backoff_s=0.01))
    eng = ContinuousBatchingEngine(ARCH, FunkyCL(mon), slots=SLOTS,
                                   prompt_len=PROMPT_LEN,
                                   max_new_tokens=MAX_NEW, registry=reg,
                                   page_size=PAGE, **eng_kw)
    eng.setup()
    return mon, eng


@pytest.fixture(scope="module")
def baseline_tokens():
    """Fault-free per-request tokens — the bit-exactness reference for
    every soak schedule (greedy decode is deterministic, so batching
    composition and replica identity must not change them)."""
    mon, eng = _engine_factory()
    for r in make_requests():
        eng.submit(r)
    eng.run_until_drained()
    ref = {rid: list(rec.tokens) for rid, rec in eng.completed.items()}
    mon.vfpga_exit()
    assert sorted(ref) == [f"r{i}" for i in range(len(SPEC))]
    return ref


def test_monitor_execute_retry_bit_exact(baseline_tokens):
    reg = MetricsRegistry()
    plan = FaultPlan([FaultSpec(site="monitor.execute", kind="error",
                                every=11, max_fires=2)],
                     seed=1, registry=reg)
    mon, eng = _engine_factory(chaos=plan, registry=reg)
    for r in make_requests():
        eng.submit(r)
    eng.run_until_drained()
    got = {rid: list(rec.tokens) for rid, rec in eng.completed.items()}
    mon.vfpga_exit()
    assert got == baseline_tokens
    assert len(plan.fired) == 2
    snap = reg.snapshot()
    assert snap["counters"]["monitor_execute_retries_total"] == 2
    kinds = [e[1] for e in reg.flight_record()["events"]]
    assert kinds.count("execute_retry") == 2


def test_monitor_execute_retry_exhaustion_fails_request():
    """A persistent fault exhausts the bounded retries and surfaces as a
    structured failure, not a hang."""
    reg = MetricsRegistry()
    plan = FaultPlan([FaultSpec(site="monitor.execute", kind="error",
                                every=1, max_fires=10,
                                match="decode_step")], registry=reg)
    mon, eng = _engine_factory(chaos=plan, registry=reg)
    eng.submit(make_requests()[0])
    with pytest.raises(InjectedFault):
        eng.run_until_drained()
    mon.vfpga_exit()
    snap = reg.snapshot()
    assert snap["counters"]["monitor_execute_failed_total"] >= 1
    kinds = [e[1] for e in reg.flight_record()["events"]]
    assert "execute_failed" in kinds


def _surfaced_pipelined_failures(settle_before_sweep: bool):
    """Serve the standard requests with the third fused EXECUTE failing;
    returns (times it surfaced, completed streams, the fault plan)."""
    reg = MetricsRegistry()
    plan = FaultPlan([FaultSpec(site="monitor.execute", kind="error",
                                at=3, max_fires=1, match="decode_multi")],
                     seed=7, registry=reg)
    # retries=1: InjectedFault is transient, so the monitor's default
    # retry loop would absorb it before it ever reached the engine
    mon, eng = _engine_factory(chaos=plan, registry=reg, retries=1,
                               fuse_steps=4, async_depth=1)
    if settle_before_sweep:
        fused = eng._fused_iteration

        def fused_then_settle():
            # every op this iteration queued completes before the sweep
            out = fused()
            for c in eng._step_completions:
                c._event.wait(60)
            return out

        eng._fused_iteration = fused_then_settle
    for r in make_requests():
        eng.submit(r)
    raises = 0
    guard = 0
    while not eng.idle:
        try:
            eng.step()
        except InjectedFault:
            raises += 1
        guard += 1
        assert guard < 10000, "engine did not drain"
    got = {rid: list(rec.tokens) for rid, rec in eng.completed.items()}
    mon.vfpga_exit()
    return raises, got, plan


def test_pipelined_execute_error_surfaces_exactly_once(baseline_tokens):
    """Regression for the step()-boundary drop: a pipelined fused EXECUTE
    that errors *after* the step that submitted it must surface exactly
    once (the old loop only raised for already-done completions, then
    cleared the list — late failures were silently dropped and their
    stale tokens committed).  After the raise the engine rolls the span
    back, resubmits deterministically, and finishes bit-exactly."""
    raises, got, plan = _surfaced_pipelined_failures(False)
    assert len(plan.fired) == 1
    assert raises == 1, f"EXECUTE failure surfaced {raises} times, want 1"
    assert got == baseline_tokens


def test_pipelined_execute_error_done_before_boundary_surfaces_once(
        baseline_tokens):
    """The failed EXECUTE may finish before the step that submitted it
    reaches its boundary sweep.  The sweep must leave it to the commit site
    that owns it, which rolls the span back and raises it once."""
    raises, got, plan = _surfaced_pipelined_failures(True)
    assert len(plan.fired) == 1
    assert raises == 1, f"EXECUTE failure surfaced {raises} times, want 1"
    assert got == baseline_tokens


def test_delayed_pipelined_execute_carried_to_next_boundary(baseline_tokens):
    """A fused EXECUTE that is merely *slow* is not done at the boundary
    of the step that submitted it: it must be carried forward, folded into
    attribution exactly once, and never mistaken for a failure."""
    plan = FaultPlan([FaultSpec(site="monitor.execute", kind="delay",
                                delay_s=0.05, at=2, max_fires=2,
                                match="decode")], seed=8)
    mon, eng = _engine_factory(chaos=plan, fuse_steps=4, async_depth=1)
    for r in make_requests():
        eng.submit(r)
    eng.run_until_drained()
    got = {rid: list(rec.tokens) for rid, rec in eng.completed.items()}
    split = eng.host_device_split()
    mon.vfpga_exit()
    assert len(plan.fired) >= 1
    assert got == baseline_tokens
    # attribution folded each EXECUTE exactly once: the queue-wait gauge
    # denominator equals the EXECUTE tally (satellite: it used to count
    # every read/write/sync completion too)
    assert eng._attr_reqs == eng._attr_execs == split["execs"]


# ---------------------------------------------------------------------------
# Chaos soak: seeded schedules over the live cluster
# ---------------------------------------------------------------------------
def _soak(plan, inject, *, num_nodes=2, seed=17):
    """Deploy one engine-serve replica, feed it SPEC requests, run the
    schedule's mid-flight ``inject(ctx)`` hook, and wait for every request
    to terminate.  Returns (router, orch, registry, plan)."""
    reg = MetricsRegistry()
    if plan is not None and plan.registry is None:
        plan.registry = reg
    img = TaskImage(name="chaos-svc", kind="engine-serve", arch=ARCH,
                    prompt_len=PROMPT_LEN, global_batch=SLOTS,
                    total_steps=10 ** 9, max_new_tokens=MAX_NEW,
                    page_size=PAGE)
    cluster = make_cluster(num_nodes=num_nodes, slices_per_node=1,
                           images={"chaos-svc": img}, metrics=reg,
                           chaos=plan)
    router = reset_router("chaos-svc")
    orch = cluster.orchestrator
    orch.start(tick_interval=0.01)
    try:
        cid = orch.submit("chaos-svc")
        node = wait_for_service(cluster, orch, cid, timeout_s=300)
        for r in make_requests(seed):
            router.submit(r)
        ctx = {"cluster": cluster, "orch": orch, "router": router,
               "cid": cid, "node": node, "plan": plan}
        inject(ctx)
        deadline = time.time() + 300
        while router.outstanding() > 0 and time.time() < deadline:
            time.sleep(0.02)
        missing = sorted({r.rid for r in make_requests(seed)}
                         - set(router.completed))
        assert router.outstanding() == 0, f"requests lost: {missing}"
        return router, orch, reg, ctx
    finally:
        router.close()
        cluster.stop()


def _assert_conserved(router, baseline_tokens):
    """Zero lost, zero duplicated, bit-exact vs the fault-free run."""
    assert sorted(router.completed) == sorted(baseline_tokens)
    assert router.duplicates == 0
    assert router.replay_mismatches == 0
    got = {rid: list(rec.tokens) for rid, rec in router.completed.items()}
    assert got == baseline_tokens


def _wait_completions(router, n, timeout=300):
    deadline = time.time() + timeout
    while len(router.completed) < n and time.time() < deadline:
        time.sleep(0.01)
    assert len(router.completed) >= n


def test_soak_node_crash_mid_decode(baseline_tokens):
    """Schedule 1: checkpoint, then hard-crash the serving node while
    requests are in flight.  Leased requests replay through the router;
    the restored replica finishes everything bit-exactly."""
    def inject(ctx):
        _wait_completions(ctx["router"], 2)
        ctx["orch"].checkpoint(ctx["cid"])
        ctx["orch"].handle_node_failure(ctx["node"])

    router, orch, reg, _ = _soak(None, inject)
    _assert_conserved(router, baseline_tokens)
    events = [e[1] for e in orch.events]
    assert "restored" in events or "resubmitted" in events
    assert "router_replay" in events or len(router.replayed) == 0


def test_soak_transient_execute_faults(baseline_tokens):
    """Schedule 2: seeded transient EXECUTE faults throughout the run —
    absorbed by the monitor's retry loop, invisible to clients."""
    plan = FaultPlan([FaultSpec(site="monitor.execute", kind="error",
                                every=7, max_fires=3, match="task-")],
                     seed=2)
    router, _, reg, _ = _soak(plan, lambda ctx: None)
    _assert_conserved(router, baseline_tokens)
    assert len(plan.fired) == 3
    assert reg.snapshot()["counters"][
        "monitor_execute_retries_total"] == 3


def test_soak_torn_checkpoint_then_crash(baseline_tokens):
    """Schedule 3: first checkpoint lands, second is torn mid-write (the
    simulated crash leaves only hidden debris).  Node failure then
    restores from the intact first checkpoint."""
    plan = FaultPlan(seed=3)

    def inject(ctx):
        _wait_completions(ctx["router"], 1)
        p1 = ctx["orch"].checkpoint(ctx["cid"])
        plan.add(FaultSpec(site="ckpt.save", kind="torn", at=1))
        with pytest.raises(InjectedCrash):
            ctx["orch"].checkpoint(ctx["cid"])
        # the torn attempt must never be discoverable as a snapshot
        assert ctx["orch"]._latest_snapshot_any(ctx["cid"]) == p1
        ctx["orch"].handle_node_failure(ctx["node"])

    router, orch, _, _ = _soak(plan, inject)
    _assert_conserved(router, baseline_tokens)
    assert "restored" in [e[1] for e in orch.events]


def test_soak_corrupt_snapshot_falls_back(baseline_tokens):
    """Schedule 4: the newest checkpoint is bit-flipped on disk after
    publish.  Restore detects the digest mismatch and falls back along
    the incremental chain to the previous good snapshot, logging
    ``restore_fallback``."""
    plan = FaultPlan(seed=4)

    def inject(ctx):
        _wait_completions(ctx["router"], 1)
        p1 = ctx["orch"].checkpoint(ctx["cid"])
        # let the guest advance so the second checkpoint lands at a new
        # step — a same-step save would *overwrite* p1, not chain to it
        step1 = int(p1.rsplit("-step", 1)[1])
        gs = (ctx["cluster"].agent(ctx["node"]).engine.runtime
              .tasks[ctx["cid"]].guest_state)
        deadline = time.time() + 60
        while gs.step <= step1 and time.time() < deadline:
            time.sleep(0.002)
        plan.add(FaultSpec(site="ckpt.corrupt", kind="corrupt", at=1))
        p2 = ctx["orch"].checkpoint(ctx["cid"])  # published, then corrupted
        assert p2 != p1
        ctx["orch"].handle_node_failure(ctx["node"])

    router, orch, reg, _ = _soak(plan, inject)
    _assert_conserved(router, baseline_tokens)
    events = [e[1] for e in orch.events]
    assert "restored" in events
    kinds = [e[1] for e in reg.flight_record()["events"]]
    assert "restore_fallback" in kinds


def test_soak_restore_failure_retried(baseline_tokens):
    """Schedule 5: the restore itself fails transiently on first attempt;
    the orchestrator's bounded retry-with-backoff lands it on attempt 2."""
    plan = FaultPlan([FaultSpec(site="ckpt.restore", kind="error", at=1)],
                     seed=5)

    def inject(ctx):
        _wait_completions(ctx["router"], 1)
        ctx["orch"].checkpoint(ctx["cid"])
        ctx["orch"].handle_node_failure(ctx["node"])

    router, orch, _, _ = _soak(plan, inject)
    _assert_conserved(router, baseline_tokens)
    events = [e[1] for e in orch.events]
    assert "restored" in events
    retries = [e for e in orch.events if e[1] == "action_retry"
               and e[2].get("action") == "restore"]
    assert len(retries) == 1


def test_replay_links_recovery_traces(baseline_tokens):
    """A replayed request's new trace carries a span link back to its
    crashed predecessor (same trace_id = rid), and the Chrome export puts
    the link on the root event for trace_dump to show."""
    from repro.obs import Tracer
    from repro.obs.export import validate_chrome_trace
    from repro.scaling.serving import RequestRouter

    tracer = Tracer()
    router = RequestRouter("link-svc", tracer=tracer)
    reqs = make_requests()[:2]
    for r in reqs:
        router.submit(r)
    popped = router.pop(2, engine_id="eng-a")
    for r in popped:
        r.committed = [1, 2]               # as if two tokens decoded
    n = router.fail_engine("eng-a")
    assert n == 2 and router.in_flight == 0
    assert router.pending_count() == 2     # replayed to the head
    assert router.replayed == {"r0": [1, 2], "r1": [1, 2]}
    for r in popped:
        assert r.trace is not None
        assert r.trace.links[0]["trace_id"] == r.rid
        assert r.trace.links[0]["relation"] == "recovers"
        r.trace.finish()
    doc = tracer.chrome_trace()
    validate_chrome_trace(doc)
    roots = [ev for ev in doc["traceEvents"]
             if ev.get("ph") == "X" and ev["args"].get("parent_id") == 0
             and "links" in ev["args"]]
    assert len(roots) == 2


def test_duplicate_completion_guard():
    """A dead replica's late completion of a replayed request must not
    double-count, and a replay that diverges from the committed prefix is
    flagged."""
    from repro.scaling.serving import RequestRouter
    from repro.serve.engine import CompletedRequest

    router = RequestRouter("dup-svc")
    req = make_requests()[0]
    router.submit(req)
    router.pop(1, engine_id="eng-a")
    req.committed = [5, 6]
    router.fail_engine("eng-a")
    router.pop(1, engine_id="eng-b")
    rec = CompletedRequest(rid=req.rid, tokens=[5, 6, 7], arrival_t=0,
                           admit_t=0, first_token_t=0, finish_t=1)
    router.complete(rec)
    router.complete(rec)                   # late duplicate from the dead one
    assert router.duplicates == 1
    assert len(router.completed) == 1
    assert router.replay_mismatches == 0

    router2 = RequestRouter("dup-svc2")
    req2 = make_requests()[1]
    router2.submit(req2)
    router2.pop(1, engine_id="eng-a")
    req2.committed = [9, 9]
    router2.fail_engine("eng-a")
    router2.pop(1, engine_id="eng-b")
    bad = CompletedRequest(rid=req2.rid, tokens=[1, 2, 3], arrival_t=0,
                           admit_t=0, first_token_t=0, finish_t=1)
    router2.complete(bad)
    assert router2.replay_mismatches == 1
