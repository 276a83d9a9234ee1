"""Span tracing: virtual-clock determinism, bounded retention (ring +
keep-slowest + probabilistic sampling), Chrome-trace export round-trip,
and end-to-end instrumentation — a router->engine->monitor request forms
one connected span tree, monitor phase attribution sums to no more than
the handler wall time, and the engine's host/device split is publishable.
Scoped spans land in the profiler's trace, nested as the code nests them."""

import json
import os
import tempfile

import numpy as np
import pytest

from repro.core import FunkyCL, Monitor, SliceAllocator
from repro.core.simulator import ServingSimulator
from repro import obs
from repro.obs import (Tracer, chrome_trace_events, export_chrome_trace,
                       validate_chrome_trace)
from repro.scaling import burst_rate, open_loop
from repro.scaling.metrics import MetricsRegistry
from repro.scaling.serving import RequestRouter
from repro.serve.engine import (M_DEVICE_US, M_HOST_US, M_QUEUE_WAIT_US,
                                ContinuousBatchingEngine, ServeRequest)

ARCH = "yi-9b-smoke"
PROMPT_LEN = 8
PAGE = 4


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


# ---------------------------------------------------------------------------
# Tracer core under a virtual clock
# ---------------------------------------------------------------------------
def test_span_tree_virtual_clock_deterministic():
    clk = FakeClock()
    tracer = Tracer(clock=clk)
    tr = tracer.start_trace("request", trace_id="r0", service="svc")
    assert tr.root.start_t == 0.0 and tr.root.parent_id == 0

    clk.now = 1.0
    queue = tr.span("router.queue")
    clk.now = 3.0
    queue.end()
    admit = tr.span("engine.admit", engine="eng0")
    clk.now = 3.5
    ex = admit.child("monitor.execute", program="decode")
    clk.now = 4.0
    ex.end()
    admit.end()
    clk.now = 6.0
    tr.finish(tokens=4)

    # exact virtual timestamps, not wall-clock noise
    assert queue.start_t == 1.0 and queue.end_t == 3.0
    assert queue.duration == 2.0
    assert ex.start_t == 3.5 and ex.duration == 0.5
    assert tr.duration == 6.0 and tr.finished

    # tree shape: root <- {queue, admit}, admit <- execute
    spans = tr.spans()
    assert spans[0] is tr.root
    by_id = {s.span_id: s for s in spans}
    assert by_id[queue.parent_id] is tr.root
    assert by_id[admit.parent_id] is tr.root
    assert by_id[ex.parent_id] is admit
    # a second identical run produces the identical tree
    clk2 = FakeClock()
    t2 = Tracer(clock=clk2).start_trace("request", trace_id="r0")
    s2 = t2.span("router.queue")
    assert (s2.span_id, s2.parent_id) == (queue.span_id, queue.parent_id)


def test_parent_defaults_to_root_and_context_manager():
    clk = FakeClock()
    tr = Tracer(clock=clk).start_trace("t")
    with tr.span("a") as sp:
        clk.now = 2.0
    assert sp.end_t == 2.0
    assert sp.end(t=99.0).end_t == 2.0          # end() is idempotent
    assert sp.parent_id == tr.root.span_id


def test_scoped_span_records_obs_child_only_with_parent():
    clk = FakeClock()
    tr = Tracer(clock=clk).start_trace("t")
    with obs.span("engine.admit", parent=tr.root, slot=3) as sp:
        clk.now = 1.5
    assert sp.name == "engine.admit" and sp.labels == {"slot": 3}
    assert sp.parent_id == tr.root.span_id
    assert (sp.start_t, sp.end_t) == (0.0, 1.5)
    with obs.span("engine.step", slot=3) as none:
        pass
    assert none is None
    assert [s.name for s in tr.spans()] == ["t", "engine.admit"]
    with pytest.raises(KeyError):
        with obs.span("engine.commit", parent=tr.root) as failed:
            raise KeyError("x")
    assert failed.end_t is not None             # closed on the way out


def test_trace_span_ring_never_evicts_root():
    clk = FakeClock()
    tracer = Tracer(clock=clk, max_spans_per_trace=4)
    tr = tracer.start_trace("hot", trace_id="h")
    for i in range(10):
        tr.span(f"s{i}").end()
    spans = tr.spans()
    assert spans[0] is tr.root                  # root survives eviction
    assert len(spans) == 1 + 4
    assert [s.name for s in spans[1:]] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped_spans == 6


def test_ring_capacity_and_keep_slowest():
    clk = FakeClock()
    tracer = Tracer(clock=clk, capacity=4, sample_rate=1.0, keep_slowest=2)
    durs = [1.0, 9.0, 2.0, 7.0, 3.0, 0.5, 0.25, 0.125]
    for i, d in enumerate(durs):
        clk.now = 10.0 * i
        tr = tracer.start_trace("t", trace_id=f"t{i}")
        clk.now = 10.0 * i + d
        tr.finish()
    kept = tracer.traces()
    ids = {t.trace_id for t in kept}
    # ring holds the 4 most recent; the slowest two (t1, t3) are retained
    # by the keep-slowest heap even though the ring evicted them
    assert {"t4", "t5", "t6", "t7"} <= ids
    assert {"t1", "t3"} <= ids
    assert "t0" not in ids and "t2" not in ids


def test_probabilistic_sampling_bounds_and_determinism():
    def run(seed):
        tracer = Tracer(clock=FakeClock(), capacity=1000, sample_rate=0.25,
                        keep_slowest=0, seed=seed)
        for i in range(400):
            tracer.start_trace("t", trace_id=f"t{i}").finish()
        return [t.trace_id for t in tracer.traces()]

    a, b = run(7), run(7)
    assert a == b                                # seeded => deterministic
    assert 40 <= len(a) <= 160                   # ~100 expected of 400
    # sample_rate=0 keeps nothing through the ring...
    t0 = Tracer(clock=FakeClock(), sample_rate=0.0, keep_slowest=0)
    for i in range(10):
        t0.start_trace("t").finish()
    assert t0.traces() == [] and t0.finished == 10
    # ...but keep-slowest still catches outliers
    clk = FakeClock()
    t1 = Tracer(clock=clk, sample_rate=0.0, keep_slowest=1)
    tr = t1.start_trace("slow")
    clk.now = 5.0
    tr.finish()
    assert [t.trace_id for t in t1.traces()] == [tr.trace_id]


def test_live_traces_visible_until_finished():
    tracer = Tracer(clock=FakeClock())
    tr = tracer.start_trace("inflight", trace_id="x")
    assert tracer.find("x") is tr
    assert tracer.traces(include_live=False) == []
    tr.finish()
    assert tracer.find("x") is tr


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------
def test_chrome_export_round_trip(tmp_path):
    clk = FakeClock()
    tracer = Tracer(clock=clk)
    tr = tracer.start_trace("request", trace_id="r9", service="svc")
    clk.now = 0.25
    sp = tr.span("engine.admit", engine="e0")
    clk.now = 0.75
    sp.end()
    unfinished = tr.span("engine.decode")
    clk.now = 1.0
    tr.finish(tokens=3)

    path = tmp_path / "trace.json"
    export_chrome_trace(tracer, str(path))
    doc = json.loads(path.read_text())
    stats = validate_chrome_trace(doc)
    assert stats == {"traces": 1, "spans": 3}
    assert doc["displayTimeUnit"] == "ms"

    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"request", "engine.admit", "engine.decode"}
    adm = xs["engine.admit"]
    assert adm["ts"] == pytest.approx(0.25e6)
    assert adm["dur"] == pytest.approx(0.5e6)
    assert adm["args"]["engine"] == "e0"
    assert adm["args"]["parent_id"] == xs["request"]["args"]["span_id"]
    assert adm["pid"] == xs["request"]["pid"]          # same process row
    assert adm["tid"] != xs["request"]["tid"]          # own name-prefix row
    assert unfinished.end_t is None             # intentionally left open
    assert xs["engine.decode"]["args"]["unfinished"] is True
    assert xs["engine.decode"]["dur"] == pytest.approx(0.25e6)


def test_validate_rejects_orphans_and_bad_ph():
    doc = chrome_trace_events([])
    doc["traceEvents"].append({"name": "x", "ph": "B", "pid": 1, "tid": 1})
    with pytest.raises(ValueError, match="unexpected ph"):
        validate_chrome_trace(doc)
    orphan = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 1,
         "args": {"span_id": 2, "parent_id": 1, "trace_id": "t"}}]}
    with pytest.raises(ValueError, match="orphaned|root"):
        validate_chrome_trace(orphan)


# ---------------------------------------------------------------------------
# Simulator (virtual clock) publishes into the same abstraction
# ---------------------------------------------------------------------------
def test_simulator_traces_deterministic_virtual_time():
    reqs = open_loop(burst_rate(3.0, 2.0, 3.0, 3.0), 10.0, seed=5,
                     mean_service_s=0.2)

    def run():
        sim = ServingSimulator(list(reqs), initial_replicas=2, trace=True)
        sim.run()
        return sim.tracer

    tr1, tr2 = run(), run()
    done1 = [t for t in tr1.traces() if t.finished]
    assert done1, "simulator produced no finished request traces"
    t = done1[0]
    names = [s.name for s in t.spans()]
    assert "router.queue" in names and "sim.service" in names
    assert "latency_s" in t.root.labels
    # virtual clock => two runs give bit-identical span timings
    d1 = [x.to_dict() for x in tr1.traces() if x.finished]
    d2 = [x.to_dict() for x in tr2.traces() if x.finished]
    assert d1 == d2
    validate_chrome_trace(tr1.chrome_trace())


# ---------------------------------------------------------------------------
# Live plane: router -> engine -> monitor, one connected tree per request
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    tracer = Tracer(capacity=512, sample_rate=1.0)
    reg = MetricsRegistry()
    mon = Monitor("obs-test", SliceAllocator("n0", 1), telemetry=reg,
                  tracer=tracer)
    eng = ContinuousBatchingEngine(ARCH, FunkyCL(mon), slots=2,
                                   prompt_len=PROMPT_LEN, max_new_tokens=8,
                                   registry=reg, page_size=PAGE)
    eng.setup()
    router = RequestRouter("svc", registry=reg, kv_aware=False,
                           tracer=tracer)
    rng = np.random.Generator(np.random.Philox(0))
    for i, n in enumerate([2, 5, 3]):
        router.submit(ServeRequest(
            rid=f"r{i}", prompt=rng.integers(0, 100, PROMPT_LEN),
            max_new_tokens=n))
    while router.outstanding() or not eng.idle:
        eng.pump(router)
    mon.vfpga_exit()
    path = tmp_path_factory.mktemp("obs") / "live.json"
    export_chrome_trace(tracer, str(path))
    return tracer, eng, reg, json.loads(path.read_text())


def test_request_trace_is_one_connected_tree(traced_run):
    tracer, eng, _, _ = traced_run
    assert sorted(eng.completed) == ["r0", "r1", "r2"]
    for rid in ("r0", "r1", "r2"):
        tr = tracer.find(rid)
        assert tr is not None and tr.finished
        spans = tr.spans()
        ids = {s.span_id for s in spans}
        for s in spans:
            assert s.parent_id == 0 or s.parent_id in ids, \
                f"{rid}: span {s.name} orphaned"
        names = {s.name for s in spans}
        # router -> engine -> monitor chain present in ONE trace
        assert {"router.queue", "engine.queue", "engine.admit",
                "engine.decode", "monitor.execute",
                "execute.device"} <= names
        # every span closed, nested within the root window
        for s in spans:
            assert s.end_t is not None
            assert s.end_t >= s.start_t
            assert s.end_t <= tr.root.end_t + 1e-9
        assert tr.root.labels["tokens"] == \
            len(eng.completed[rid].tokens)


def test_exported_live_trace_validates(traced_run):
    _, _, _, doc = traced_run
    stats = validate_chrome_trace(doc)
    assert stats["traces"] >= 3                 # 3 requests + step traces
    execs = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "execute.device"]
    assert execs and any(e["dur"] > 0 for e in execs)


def test_iteration_traces_cover_decode_steps(traced_run):
    tracer, eng, _, _ = traced_run
    its = [t for t in tracer.traces()
           if t.name == "engine.step" and t.finished]
    assert its, "no per-iteration engine.step traces"
    assert all(t.trace_id.startswith(eng.engine_id) for t in its)
    decoded = sum(t.root.labels.get("decoded", 0) for t in its)
    admitted = sum(t.root.labels.get("admitted", 0) for t in its)
    total = sum(len(rec.tokens) for rec in eng.completed.values())
    assert decoded + admitted == total


def test_phase_attribution_bounded_by_wall_time(traced_run):
    tracer, _, _, _ = traced_run
    for tr in tracer.traces():
        for mon_span in tr.find_spans("monitor.execute"):
            kids = [s for s in tr.spans()
                    if s.parent_id == mon_span.span_id]
            assert kids, "monitor.execute has no phase children"
            for k in kids:
                assert k.duration >= 0.0
            assert sum(k.duration for k in kids) \
                <= mon_span.duration + 1e-6


def test_host_device_split_published(traced_run):
    _, eng, reg, _ = traced_run
    split = eng.host_device_split()
    total = sum(len(rec.tokens) for rec in eng.completed.values())
    assert split["tokens"] == total
    assert split["execs"] > 0
    assert split["device_us_per_token"] > 0.0
    assert split["host_us_per_token"] >= 0.0
    text = reg.to_prometheus_text()
    assert M_HOST_US in text and M_DEVICE_US in text
    assert (f'{M_DEVICE_US}{{engine="{eng.engine_id}",service="svc"}}'
            in text)


def test_queue_wait_gauge_denominator_counts_only_executes(traced_run):
    """The queue-wait gauge averages per-EXECUTE queue time.  The
    denominator must be the EXECUTE tally — it used to add every
    completion the step saw (writes, reads, syncs), diluting the gauge by
    the transfer traffic of the same iteration."""
    _, eng, reg, _ = traced_run
    split = eng.host_device_split()
    assert eng._attr_reqs == eng._attr_execs == split["execs"]
    assert split["queue_wait_us_mean"] == pytest.approx(
        eng._attr_queue_wait_s / split["execs"] * 1e6)
    val = reg.gauge(M_QUEUE_WAIT_US, service="svc",
                    engine=eng.engine_id).value
    assert val == pytest.approx(split["queue_wait_us_mean"])


def test_engine_crash_dumps_flight_record(monkeypatch):
    """An unexpected step() exception must leave the event ring on disk
    (the post-mortem) before the error reaches the caller."""
    reg = MetricsRegistry()
    mon = Monitor("obs-crash", SliceAllocator("n2", 1), telemetry=reg)
    eng = ContinuousBatchingEngine(ARCH, FunkyCL(mon), slots=1,
                                   prompt_len=PROMPT_LEN, max_new_tokens=4,
                                   registry=reg, page_size=PAGE)
    eng.setup()
    reg.record_event("engine_admit", rid="x", slot=0)

    def boom():
        raise RuntimeError("boom")

    monkeypatch.setattr(eng, "_step_inner", boom)
    with pytest.raises(RuntimeError, match="boom"):
        eng.step()
    path = os.path.join(tempfile.gettempdir(),
                        f"funky_flight_{eng.engine_id}.json")
    with open(path) as f:
        doc = json.load(f)
    os.unlink(path)
    assert "RuntimeError" in doc["context"]["error"]
    assert doc["context"]["engine"] == eng.engine_id
    assert any(e["kind"] == "engine_admit" for e in doc["events"])
    mon.vfpga_exit()


def test_untraced_engine_still_attributes_phases():
    """No tracer anywhere: the split still comes from Completion.phases."""
    reg = MetricsRegistry()
    mon = Monitor("obs-plain", SliceAllocator("n1", 1), telemetry=reg)
    eng = ContinuousBatchingEngine(ARCH, FunkyCL(mon), slots=2,
                                   prompt_len=PROMPT_LEN, max_new_tokens=6,
                                   registry=reg, page_size=PAGE)
    eng.setup()
    assert eng.tracer is None
    rng = np.random.Generator(np.random.Philox(1))
    eng.submit(ServeRequest(rid="p0", prompt=rng.integers(0, 100, PROMPT_LEN),
                            max_new_tokens=4))
    eng.run_until_drained()
    mon.vfpga_exit()
    split = eng.host_device_split()
    assert split["tokens"] == 4
    assert split["device_us_per_token"] > 0.0


# ---------------------------------------------------------------------------
# Scoped spans on the profiler's clock
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    """A short engine run under the JAX profiler: the profile's host
    lines, each ``[(start_ns, end_ns, name, stats)]``."""
    import jax
    from jax.profiler import ProfileData

    reg = MetricsRegistry()
    mon = Monitor("obs-prof", SliceAllocator("n3", 1), telemetry=reg)
    eng = ContinuousBatchingEngine(ARCH, FunkyCL(mon), slots=2,
                                   prompt_len=PROMPT_LEN, max_new_tokens=4,
                                   registry=reg, page_size=PAGE)
    eng.setup()
    rng = np.random.Generator(np.random.Philox(2))
    eng.submit(ServeRequest(rid="q0", prompt=rng.integers(0, 100, PROMPT_LEN),
                            max_new_tokens=3))
    eng.step()                                  # compiled before the trace
    log_dir = tmp_path_factory.mktemp("prof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        eng.run_until_drained()
    finally:
        jax.profiler.stop_trace()
        mon.vfpga_exit()
    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.start_ns, e.start_ns + e.duration_ns,
                               e.name, dict(e.stats)) for e in line.events
                              if e.name.startswith("funky.")])
    return [ln for ln in lines if ln]


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_scoped_spans_nest_in_the_profile(profiled_run):
    names = {e[2] for ln in profiled_run for e in ln}
    assert {"funky.engine.step", "funky.engine.commit",
            "funky.monitor.execute", "funky.monitor.launch"} <= names
    for ln in profiled_run:
        steps = [e for e in ln if e[2] == "funky.engine.step"]
        execs = [e for e in ln if e[2] == "funky.monitor.execute"]
        for e in ln:
            if e[2] == "funky.engine.commit":
                # the engine's spans run on the driver's thread, inside
                # its step
                assert any(_inside(e, s) for s in steps)
            if e[2] == "funky.monitor.launch":
                # the monitor's worker thread: each launch inside the
                # EXECUTE of the same program, which it labels
                outer = [x for x in execs if _inside(e, x)]
                assert len(outer) == 1
                assert e[3]["program"] == outer[0][3]["program"]
                assert not steps        # another thread than the engine's
    launches = [e for ln in profiled_run for e in ln
                if e[2] == "funky.monitor.launch"]
    assert any(e[3]["program"] == "decode_step" for e in launches)
