"""Attribution of idle device time to the program's spans: the priority
split across threads, idle under no span, the layers summing to the idle
seconds of ``trace.reduce``'s window, the readers, and ``trace.reduce``
unchanged by the program's spans among its host events."""

import pytest

from bench.harness import spans as S
from bench.harness import trace as T
from bench.harness.catalog import Catalog
from bench.tests.test_bench_metrics import ctx
from bench.tests.test_bench_trace import DEV, constructed

# Two host threads over the constructed trace's window (0..10 s), whose
# device is idle in 0-1, 3-5 and 6-9.5 s.  Driver thread: a runtime step
# holding an engine step, a router pop; monitor thread: a transfer that
# overlaps the engine step's end, an EXECUTE holding its launch.
DRIVER = [(0.0, 4.0, "funky.runtime.step"),
          (0.5, 3.5, "funky.engine.step"),
          (1.0, 3.0, "funky.engine.commit"),
          (6.0, 6.5, "funky.router.pop")]
MONITOR = [(-1.0, -0.5, "funky.monitor.execute"),
           (3.2, 3.6, "funky.monitor.transfer"),
           (7.0, 8.0, "funky.monitor.execute"),
           (7.0, 7.5, "funky.monitor.launch")]
IDLE = {"launch": 0.4 + 1.0,    # 3.2-3.6, 7-8
        "engine": 0.5 + 0.2,    # 0.5-1, 3-3.2
        "loop": 0.5 + 0.4 + 0.5,  # 0-0.5, 3.6-4, 6-6.5
        "none": 1.0 + 0.5 + 1.5}  # 4-5, 6.5-7, 8-9.5


def with_spans():
    devs, mods, spans, host = constructed()
    return devs, mods, spans, host + DRIVER + MONITOR


def test_priority_split_over_two_threads():
    devs, _, _, host = with_spans()
    r = S.reduce(devs, host, 0.0, 10.0)
    assert r["idle_by_layer"] == pytest.approx(IDLE)
    assert r["spans"]["funky.monitor.execute"]["count"] == 1   # one before
    assert r["spans"]["funky.engine.step"] == pytest.approx(
        {"count": 1, "seconds": 3.0})
    assert "TransferToDevice" not in r["spans"]


def test_idle_under_no_span_is_none():
    devs, _, _, host = constructed()
    r = S.reduce(devs, host, 0.0, 10.0)
    assert r["idle_by_layer"] == pytest.approx(
        {"launch": 0.0, "engine": 0.0, "loop": 0.0, "none": 6.5})
    assert r["spans"] == {}


def test_layers_sum_to_idle_of_the_cut_window():
    devs, mods, spans, host = with_spans()
    launch = (7.0, 7.001, "PjitFunction(jit(decode_step))")
    for cut in (False, True):
        d = {DEV: [op for op in devs[DEV] if op[0] < 9.0]} if cut else devs
        late = [launch] if cut else []
        r = T.reduce(d, mods, spans, host + late)
        lo = T.window(spans)[0]
        a = S.reduce(d, host + late, lo, lo + r["window_s"])
        assert sum(a["idle_by_layer"].values()) == pytest.approx(
            r["window_s"] - r["busy_s"])
    assert r["window_s"] == pytest.approx(6.0)
    # the cut window ends at 6 s: the EXECUTE at 7 s is outside it
    assert a["idle_by_layer"]["launch"] == pytest.approx(0.4)
    assert "funky.monitor.launch" not in a["spans"]


def test_first_chip_that_ran_anything():
    devs, _, _, host = with_spans()
    devs = {"/device:TPU:0": [], "/device:TPU:1": devs[DEV]}
    r = S.reduce(devs, host, 0.0, 10.0)
    assert r["idle_by_layer"] == pytest.approx(IDLE)


def test_reduce_unchanged_by_program_spans():
    """The program's spans are host events to ``trace.reduce``: every key
    reads as without them, and only the idle gaps' labels may name them."""
    plain = T.reduce(*constructed())
    spanned = T.reduce(*with_spans())
    assert spanned.keys() == plain.keys()
    for k in plain:
        if k != "idle_gaps":
            assert spanned[k] == plain[k], k
    assert [s for _, s in spanned["idle_gaps"]] == \
        [s for _, s in plain["idle_gaps"]]
    # the gap at 0-1 s, unattributed without them
    assert plain["idle_gaps"][2][0] == "unattributed"
    assert spanned["idle_gaps"][2][0] == "funky.runtime.step"


def test_readers():
    cat = Catalog()
    names = {"launch": "idle_launch_share", "engine": "idle_engine_share",
             "loop": "idle_loop_share"}
    devs, mods, spans, host = with_spans()
    tr = T.reduce(devs, mods, spans, host)
    for layer, name in names.items():
        reader = cat.reader(name)
        assert reader.read(ctx()) is None                  # untraced run
        # a traced run of a program without spans: nothing to read
        assert reader.read(ctx(trace=dict(tr))) is None
        plain = S.reduce(devs, constructed()[3], 0.0, 10.0)
        assert reader.read(ctx(trace=dict(tr, **plain))) is None
        full = dict(tr, **S.reduce(devs, host, 0.0, 10.0))
        assert reader.read(ctx(trace=full)) == pytest.approx(
            100.0 * IDLE[layer] / 10.0)


def test_recorded_trace(tmp_path):
    """Spans opened by ``repro.obs.span`` on two threads read back from a
    recorded profile; on the CPU no chip is traced, so nothing is idle."""
    import threading

    import jax
    import jax.numpy as jnp

    from repro import obs

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()

    def worker():
        with obs.span("monitor.execute", program="f"):
            with obs.span("monitor.launch", program="f"):
                f(x).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with obs.span("runtime.step"):
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=60)
    jax.profiler.stop_trace()
    assert not th.is_alive()
    path = T.find_xplane(str(tmp_path))
    tr = T.summarize(path)
    assert set(tr) == {"window_s", "cut_s", "busy_s", "chips_traced",
                       "programs", "device_ops", "idle_gaps", "lines"}
    r = S.summarize(path, tr["window_s"])
    assert {n: c["count"] for n, c in r["spans"].items()} == {
        "funky.runtime.step": 1, "funky.monitor.execute": 1,
        "funky.monitor.launch": 1}
    assert set(r["idle_by_layer"].values()) == {0.0}
