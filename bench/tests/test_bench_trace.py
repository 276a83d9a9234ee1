"""Trace reduction: busy union, idle share, per-program device time, op
self times and labelled idle gaps, on a constructed trace and on a trace
recorded here."""

import pytest

from bench.harness import trace as T

DEV = "/device:TPU:0"


def constructed():
    # window 0..10 s.  A loop op (1-3) holds two nested ops; one op runs
    # past the window's end; one before it.
    ops = [(-2.0, -1.0, "%fusion.0 = f32[] fusion()"),
           (1.0, 3.0, "%while.18 = (s32[]) while(...)"),
           (1.2, 1.7, "%fusion.1 = bf16[8] fusion(...)"),
           (2.0, 2.5, "%fusion.2 = bf16[8] fusion(...)"),
           (5.0, 6.0, "%fusion.1 = bf16[8] fusion(...)"),
           (9.5, 11.0, "%copy.3 = bf16[8] copy(...)")]
    modules = [(1.0, 3.0, "decode_step"), (5.0, 6.0, "prefill_admit"),
               (9.5, 11.0, "decode_step"), (-1.0, 0.5, "decode_step")]
    spans = [(0.0, 10.0, "bench.window"), (6.2, 9.0, "bench.move.evict")]
    host = [(3.0, 4.9, "TransferToDevice"), (0.0, 60.0, "thread")]
    return {DEV: ops}, {DEV: modules}, spans, host


def test_device_summary_one_pass():
    ops = constructed()[0][DEV]
    d = T.device_summary(iter(ops), 0.0, 10.0)
    # busy: outermost ops 1-3, 5-6, 9.5-10
    assert d["busy_s"] == pytest.approx(3.5)
    assert d["self_s"]["while.18"] == pytest.approx(1.0)   # 2 - 0.5 - 0.5
    assert d["self_s"]["fusion.1"] == pytest.approx(1.5)
    assert d["self_s"]["fusion.2"] == pytest.approx(0.5)
    assert d["self_s"]["copy.3"] == pytest.approx(0.5)
    assert d["self_s"]["fusion.0"] == 0.0
    assert d["gaps"] == [(6.0, 9.5), (3.0, 5.0), (0.0, 1.0)]
    assert d["end"] == 10.0
    idle = T.device_summary([], 0.0, 10.0)
    assert idle["gaps"] == [] and idle["end"] == 0.0


def test_reduce_constructed():
    r = T.reduce(*constructed())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(3.5)          # idle share 65 %
    assert r["chips_traced"] == 1
    # executions counted by start inside the window
    assert r["programs"]["decode_step"]["count"] == 2
    assert r["programs"]["decode_step"]["seconds"] == pytest.approx(3.5)
    assert r["programs"]["prefill_admit"] == {"count": 1, "seconds": 1.0}
    assert [n for n, _ in r["device_ops"]] == [
        "fusion.1", "while.18", "fusion.2", "copy.3"]
    gaps = r["idle_gaps"]
    assert [round(s, 6) for _, s in gaps] == [3.5, 2.0, 1.0]
    # longest gap 6-9.5 lies under the benchmark's evict span; 3-5 under
    # a runtime event; 0-1 under nothing but the umbrella thread event
    assert [n for n, _ in gaps] == ["bench.move.evict", "TransferToDevice",
                                    "unattributed"]


def test_window_cut_where_the_device_record_stops():
    # the device's record stops after the op ending at 6 s while the host
    # goes on launching programs: the traced window is 0..6 s
    devs, mods, spans, host = constructed()
    devs[DEV] = [op for op in devs[DEV] if op[0] < 9.0]
    mods[DEV] = [m for m in mods[DEV] if m[0] < 9.0]
    late = host + [(7.0, 7.001, "PjitFunction(jit(decode_step))"),
                   (8.0, 8.001, "PjitFunction(jit(decode_step))")]
    r = T.reduce(devs, mods, spans, late)
    assert r["window_s"] == pytest.approx(6.0)
    assert r["cut_s"] == pytest.approx(4.0)
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["programs"]["decode_step"]["count"] == 1
    assert [round(s, 6) for _, s in r["idle_gaps"]] == [2.0, 1.0]
    # a device idle at the window's end with no launch after it is idle
    r = T.reduce(devs, mods, spans, host)
    assert r["window_s"] == pytest.approx(10.0) and r["cut_s"] == 0.0
    assert [round(s, 6) for _, s in r["idle_gaps"]] == [4.0, 2.0, 1.0]


def test_busy_averages_over_chips():
    devs, mods, spans, host = constructed()
    devs["/device:TPU:1"] = [(0.0, 10.0, "%fusion.9 = f32[] fusion()")]
    r = T.reduce(devs, mods, spans, host)
    assert r["chips_traced"] == 2
    assert r["busy_s"] == pytest.approx((3.5 + 10.0) / 2)


def test_names():
    assert T.program_name("jit_decode_step(17)") == "decode_step"
    assert T.program_name("jit_prefill_admit") == "prefill_admit"
    assert T.op_name("%fusion.154 = bf16[8,11008]{1,0} fusion(x)") == \
        "fusion.154"


def test_window_required():
    devs, mods, _, host = constructed()
    with pytest.raises(ValueError):
        T.reduce(devs, mods, [], host)


def test_recorded_trace(tmp_path):
    """A trace recorded with the profiler reads back with its window span;
    on the CPU there is no device plane, so nothing counts as busy."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.client.send"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = T.summarize(T.find_xplane(str(tmp_path)))
    assert r["window_s"] > 0
    assert r["chips_traced"] == 0 and r["busy_s"] == 0.0
    assert any(p.startswith("/host:") for p, _ in r["lines"])
