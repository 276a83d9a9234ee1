"""Metric arithmetic of every reader in bench/metrics on a constructed run,
and the peaks table refusing a device it does not list."""

import json
import os

import numpy as np
import pytest

from bench.harness import model_math
from bench.harness.catalog import Catalog, CatalogError
from bench.harness.clients import Record
from bench.harness.context import Move, RunContext

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cfg():
    with open(os.path.join(BENCH, "configs", "stablelm-3b.json")) as f:
        return json.load(f)


def rec(index, times, bucket=128, prompt_len=100, max_new=4):
    return Record(index=index, rid=f"r{index}", prompt_len=prompt_len,
                  bucket=bucket, max_new=max_new, req=None, submit_t=0.0,
                  times=list(times))


def ctx(**kw):
    # window 10..20 s: r0 delivers 3 tokens in it (gaps 1.5, 1.0, 2.0;
    # its first token, at 9.5, before it), r1 its first token and one
    # decode token (gap 0.5); the warm-up request never counts
    records = [rec(0, [9.5, 11.0, 12.0, 14.0, 25.0]),
               rec(1, [15.0, 15.5], bucket=512, prompt_len=300),
               rec(-1, [12.0, 13.0])]
    base = dict(cfg=cfg(), mix={}, peaks=PEAKS, chips=1, t0=10.0, t1=20.0,
                setup_s=33.0, records=records)
    base.update(kw)
    return RunContext(**base)


def read(name, c):
    return Catalog().reader(name).read(c)


def test_window_views():
    c = ctx()
    assert len(c.window_tokens()) == 5
    assert sorted(c.gaps().tolist()) == [0.5, 1.0, 1.5, 2.0]
    # r0's tokens 1..3 at positions 128+1..128+3; r1's token 1 at 512+1
    assert sorted(c.decode_contexts()) == [129, 130, 131, 513]
    assert [r.rid for r in c.admissions()] == ["r1"]


def test_rate_tail_and_setup():
    c = ctx()
    assert read("tokens_per_s", c) == pytest.approx(5 / 10.0)
    assert read("tbt_p95_ms", c) == pytest.approx(
        np.quantile([0.5, 1.0, 1.5, 2.0], 0.95) * 1e3)
    assert read("setup_s", c) == 33.0
    assert read("tbt_p95_ms", ctx(records=[])) is None


def test_move_stall_over_moves():
    moves = [Move(t_cmd=11.0, t_resumed=13.0, replica="a", src="n0",
                  dst="n0", t_first=14.0),
             Move(t_cmd=16.0, t_resumed=16.5, replica="a", src="n0",
                  dst="n0", t_first=18.0),
             Move(t_cmd=25.0, t_resumed=26.0, replica="a", src="n0",
                  dst="n0", t_first=27.0)]
    # the move after the window is left out: (3 + 2) / 2
    assert read("move_stall_s", ctx(moves=moves)) == pytest.approx(2.5)
    assert read("move_stall_s", ctx()) is None


def test_evict_rate():
    ev = [{"saved_bytes": 7e9, "total_seconds": 11.0},
          {"saved_bytes": 1e9, "total_seconds": 1.0}]
    assert read("evict_gbps", ctx(evicts=ev)) == pytest.approx(8 / 12.0)
    assert read("evict_gbps", ctx()) is None


def test_host_per_token():
    c = ctx(split={"host_s": 0.5, "tokens": 1000})
    assert read("host_us_per_token", c) == pytest.approx(500.0)
    assert read("host_us_per_token", ctx()) is None


def test_trace_metrics():
    tr = {"window_s": 10.0, "busy_s": 8.0, "chips_traced": 1,
          "programs": {"decode_step": {"count": 3, "seconds": 2.7},
                       "prefill_admit": {"count": 2, "seconds": 0.08}}}
    c = ctx(trace=tr)
    assert read("idle_share", c) == pytest.approx(20.0)
    assert read("prefill_ms", c) == pytest.approx(40.0)
    ctxs = [129, 130, 131, 513]
    flops, byts = model_math.decode_step_cost(cfg(), ctxs)
    byts += 2 * model_math.decode_step_cost(cfg(), [])[1]
    least = max(flops / 197e12, byts / 819e9)
    assert read("decode_roofline", c) == pytest.approx(100 * least / 2.7)
    mfu = (model_math.prefill_flops(cfg(), 300)
           + sum(model_math.token_flops(cfg(), x) for x in ctxs))
    assert read("mfu", c) == pytest.approx(100 * mfu / (10.0 * 197e12))
    for name in ("idle_share", "prefill_ms", "decode_roofline", "mfu"):
        assert read(name, ctx()) is None


def test_peaks_lookup():
    p = Catalog().peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(CatalogError):
        Catalog().peaks("TPU v4")
