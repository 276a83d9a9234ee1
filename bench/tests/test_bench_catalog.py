"""The harness finds every piece of a cell by name, and a configuration,
traffic mix, metric and cell added as new files need no edit to a file
that is already there."""

import json
import os
import shutil

import pytest

from bench.harness.catalog import Catalog, CatalogError
from bench.harness.context import RunContext

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def snapshot(d):
    out = {}
    for base, _, files in os.walk(d):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, d)] = fh.read()
    return out


def test_every_cell_resolves():
    cat = Catalog()
    b = cat.benchmark
    for w in b["workloads"]:
        cfg = cat.config(w["config"])
        assert cfg["name"] == w["config"]
        mix = cat.traffic(w["traffic"])
        assert mix["engine"]["slots"] >= 1
        lim = cat.limits(w["name"])
        assert lim["logit_gap"]["limit"] > 0
        assert hasattr(cat.reference(cfg["reference"]), "Reference")
        for kind in ("end_to_end", "per_layer"):
            for m in cat.metrics(w["name"], kind):
                assert callable(cat.reader(m["name"]).read)
    with pytest.raises(CatalogError):
        cat.workload("no-such-cell")


@pytest.mark.parametrize("name", ["stablelm-3b", "yi-9b-24l"])
def test_config_files_are_what_the_program_runs(name):
    from bench.run import register
    from repro.configs import get_arch, registry

    cfg = Catalog().config(name)
    before = dict(registry.ARCHS)
    try:
        assert register(cfg) == name
        assert get_arch(name).num_layers == cfg["num_layers"]
        bad = dict(cfg, d_ff=cfg["d_ff"] + 1)
        with pytest.raises(RuntimeError):
            register(bad)
        # the norm's epsilon is fixed in the program and read off it
        with pytest.raises(RuntimeError, match="eps"):
            register(dict(cfg, norm_eps=cfg["norm_eps"] * 10))
    finally:
        registry.ARCHS.clear()
        registry.ARCHS.update(before)


def test_new_cell_as_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = snapshot(str(root / "bench"))
    b = root / "bench"
    cfg = json.loads((b / "configs" / "yi-9b-24l.json").read_text())
    cfg.update(name="yi-9b-12l", num_layers=12)
    (b / "configs" / "yi-9b-12l.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["prompt_len"]["max"] = 128
    (b / "traffic" / "short.json").write_text(json.dumps(mix))
    (b / "limits" / "yi-9b-12l.short.json").write_text(
        json.dumps({"logit_gap": {"limit": 0.5}}))
    (b / "metrics" / "tokens_per_request.py").write_text(
        "def read(ctx):\n"
        "    n = len({r.rid for r, _ in ctx.window_tokens()})\n"
        "    return len(ctx.window_tokens()) / n if n else None\n")
    bj = json.loads((root / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": "yi-9b-12l", "source": "x",
                          "file": "bench/configs/yi-9b-12l.json",
                          "reduced": ["num_layers"], "why": "x"})
    bj["workloads"].append({"name": "yi-9b-12l.short", "config": "yi-9b-12l",
                            "traffic": "short", "chips": 1, "why": "x"})
    bj["per_layer"].append({"name": "tokens_per_request", "unit": "tokens",
                            "better": "higher", "source": "host_clock",
                            "layer": "router", "moves": "tokens_per_s",
                            "workloads": ["yi-9b-12l.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bj))

    cat = Catalog(root=str(root), bench_dir=str(b))
    w = cat.workload("yi-9b-12l.short")
    assert cat.config(w["config"])["num_layers"] == 12
    assert cat.traffic(w["traffic"])["prompt_len"]["max"] == 128
    assert cat.limits(w["name"])["logit_gap"]["limit"] == 0.5
    names = [m["name"] for m in cat.metrics(w["name"], "per_layer")]
    assert names == ["tokens_per_request"]
    assert "tbt_p95_ms" not in [m["name"]
                                for m in cat.metrics(w["name"], "end_to_end")]
    from bench.harness.clients import Record

    recs = [Record(index=i, rid=f"r{i}", prompt_len=8, bucket=128,
                   max_new=4, req=None, submit_t=0.0, times=[1.0, 2.0])
            for i in range(3)]
    ctx = RunContext(cfg=cfg, mix=mix, peaks={}, chips=1, t0=0.0, t1=5.0,
                     setup_s=1.0, records=recs)
    assert cat.reader("tokens_per_request").read(ctx) == 2.0
    # nothing that was there changed
    after = snapshot(str(b))
    assert {k: v for k, v in after.items() if k in before} == before
