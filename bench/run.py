"""Chip benchmark of the orchestrated serving path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown`` of the trace, and last ``checks``: each number that decides
``correct`` beside its limit.  The checks are also the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, or on a device kind that ``bench/peaks.json`` does not list, it exits
non-zero and prints no result.

A run: replicas are deployed through the orchestrator (orchestrator ->
node agent -> CRI -> runtime -> EngineServeTask -> engine -> monitor), with
weights made on the chip from the seed and programs from the persistent
compilation cache in ``<checkout>/.jax_cache``; one warm-up request per
prompt bucket is served; closed-loop clients fill every lane; then the
window is measured for ``--seconds`` while the clients keep a backlog at
the service's RequestRouter and the mix's replica moves happen at their
offsets.  After the window the replicas are removed, and a sample of the
finished requests is checked against the configuration's plain float32
reference (``bench/harness/verdict.py``).

A cell is data.  To add one, add its entry to ``BENCHMARK.json`` and these
files, each found by name; no file that is here needs an edit:

    bench/configs/<config>.json   sizes as run, source, cut (``reduced``),
                                  the program's ``arch`` it derives from,
                                  and the name of its reference
    bench/configs/<ref>.py        a plain reference (``Reference(cfg)``)
    bench/traffic/<mix>.json      traffic parameters for bench/harness/traffic.py
    bench/metrics/<metric>.py     ``read(ctx)`` -> number or None, for every
                                  end-to-end and per-layer metric
    bench/limits/<cell>.json      the limit of each compared number, with
                                  the readings it was set from
    bench/peaks.json              peaks per device kind

``bench/harness/`` holds the generator, the client loop, the deployment,
the trace reduction, the operation and byte counts and the verdict;
``bench/tests/`` their CPU tests.  ``--control 1`` (not used by the
benchmark's own runs) puts the control, the reference in float8, in the
program's place: its own choices on the same sequences are judged by the
same limit, so a control run reads ``correct`` false.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.harness.catalog import Catalog, CatalogError  # noqa: E402

MODEL_FIELDS = ("family", "num_layers", "d_model", "num_heads",
                "num_kv_heads", "head_dim", "d_ff", "vocab_size", "mlp_kind",
                "norm_kind", "rope_pct", "rope_theta", "qk_norm",
                "sliding_window", "tie_embeddings", "dtype")


class RunError(RuntimeError):
    pass


def log(what: str, **kw) -> None:
    print(what + (" " + json.dumps(kw, default=str) if kw else ""),
          file=sys.stderr, flush=True)


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_fail(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise RunError(f"cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def program_norm_eps(run) -> float:
    """The epsilon the program's norm adds, read off the norm itself: on
    a row of +s and -s the mean square and the variance are both s**2, so
    the norm returns s / sqrt(s**2 + eps)."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import init_norm, norm_fwd

    s = 1e-3
    f32 = dataclasses.replace(run, dtype="float32")
    p = init_norm(f32, 2)
    y = jax.jit(lambda x: norm_fwd(f32, p, x))(
        jnp.asarray([[s, -s]], jnp.float32))
    return (s / float(y[0, 0])) ** 2 - s ** 2


def register(cfg: dict) -> str:
    """Make the configuration known to the program under its name and
    check that what the program will run is what the file states.  Keys
    of ``reduced`` that the program's configuration has are applied; the
    others (a norm's epsilon) are fixed in the program and only checked."""
    from repro.configs import registry
    from repro.configs.registry import get_arch

    base = get_arch(cfg["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    changes = {k: cfg[k] for k in cfg["reduced"] if k in fields}
    run = dataclasses.replace(base, name=cfg["name"], **changes)
    for k in MODEL_FIELDS:
        got = run.head_dim_ if k == "head_dim" else getattr(run, k)
        if k in cfg and got != cfg[k]:
            raise RunError(f"{cfg['name']}: the program runs {k}={got!r}, "
                           f"the configuration file states {cfg[k]!r}")
    eps = program_norm_eps(run)
    if not math.isclose(eps, cfg["norm_eps"], rel_tol=1e-3):
        raise RunError(f"{cfg['name']}: the program's norm adds eps={eps:.3g}"
                       f", the configuration file states {cfg['norm_eps']!r}")
    if cfg["name"] != cfg["arch"]:
        registry.ARCHS[cfg["name"]] = run
    return cfg["name"]


class Compiles:
    """Counts, while open, the programs JAX loaded (``loads``: every
    lowering this process had not made before) and of those the ones the
    persistent cache did not hold (``n``: compiled)."""

    LOAD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __enter__(self):
        import jax

        self.loads = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_load)
        jax.monitoring.register_event_listener(self._on_hit)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_load)
        jax.monitoring.unregister_event_listener(self._on_hit)

    @property
    def n(self) -> int:
        return self.loads - self.hits

    def _on_load(self, event, duration, **kw):
        if event == self.LOAD:
            self.loads += 1

    def _on_hit(self, event, **kw):
        if event == self.HIT:
            self.hits += 1


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def split_totals(svc) -> dict:
    host = toks = 0.0
    for cid in svc.cids:
        s = svc.engine(cid).host_device_split()
        host += s["host_s_total"]
        toks += s["tokens"]
    return {"host_s": host, "tokens": toks}


def resolve_moves(moves, clients, svc, single: bool) -> bool:
    """Stamp each finished move with the first token its replica delivered
    after the resume returned; True once every move has one."""
    open_ = [m for m in moves if m.t_first is None]
    if not open_:
        return True
    owner = None if single else svc.engine_of()
    for m in open_:
        firsts = []
        for r in clients.records:
            if r.index < 0 or not (single or owner.get(r.rid) == m.replica):
                continue
            i = bisect.bisect_right(r.times, m.t_resumed)
            if i < len(r.times):
                firsts.append(r.times[i])
        if firsts:
            m.t_first = min(firsts)
    return all(m.t_first is not None for m in moves)


def mover(svc, mix, t0, moves, stop, errors) -> None:
    from bench.harness.context import Move

    try:
        gap = mix.get("move_min_serve_s", 0.0)
        for mv in mix.get("moves", ()):
            cid = svc.cids[mv["replica"]]
            while True:
                if stop.is_set():
                    return
                now = time.perf_counter()
                prev = moves[-1] if moves else None
                if now >= t0 + mv["at_s"] and (
                        prev is None or (prev.t_first is not None
                                         and now >= prev.t_first + gap)):
                    break
                time.sleep(0.005)
            t_cmd, t_res, src, dst = svc.move(cid, mv["to_node"], annotate)
            moves.append(Move(t_cmd, t_res, cid, src, dst))
    except BaseException as e:  # noqa: BLE001 - surfaced by the main loop
        errors.append(e)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else 0


def run_cell(cat: Catalog, args, **kw) -> dict:
    """One run of a cell; ``kw``: cfg, mix, limits, peaks, devices, chips."""
    with Compiles() as compiles:
        return _run_cell(cat, args, compiles, **kw)


def _run_cell(cat: Catalog, args, compiles, *, cfg: dict, mix: dict,
              limits: dict, peaks: dict, devices: list, chips: int) -> dict:
    import jax

    from bench.harness import trace as trace_mod
    from bench.harness import verdict
    from bench.harness.clients import ClosedLoop
    from bench.harness.context import RunContext
    from bench.harness.serving import Service
    from bench.harness.traffic import Requests

    arch = register(cfg)
    eng = mix["engine"]
    slots, replicas = eng["slots"], mix.get("replicas", 1)
    weight_seed = args.seed % (2 ** 31)
    mem = (devices[0].memory_stats() or {}).get("bytes_limit", 8 << 30)
    svc = Service(arch, mix, weight_seed, int(mem))
    gen = Requests(mix, args.seed, cfg["vocab_size"])
    clients = ClosedLoop(svc.router, gen, gen.clients(slots),
                         eng["prompt_buckets"], annotate=annotate)
    with annotate("bench.setup"):
        svc.up()
        log("replicas_up", where=svc.where(),
            seconds=time.perf_counter() - T_START)
        clients.warmup(600.0, svc.check)
        clients.start()
        clients.wait(lambda: clients.lanes_busy() >= slots * replicas,
                     600.0, svc.check)
    log_dir = None
    if args.trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    single = len(svc.cids) == 1
    c0, l0, s0 = compiles.n, compiles.loads, split_totals(svc)
    moves, errors, stop = [], [], threading.Event()
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    t1 = t0 + args.seconds
    th = threading.Thread(target=mover, args=(svc, mix, t0, moves, stop,
                                              errors), daemon=True)
    with annotate("bench.window"):
        th.start()
        k = 0
        while time.perf_counter() < t1:
            clients.poll()
            k += 1
            if k % 20 == 0:
                svc.check()
                resolve_moves(moves, clients, svc, single)
                if errors:
                    raise errors[0]
            time.sleep(0.001)
    clients.accepting = False
    stop.set()
    c1, l1, s1 = compiles.n, compiles.loads, split_totals(svc)
    if log_dir:
        jax.profiler.stop_trace()
    th.join(timeout=300)
    if errors:
        raise errors[0]
    clients.wait(lambda: resolve_moves(moves, clients, svc, single), 120.0,
                 svc.check)
    # streams that crossed a move are checked whole: let them finish
    cross = [r for r in clients.records
             if r.index >= 0 and verdict.crossed(r, moves)]
    clients.wait(lambda: all(r.done_t is not None for r in cross), 180.0,
                 svc.check)
    mem_peak = memory_peak(svc.devices())
    wall = time.time() - time.perf_counter()
    evicts = [kw for t, kw in svc.evicts() if t0 <= t - wall <= t1]
    sample = verdict.sample(clients.records, moves, args.seed,
                            mix["correctness"]["sample_tokens"],
                            mix["correctness"]["max_requests"])
    attempted = sum(1 for r in clients.records if r.index >= 0
                    and r.submit_t <= t1 and (r.done_t is None
                                              or r.done_t >= t0))
    svc.down()
    del svc
    gc.collect()
    log("window_done", compiles_in_window=c1 - c0,
        programs_loaded_in_window=l1 - l0, sampled=len(sample),
        memory_peak_bytes=mem_peak)
    for m in moves:
        log("move", src=m.src, dst=m.dst, command_s=m.t_cmd - t0,
            resumed_s=m.t_resumed - t0, first_token_s=m.t_first - t0)
    for e in evicts:
        log("evict", **e)
    # the reference runs with the program's state freed
    ref = cat.reference(cfg["reference"]).Reference(cfg)
    tokens, targets = verdict.arrays(
        sample, mix["correctness"]["max_requests"],
        max(eng["prompt_buckets"]) + eng["max_new_tokens"])
    t_ref = time.perf_counter()
    ref_out = ref.run(weight_seed, tokens, targets,
                      control=bool(args.control))
    v = verdict.judge(ref_out, sample, limits)
    log("reference", seconds=time.perf_counter() - t_ref)
    tr = None
    if log_dir:
        t_tr = time.perf_counter()
        tr = trace_mod.summarize(trace_mod.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        log("trace", seconds=time.perf_counter() - t_tr, cut_s=tr["cut_s"],
            lines=tr["lines"][:24])
    ctx = RunContext(cfg=cfg, mix=mix, peaks=peaks, chips=chips, t0=t0,
                     t1=t1, setup_s=setup_s, records=clients.records,
                     moves=moves, evicts=evicts, trace=tr,
                     split={"host_s": s1["host_s"] - s0["host_s"],
                            "tokens": s1["tokens"] - s0["tokens"]})
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cat.metrics(args.workload, kind):
        val = cat.reader(m["name"]).read(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": v["correct"], "attempted": attempted,
           "failed": v["failed"], "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
        log("programs", **tr["programs"])
    out["checks"] = v["checks"]
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cat = Catalog()
        cell = cat.workload(args.workload)
        cfg = cat.config(cell["config"])
        mix = cat.traffic(cell["traffic"])
        limits = cat.limits(cell["name"])
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            cat.root, ".jax_cache")
        sys.path.insert(0, os.path.join(cat.root, "src"))
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devices = chips_or_fail(cell["chips"])[:cell["chips"]]
        peaks = cat.peaks(devices[0].device_kind)
        out = run_cell(cat, args, cfg=cfg, mix=mix, limits=limits,
                       peaks=peaks, devices=devices, chips=cell["chips"])
    except (RunError, CatalogError) as e:
        log("error", message=str(e))
        return 2
    report(out)
    return 0


def report(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    try:
        rc = main()
    except Exception:  # noqa: BLE001 - any failed phase fails the run
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # engine and monitor threads may still hold XLA state; skip interpreter
    # teardown so a finished run cannot abort on exit
    os._exit(rc)
