"""Smoke run of the orchestrated serving path on TPU chips.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a four-chip host, one node per chip

One chip: build a one-node cluster whose vSlice budget is the chip's
``bytes_limit``, run each Pallas kernel once against its reference, deploy a
full-width ``stablelm-3b`` engine-serve replica through the orchestrator
(random weights from ``Smoke.seed``), serve 16 seeded requests through the
service's ``RequestRouter``, serve eight of the prompts again with an evict
and resume in the middle of their generation (the streams must be
bit-exact), close the router, see the task end DONE, and compare two served
requests with a plain dense greedy decode on the same chip.

Four chips: node *i* owns chip *i*.  One replica per node behind one router
serves the 16 requests; every buffer of each replica must live on its own
chip.  Eight prompts are then served again by the node-0 replica, which is
migrated to node 1 mid-generation (bit-exact again), and every served
request is compared with a dense greedy decode on chip 0.

A served stream may leave the dense reference only where the reference's
two best logits lie within ``GAP_TOL_ULPS`` bf16 ulps: the paged engine and
the dense decode sum attention in different orders, which can flip a
near-tie.  Any failed phase exits non-zero.  The last line of standard
output is the JSON result; the script prints none without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from dataclasses import dataclass

import numpy as np

SERVICE = "smoke-svc"
GAP_TOL_ULPS = 4
TIMEOUT_S = 600.0


@dataclass(frozen=True)
class Smoke:
    arch: str = "stablelm-3b"
    slots: int = 8
    buckets: tuple = (128, 512)
    max_new: int = 64
    page: int = 8
    n_requests: int = 16
    prompt_lens: tuple = (100, 512)     # inclusive range of prompt lengths
    repeats: int = 8                    # prompts served again across a move
    reference_checks: int = 2           # one chip: requests checked densely
    seed: int = 0


class SmokeError(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def log(what: str, **kw) -> None:
    print(what + (" " + json.dumps(kw, default=str) if kw else ""),
          flush=True)


def bytes_limit(dev) -> int:
    stats = dev.memory_stats()
    check(stats is not None and "bytes_limit" in stats,
          f"{dev} reports no memory_stats()['bytes_limit']")
    return int(stats["bytes_limit"])


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel once, compiled for the chip, against its ref
# ---------------------------------------------------------------------------
def check_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import sdpa_ref
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rglru_scan.ref import rglru_scan_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref

    def err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16
    # stablelm-3b's attention layout: 32 heads of 80
    q = jax.random.normal(ks[0], (2, 1, 32, 80)).astype(bf)
    k = jax.random.normal(ks[1], (2, 1024, 32, 80)).astype(bf)
    v = jax.random.normal(ks[2], (2, 1024, 32, 80)).astype(bf)
    kv_pos = jnp.arange(1024, dtype=jnp.int32)
    errs = {"decode_attention": err(
        decode_attention(q, k, v, 700, kv_pos=kv_pos),
        decode_ref(q, k, v, 700, kv_pos))}
    qf = jax.random.normal(ks[3], (1, 512, 32, 80)).astype(bf)
    errs["flash_attention"] = err(flash_attention(qf, k[:1, :512],
                                                  v[:1, :512]),
                                  sdpa_ref(qf, k[:1, :512], v[:1, :512]))
    # recurrentgemma-9b's lru_width
    a = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 512, 4096))) * 0.98
    b = jax.random.normal(ks[5], (1, 512, 4096)) * 0.1
    errs["rglru_scan"] = err(rglru_scan(a, b)[0], rglru_scan_ref(a, b)[0])
    # mamba2-1.3b: 64 SSD heads of 64, state 128
    x = jax.random.normal(ks[6], (1, 512, 64, 64), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[7], (1, 512, 64)))
    A = -jnp.exp(jax.random.normal(ks[0], (64,)) * 0.2)
    Bm = jax.random.normal(ks[1], (1, 512, 128)) * 0.3
    Cm = jax.random.normal(ks[2], (1, 512, 128)) * 0.3
    errs["ssd_scan"] = err(ssd_scan(x, dt, A, Bm, Cm)[0],
                           ssd_ref(x, dt, A, Bm, Cm)[0])
    log("kernels_vs_ref_max_rel_err", **errs)
    # bf16 attention inputs; the scans run in f32 (MXU passes are bf16)
    tol = {"decode_attention": 2e-2, "flash_attention": 2e-2,
           "rglru_scan": 1e-4, "ssd_scan": 2e-2}
    for name, e in errs.items():
        check(e < tol[name], f"{name} differs from its reference: {e}")


# ---------------------------------------------------------------------------
# cluster, requests, serving
# ---------------------------------------------------------------------------
def image(sm: Smoke):
    from repro.core import TaskImage

    return TaskImage(name=SERVICE, kind="engine-serve", arch=sm.arch,
                     global_batch=sm.slots, prompt_len=max(sm.buckets),
                     prompt_buckets=tuple(sm.buckets),
                     max_new_tokens=sm.max_new, page_size=sm.page,
                     paged_kv=True, total_steps=10 ** 9, seed=sm.seed)


def make_requests(sm: Smoke, vocab: int) -> list:
    from repro.serve.engine import ServeRequest

    rng = np.random.Generator(np.random.Philox(sm.seed))
    lo, hi = sm.prompt_lens
    return [ServeRequest(rid=f"r{i:02d}",
                         prompt=rng.integers(0, vocab, int(rng.integers(
                             lo, hi + 1))).astype(np.int32),
                         max_new_tokens=sm.max_new)
            for i in range(sm.n_requests)]


def again(reqs: list) -> list:
    """The same prompts under new request ids."""
    from repro.serve.engine import ServeRequest

    return [ServeRequest(rid=f"again-{r.rid}", prompt=r.prompt,
                         max_new_tokens=r.max_new_tokens) for r in reqs]


def _records(cluster, cid):
    return [nd.runtime.tasks[cid] for nd in cluster.nodes.values()
            if cid in nd.runtime.tasks]


def _raise_if_failed(cluster) -> None:
    from repro.core import TaskStatus

    for nd in cluster.nodes.values():
        for cid, rec in list(nd.runtime.tasks.items()):
            if rec.status is TaskStatus.FAILED:
                raise SmokeError(f"task {cid} on {nd.node_id} FAILED: "
                                 f"{rec.error!r}") from rec.error


def wait_running(cluster, cids) -> dict:
    """Block until every task finished setup; returns cid -> node id."""
    from repro.core import TaskStatus

    deadline = time.time() + TIMEOUT_S
    while time.time() < deadline:
        _raise_if_failed(cluster)
        where = {cid: nid for nid, nd in cluster.nodes.items()
                 for cid, rec in list(nd.runtime.tasks.items())
                 if cid in cids and rec.status is TaskStatus.RUNNING}
        if len(where) == len(cids):
            return where
        time.sleep(0.05)
    raise SmokeError(f"tasks {cids} not RUNNING after {TIMEOUT_S} s")


def wait_completed(cluster, router, rids, until=None) -> None:
    deadline = time.time() + TIMEOUT_S
    while time.time() < deadline:
        _raise_if_failed(cluster)
        if until is not None and until():
            return
        if until is None and all(r in router.completed for r in rids):
            return
        time.sleep(0.002)
    missing = sorted(r for r in rids if r not in router.completed)
    raise SmokeError(f"requests not completed after {TIMEOUT_S} s: "
                     f"{missing}")


def serve(cluster, router, reqs) -> dict:
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(r)
    wait_completed(cluster, router, [r.rid for r in reqs])
    wall = time.perf_counter() - t0
    done = {r.rid: router.completed[r.rid] for r in reqs}
    ttft = sorted(done[r.rid].ttft_s for r in reqs)
    for r in reqs:
        check(len(done[r.rid].tokens) == r.max_new_tokens,
              f"{r.rid} served {len(done[r.rid].tokens)} tokens, "
              f"asked {r.max_new_tokens}")
    log("served", requests=len(reqs),
        tokens=sum(len(c.tokens) for c in done.values()),
        wall_s=wall, ttft_s_min=ttft[0],
        ttft_s_median=ttft[len(ttft) // 2], ttft_s_max=ttft[-1])
    return {rid: list(c.tokens) for rid, c in done.items()}


def buffer_devices(cluster, node: str, cid: str) -> set:
    import jax

    table = cluster.nodes[node].runtime.tasks[cid].monitor.buffers
    devs = set()
    for bid in table.ids():
        # sharding, not devices(): the running engine may have just
        # donated the array read here
        for leaf in jax.tree.leaves(table.get(bid).device_value):
            devs |= leaf.sharding.device_set
    return devs


def move_mid_generation(cluster, router, cid, src: str, dst: str,
                        reqs: list, served: dict) -> None:
    """Serve ``reqs`` again on the replica ``cid`` and, once every one of
    them is in a lane with tokens committed, evict it from ``src`` and
    resume it on ``dst`` (the same node, or a migration).  The streams must
    equal ``served`` (the first service of the same prompts)."""
    from repro.core import TaskStatus

    rerun = again(reqs)
    for r in rerun:
        router.submit(r)
    wait_completed(cluster, router, [r.rid for r in rerun],
                   until=lambda: all(len(r.committed or ()) >= 2
                                     for r in rerun))
    cluster.agent(src).evict(cid)
    rec = _records(cluster, cid)[0]
    check(rec.status is TaskStatus.EVICTED, f"{cid} is {rec.status} "
          "after evict")
    mid = [r.rid for r in rerun
           if 0 < len(r.committed) < r.max_new_tokens]
    check(mid, "no request was mid-generation at the eviction")
    evict = [kw for _, ev, kw in rec.timeline if ev == "evict"][-1]
    if dst == src:
        cluster.agent(src).resume(cid)
    else:
        cluster.agent(dst).migrate_in(cid, SERVICE, source_node=src)
    rec = _records(cluster, cid)[0]
    resume = [kw for _, ev, kw in rec.timeline if ev == "resume"][-1]
    log("moved", src=src, dst=dst, mid_generation=len(mid),
        evict_s=evict["total_seconds"], saved_bytes=evict["saved_bytes"],
        resume_s=resume["total_seconds"],
        restored_bytes=resume["restored_bytes"])
    wait_completed(cluster, router, [r.rid for r in rerun])
    for orig, r in zip(reqs, rerun):
        got = list(router.completed[r.rid].tokens)
        check(got == served[orig.rid],
              f"{r.rid} after the move diverges from {orig.rid}: "
              f"{got[:8]}... vs {served[orig.rid][:8]}...")
    log("move_bit_exact", streams=len(rerun))


def finish(cluster, router, cid) -> None:
    """Close the router and wait for the replica to end DONE."""
    from repro.core import TaskStatus

    router.close()
    rec = _records(cluster, cid)[0]
    deadline = time.time() + TIMEOUT_S
    while time.time() < deadline and rec.status not in (
            TaskStatus.DONE, TaskStatus.FAILED, TaskStatus.REMOVED):
        time.sleep(0.01)
    check(rec.status is TaskStatus.DONE,
          f"{cid} ended {rec.status}: {rec.error!r}")


def report_compiles(cluster) -> None:
    for nid, nd in cluster.nodes.items():
        st = nd.runtime.programs.stats
        log("compile_seconds", node=nid, total=st["compile_seconds"],
            misses=st["misses"], hits=st["hits"],
            per_program=st["per_program"])


# ---------------------------------------------------------------------------
# dense reference: plain greedy decode of the same parameters
# ---------------------------------------------------------------------------
def _bf16_ulp(x: float) -> float:
    return float(2.0 ** (np.floor(np.log2(abs(x) + 1e-30)) - 7))


def dense_reference(sm: Smoke, programs, dev, reqs: list,
                    served: dict) -> dict:
    """Compare served streams with ``serve_step.generate`` on ``dev``, with
    the parameters made by the replica's own compiled ``init_params`` (from
    the node's program cache, so nothing is compiled again).  A stream that
    differs is fed to the dense model token by token: at every position the
    served token's logit must be within the tolerance of the best one."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serve.serve_step import (generate, make_decode_step,
                                        make_prefill_step)

    init = programs.get_or_compile("init_params", (sm.seed,), (), dev)
    params = init.compiled(sm.seed)
    bundle = build_model(get_arch(sm.arch), cache_margin=sm.max_new)
    prefill = jax.jit(make_prefill_step(bundle))
    decode = jax.jit(make_decode_step(bundle))
    out = {"exact": 0, "near_tie": 0}
    for r in reqs:
        n = len(r.prompt)
        bucket = min(b for b in sm.buckets if b >= n)
        # the engine right-pads each prompt to its bucket with token 0
        padded = jax.device_put(np.pad(r.prompt, (0, bucket - n))[None], dev)
        ref = np.asarray(generate(bundle, params, {"tokens": padded},
                                  sm.max_new))[0].tolist()
        got = served[r.rid]
        if got == ref:
            out["exact"] += 1
            continue
        d = next(i for i in range(len(ref)) if got[i] != ref[i])
        logits, caches = prefill(params, {"tokens": padded})
        for i, tok in enumerate(got):
            row = np.asarray(logits[0], np.float32)
            top2 = np.sort(row)[-2:]
            deficit = float(top2[1] - row[tok])
            tol = GAP_TOL_ULPS * _bf16_ulp(top2[1])
            if i == d:
                log("reference_divergence", rid=r.rid, position=d,
                    served=tok, reference=ref[d],
                    top2_gap=float(top2[1] - top2[0]), tolerance=tol)
            check(deficit <= tol, f"{r.rid} token {i} ({tok}) is "
                  f"{deficit} below the dense model's best logit "
                  f"(tolerance {tol}); first divergence at {d}")
            logits, caches = decode(params, jnp.asarray([tok], jnp.int32),
                                    jnp.int32(bucket + i), caches)
        out["near_tie"] += 1
    log("dense_reference", checked=len(reqs), **out)
    return out


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------
def one_chip(sm: Smoke, dev, mem_cap: int) -> None:
    from repro.configs import get_arch
    from repro.core import make_cluster
    from repro.scaling.serving import reset_router

    cluster = make_cluster(num_nodes=1, slices_per_node=1,
                           images={SERVICE: image(sm)},
                           mem_cap_bytes=mem_cap)
    router = reset_router(SERVICE)
    orch = cluster.orchestrator
    orch.start()
    try:
        t0 = time.perf_counter()
        cid = orch.submit(SERVICE)
        node = wait_running(cluster, [cid])[cid]
        log("replica_up", cid=cid, node=node,
            setup_s=time.perf_counter() - t0)
        check(buffer_devices(cluster, node, cid) == {dev},
              "replica buffers are not on the chip")
        reqs = make_requests(sm, get_arch(sm.arch).vocab_size)
        served = serve(cluster, router, reqs)
        move_mid_generation(cluster, router, cid, node, node,
                            reqs[:sm.repeats], served)
        finish(cluster, router, cid)
        deadline = time.time() + 30
        while orch.deployments[cid].status != "done" and \
                time.time() < deadline:
            time.sleep(0.02)
        check(orch.deployments[cid].status == "done",
              f"orchestrator sees {cid} as {orch.deployments[cid].status}")
        log("task_done", cid=cid)
        report_compiles(cluster)
    finally:
        cluster.stop()
    dense_reference(sm, cluster.nodes[node].runtime.programs, dev,
                    reqs[:sm.reference_checks], served)


def four_chips(sm: Smoke, devs, mem_cap: int) -> None:
    from repro.configs import get_arch
    from repro.core import make_cluster
    from repro.scaling.serving import reset_router

    cluster = make_cluster(num_nodes=4, slices_per_node=1,
                           images={SERVICE: image(sm)},
                           mem_cap_bytes=mem_cap)
    router = reset_router(SERVICE)
    orch = cluster.orchestrator
    orch.start()
    try:
        t0 = time.perf_counter()
        cids = [orch.submit(SERVICE, group=SERVICE) for _ in range(4)]
        where = wait_running(cluster, cids)
        log("replicas_up", where=where, setup_s=time.perf_counter() - t0)
        check(sorted(where.values()) == [f"node{i}" for i in range(4)],
              f"replicas are not one per node: {where}")
        for cid, node in where.items():
            own = devs[int(node[len("node"):])]
            got = buffer_devices(cluster, node, cid)
            check(got == {own}, f"{cid} on {node} has buffers on {got}, "
                  f"not only on {own}")
        log("placement", **{cid: str(devs[int(n[4:])])
                            for cid, n in where.items()})
        reqs = make_requests(sm, get_arch(sm.arch).vocab_size)
        served = serve(cluster, router, reqs)
        admits = [e[2]["engine"] for e in cluster.metrics.flight_record()[
            "events"] if e[1] == "engine_admit"]
        log("admissions_per_replica",
            **{cid: admits.count(cid) for cid in cids})
        # only the node-0 replica stays, so it serves the repeats; node 1
        # is then free to take it
        cid0 = next(c for c, n in where.items() if n == "node0")
        for cid in cids:
            if cid != cid0:
                orch.scale_in(cid)
        move_mid_generation(cluster, router, cid0, "node0", "node1",
                            reqs[:sm.repeats], served)
        check(buffer_devices(cluster, "node1", cid0) == {devs[1]},
              "migrated replica's buffers are not on chip 1")
        log("migrated_buffers_on", device=str(devs[1]))
        finish(cluster, router, cid0)
        log("task_done", cid=cid0, node="node1")
        report_compiles(cluster)
    finally:
        cluster.stop()
    dense_reference(sm, cluster.nodes["node0"].runtime.programs, devs[0],
                    reqs, served)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="one node per chip of a four-chip host")
    args = ap.parse_args()
    n = 4 if args.four_chips else 1

    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"needs a TPU; JAX found {devs[0].platform}")
    check(len(devs) >= n, f"needs {n} chips; JAX found {len(devs)}")
    devs = devs[:n]
    limits = [bytes_limit(d) for d in devs]
    log("device", kind=devs[0].device_kind, count=len(jax.devices()),
        bytes_limit=limits)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log("compile_cache", dir=enable_compile_cache())
    sm = Smoke()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(sm, devs, min(limits))
    else:
        check_kernels()
        one_chip(sm, devs[0], limits[0])
    log("peak_bytes_in_use",
        **{str(d): d.memory_stats()["peak_bytes_in_use"] for d in devs})
    log("wall_seconds", total=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception:  # noqa: BLE001 - any failed phase fails the run
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # engine worker threads may still hold XLA state; skip interpreter
    # teardown so a clean run cannot abort on exit
    os._exit(rc)
