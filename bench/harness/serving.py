"""The system under test, deployed as a user would run it.

Replicas are ``engine-serve`` tasks submitted to the orchestrator, which
places them through node agent -> CRI -> runtime -> ``EngineServeTask`` ->
``ContinuousBatchingEngine`` -> monitor, one node per chip.  Clients reach
them only through the service's ``RequestRouter``.  A move is the
orchestrator's evict command to the replica's node agent, then a resume
there or a migration into another node.
"""

from __future__ import annotations

import time

SERVICE = "bench-svc"
TIMEOUT_S = 900.0


class SystemFailed(RuntimeError):
    pass


def image(arch: str, mix: dict, seed: int):
    from repro.core import TaskImage

    eng = mix["engine"]
    return TaskImage(name=SERVICE, kind="engine-serve", arch=arch,
                     global_batch=eng["slots"],
                     prompt_len=max(eng["prompt_buckets"]),
                     prompt_buckets=tuple(eng["prompt_buckets"]),
                     max_new_tokens=eng["max_new_tokens"],
                     page_size=eng["page_size"], paged_kv=True,
                     total_steps=10 ** 9, seed=seed)


class Service:
    def __init__(self, arch: str, mix: dict, seed: int, mem_cap: int):
        from repro.core import make_cluster
        from repro.scaling.serving import reset_router

        self.mix = mix
        self.cluster = make_cluster(
            num_nodes=mix.get("nodes", 1), slices_per_node=1,
            images={SERVICE: image(arch, mix, seed)}, mem_cap_bytes=mem_cap)
        self.router = reset_router(SERVICE)
        self.orch = self.cluster.orchestrator
        self.cids: list = []
        self.home: dict = {}

    # -- life cycle -----------------------------------------------------
    def up(self) -> None:
        self.orch.start()
        n = self.mix.get("replicas", 1)
        self.cids = [self.orch.submit(SERVICE, group=SERVICE)
                     for _ in range(n)]
        deadline = time.time() + TIMEOUT_S
        while True:
            self.check()
            where = self.where()
            if len(where) == n and all(
                    self.record(c).status.value == "running"
                    for c in self.cids):
                self.home = dict(where)
                return
            if time.time() > deadline:
                raise SystemFailed(f"replicas not running after {TIMEOUT_S} s")
            time.sleep(0.05)

    def down(self) -> None:
        """Stop the orchestrator and remove every replica, freeing its
        device memory; the router is closed first so nothing is requeued."""
        self.router.close()
        self.orch.stop()
        for cid, node in self.where().items():
            self.cluster.agent(node).remove(cid)

    # -- introspection --------------------------------------------------
    def where(self) -> dict:
        return {cid: nid for nid, nd in self.cluster.nodes.items()
                for cid in list(nd.runtime.tasks) if cid in self.cids}

    def record(self, cid: str):
        for nd in self.cluster.nodes.values():
            rec = nd.runtime.tasks.get(cid)
            if rec is not None:
                return rec
        raise SystemFailed(f"replica {cid} is on no node")

    def engine(self, cid: str):
        return self.record(cid).task._engine

    def check(self) -> None:
        for nd in self.cluster.nodes.values():
            for cid, rec in list(nd.runtime.tasks.items()):
                if rec.status.value == "failed":
                    raise SystemFailed(f"replica {cid} on {nd.node_id} "
                                       f"failed: {rec.error!r}") from rec.error

    def devices(self) -> list:
        """The chips the replicas' nodes own."""
        return sorted({nd.allocator.slices[0].device
                       for nd in self.cluster.nodes.values()},
                      key=lambda d: d.id)

    def evicts(self) -> list:
        """(host time, stats) of every eviction of every replica."""
        out = []
        for cid in self.cids:
            for t, ev, kw in self.record(cid).timeline:
                if ev == "evict":
                    out.append((t, kw))
        return out

    def engine_of(self) -> dict:
        """rid -> replica id, from the engines' admission events."""
        return {e[2]["rid"]: e[2]["engine"]
                for e in self.cluster.metrics.flight_record(1)["events"]
                if e[1] == "engine_admit"}

    # -- moves ------------------------------------------------------------
    def target(self, cid: str, to: str) -> str:
        src = self.where()[cid]
        if to == "same":
            return src
        if to == "home":
            return self.home[cid]
        if to == "free":
            used = set(self.where().values())
            free = [n for n in sorted(self.cluster.nodes) if n not in used]
            if not free:
                raise SystemFailed("no free node to migrate to")
            return free[0]
        return to

    def move(self, cid: str, to: str, annotate) -> tuple:
        """Evict ``cid`` and resume it on node ``to``; returns the host
        times of the command and of the resume's return."""
        src = self.where()[cid]
        dst = self.target(cid, to)
        t_cmd = time.perf_counter()
        with annotate("bench.move.evict"):
            self.cluster.agent(src).evict(cid)
        with annotate("bench.move.resume"):
            if dst == src:
                self.cluster.agent(src).resume(cid)
            else:
                self.cluster.agent(dst).migrate_in(cid, SERVICE,
                                                   source_node=src)
        return t_cmd, time.perf_counter(), src, dst
