"""Node *i* of a cluster owns device *i*: with four devices (four virtual CPU
devices, in a child process so the flag takes effect before JAX starts)
each node's engine buffers land on its own device, and evicting a replica
on node0 mid-generation and migrating it to node1 moves its buffers to
node1's device with every stream bit-exact."""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

CHILD = textwrap.dedent("""
    import os, time
    import jax
    import numpy as np
    from repro.core import TaskImage, TaskStatus, make_cluster
    from repro.scaling.serving import reset_router
    from repro.serve.engine import ServeRequest

    def image(name):
        return TaskImage(name=name, kind="engine-serve",
                         arch="stablelm-3b-smoke", global_batch=2,
                         prompt_len=8, max_new_tokens=12, page_size=4,
                         total_steps=10 ** 9)

    def devices_of(rt, cid):
        # sharding, not devices(): a running engine may have just donated
        # the array read here
        table = rt.tasks[cid].monitor.buffers
        return {d for b in table.ids()
                for leaf in jax.tree.leaves(table.get(b).device_value)
                for d in leaf.sharding.device_set}

    def wait(cond, what):
        deadline = time.time() + 300
        while not cond():
            assert time.time() < deadline, what
            time.sleep(0.002)

    devs = jax.devices()
    assert len(devs) == 4, devs
    names = [f"svc{i}" for i in range(4)]
    cluster = make_cluster(num_nodes=4, slices_per_node=2,
                           images={n: image(n) for n in names})
    rts = [cluster.nodes[f"node{i}"].runtime for i in range(4)]
    routers = {n: reset_router(n) for n in names}
    for i, n in enumerate(names):
        cluster.agent(f"node{i}").deploy(f"t{i}", n)
    for i, rt in enumerate(rts):
        wait(lambda: rt.status(f"t{i}") is TaskStatus.RUNNING, "setup")
        assert devices_of(rt, f"t{i}") == {devs[i]}, (i, devices_of(rt, f"t{i}"))

    rng = np.random.Generator(np.random.Philox(3))
    prompts = [rng.integers(0, 100, 8).astype(np.int32) for _ in range(2)]
    moved = [ServeRequest(rid=f"m{j}", prompt=p, max_new_tokens=12)
             for j, p in enumerate(prompts)]
    base = [ServeRequest(rid=f"b{j}", prompt=p, max_new_tokens=12)
            for j, p in enumerate(prompts)]
    for r in base:
        routers["svc1"].submit(r)
    for r in moved:
        routers["svc0"].submit(r)
    wait(lambda: all(len(r.committed or ()) >= 2 for r in moved), "admit")
    cluster.agent("node0").evict("t0")
    assert any(len(r.committed) < 12 for r in moved), "not mid-generation"
    cluster.agent("node1").migrate_in("t0", "svc0", source_node="node0")
    assert "t0" not in rts[0].tasks
    assert devices_of(rts[1], "t0") == {devs[1]}, devices_of(rts[1], "t0")
    for n, reqs in (("svc0", moved), ("svc1", base)):
        wait(lambda: all(r.rid in routers[n].completed for r in reqs), n)
    for m, b in zip(moved, base):
        assert (routers["svc0"].completed[m.rid].tokens
                == routers["svc1"].completed[b.rid].tokens), m.rid
    for n in names:
        routers[n].close()
    for cid, rt in [("t0", rts[1]), ("t1", rts[1]), ("t2", rts[2]),
                    ("t3", rts[3])]:
        assert rt.wait(cid, timeout=120) is TaskStatus.DONE, cid
    print("OK", flush=True)
    os._exit(0)
""")


def test_nodes_own_devices_and_migration_moves_buffers():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [SRC] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("OK"), out.stdout[-2000:]
