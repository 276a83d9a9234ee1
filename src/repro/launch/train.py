"""End-to-end training driver — runs a real (reduced-size) model for a few
hundred steps on this host, *under the Funky runtime*: the training loop is a
guest task whose every device interaction flows through the monitor
(MEMORY/TRANSFER/EXECUTE/SYNC), so it is preemptible and checkpointable.

    PYTHONPATH=src python -m repro.launch.train \
        --arch yi-9b-smoke --steps 200 --batch 8 --seq 64 --chunks 4

Use ``--native`` to bypass the Funky layer (same jitted step functions,
direct dispatch) — the pair is the Fig 4 virtualization-overhead experiment.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_arch
from repro.core import TaskImage, TaskStatus, make_cluster
from repro.launch.compile_cache import enable_compile_cache
from repro.train import (DataConfig, OptConfig, make_batch, make_train_state,
                         make_train_step)


def run_native(args) -> dict:
    from repro.configs.base import ShapeConfig
    from repro.models import build_model

    cfg = get_arch(args.arch)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    bundle = build_model(cfg)
    oc = OptConfig(warmup_steps=10, decay_steps=max(args.steps, 20))
    params, opt = make_train_state(bundle, oc, jax.random.PRNGKey(args.seed))
    step = jax.jit(make_train_step(bundle, oc, num_microbatches=args.chunks))
    t0 = time.perf_counter()
    losses = []
    for i in range(args.steps):
        batch = make_batch(cfg, shape, i, DataConfig(seed=args.seed))
        params, opt, m = step(params, opt, batch)
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            losses.append(float(m["loss"]))
            print(f"step {i:5d} loss {losses[-1]:.4f} "
                  f"lr {float(m['lr']):.2e}", flush=True)
    dt = time.perf_counter() - t0
    return {"seconds": dt, "loss_first": losses[0], "loss_last": losses[-1]}


def run_funky(args) -> dict:
    image = TaskImage(
        name="cli-train", kind="train", arch=args.arch, seq_len=args.seq,
        global_batch=args.batch, total_steps=args.steps, chunks=args.chunks,
        seed=args.seed,
        opt=OptConfig(warmup_steps=10, decay_steps=max(args.steps, 20)))
    cluster = make_cluster(num_nodes=1, slices_per_node=1,
                           images={"cli-train": image})
    rt = cluster.nodes["node0"].runtime
    t0 = time.perf_counter()
    rt.create("train0", image)
    rt.start("train0")
    status = rt.wait("train0", timeout=36000)
    dt = time.perf_counter() - t0
    rec = rt.tasks["train0"]
    if status is not TaskStatus.DONE:
        raise SystemExit(f"task ended {status}: {rec.error}")
    mon = rec.monitor
    print(f"done: {rec.guest_state.step} steps in {dt:.1f}s | "
          f"final_loss={rec.guest_state.user.get('final_loss'):.4f} | "
          f"requests: EXECUTE={int(mon.metrics['n_EXECUTE'])} "
          f"TRANSFER={int(mon.metrics['n_TRANSFER'])} "
          f"reconfig={mon.metrics['reconfig_seconds']:.1f}s")
    return {"seconds": dt,
            "final_loss": rec.guest_state.user.get("final_loss")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b-smoke")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--native", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    out = run_native(args) if args.native else run_funky(args)
    print(out)


if __name__ == "__main__":
    main()
