"""Serving driver: batched greedy decoding under the Funky runtime.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen3-8b-smoke --batch 4 --prompt-len 16 --tokens 64
"""

from __future__ import annotations

import argparse
import time

from repro.core import TaskImage, TaskStatus, make_cluster
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--tokens-per-step", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    steps = max(args.tokens // args.tokens_per_step, 1)
    image = TaskImage(
        name="cli-serve", kind="serve", arch=args.arch,
        global_batch=args.batch, prompt_len=args.prompt_len,
        total_steps=steps, tokens_per_step=args.tokens_per_step)
    cluster = make_cluster(num_nodes=1, slices_per_node=1,
                           images={"cli-serve": image})
    rt = cluster.nodes["node0"].runtime
    t0 = time.perf_counter()
    rt.create("serve0", image)
    rt.start("serve0")
    status = rt.wait("serve0", timeout=36000)
    dt = time.perf_counter() - t0
    rec = rt.tasks["serve0"]
    if status is not TaskStatus.DONE:
        raise SystemExit(f"task ended {status}: {rec.error}")
    n_tok = steps * args.tokens_per_step * args.batch
    print(f"decoded {n_tok} tokens in {dt:.1f}s "
          f"({n_tok / dt:.1f} tok/s) | last tokens: "
          f"{rec.guest_state.user.get('last_token')}")


if __name__ == "__main__":
    main()
