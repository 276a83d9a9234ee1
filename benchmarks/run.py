"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig09 fig13  # subset
"""

from __future__ import annotations

import importlib
import os
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "fig04_virt_overhead",
    "fig05_api_times",
    "fig06_setup",
    "table4_portability",
    "fig07_evict_resume",
    "fig08_migrate_ckpt",
    "fig09_sync_split",
    "fig10_preemption",
    "fig11_scalability",
    "fig12_fault_tolerance",
    "fig13_sched_policies",
    "fig14_autoscale",
    "fig15_serving",
]


def main() -> None:
    wanted = sys.argv[1:]
    enable_compile_cache()
    mods = [m for m in MODULES
            if not wanted or any(w in m for w in wanted)]
    print("name,us_per_call,derived")
    failures = []
    for name in mods:
        t0 = time.perf_counter()
        print(f"# --- {name} ---", flush=True)
        try:
            importlib.import_module(f"benchmarks.{name}").main()
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            traceback.print_exc()
        print(f"# {name} took {time.perf_counter() - t0:.1f}s", flush=True)
    if failures:
        for name, e in failures:
            print(f"# FAILED {name}: {e}")
        raise SystemExit(1)
    sys.stdout.flush()
    # live-cluster benchmarks (fig10/12/14) leave XLA worker threads from
    # killed guest tasks behind; they can abort CPython teardown, so
    # hard-exit once every row is emitted.
    os._exit(0)


if __name__ == '__main__':
    main()
