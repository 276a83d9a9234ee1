"""Program ("bitstream") registry and compile cache.

An FPGA bitstream maps to an AOT-compiled XLA executable.  ``vfpga_init``'s
bitstream transfer + reconfiguration (≈3.5 s on the Vitis XDMA shell) maps to
``jit(fn).lower(specs).compile()`` — slow the first time, free on a cache hit
(a *warm* vSlice, the paper's "keep it warmed up" motivation §1).

Keyed by (program name, abstract arg tree structure, device); stats feed
Fig 6.  Every program compiles for one device — the vSlice's — including
programs with no buffer inputs (``init_params(seed)``), whose outputs are
pinned there.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
from jax.sharding import SingleDeviceSharding


@dataclass
class Program:
    program_id: str
    fn: Callable
    static_argnums: tuple = ()
    # how EXECUTE maps buffers: fn(*in_buffs_values, *const_args) -> outputs
    # matched positionally with out_buffs.


@dataclass
class CompiledEntry:
    compiled: Any
    compile_seconds: float
    arg_fingerprint: str


def _fingerprint(tree: Any) -> str:
    leaves = jax.tree.leaves(tree)
    parts = [f"{getattr(l, 'shape', ())}:{getattr(l, 'dtype', type(l).__name__)}"
             for l in leaves]
    return "|".join(parts)


def _on_device(tree: Any, sharding: SingleDeviceSharding) -> Any:
    """Array leaves become ShapeDtypeStructs placed on ``sharding``; Python
    scalars stay weakly-typed constants, moved to the device at call time."""
    return jax.tree.map(
        lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
                   if hasattr(x, "shape") else x), tree)


class ProgramCache:
    def __init__(self):
        self._programs: Dict[str, Program] = {}
        self._compiled: Dict[tuple, CompiledEntry] = {}
        self._lock = threading.Lock()
        # per_program: program id -> seconds summed over its compiles
        self.stats = {"hits": 0, "misses": 0, "compile_seconds": 0.0,
                      "per_program": {}}

    def register(self, program: Program):
        with self._lock:
            self._programs[program.program_id] = program

    def __contains__(self, program_id: str) -> bool:
        return program_id in self._programs

    def get_program(self, program_id: str) -> Program:
        return self._programs[program_id]

    def get_or_compile(self, program_id: str, abstract_args: tuple,
                       donate_argnums: tuple, device) -> CompiledEntry:
        """AOT-compile fn for the given abstract args on ``device`` (cache on
        fingerprint and device)."""
        prog = self._programs[program_id]
        fp = _fingerprint(abstract_args)
        key = (program_id, fp, donate_argnums, device.id)
        with self._lock:
            hit = self._compiled.get(key)
            if hit is not None:
                self.stats["hits"] += 1
                return hit
        t0 = time.perf_counter()
        sharding = SingleDeviceSharding(device)
        jitted = jax.jit(prog.fn, donate_argnums=donate_argnums,
                         out_shardings=sharding)
        compiled = jitted.lower(*_on_device(abstract_args, sharding)).compile()
        dt = time.perf_counter() - t0
        entry = CompiledEntry(compiled=compiled, compile_seconds=dt,
                              arg_fingerprint=fp)
        with self._lock:
            self._compiled[key] = entry
            self.stats["misses"] += 1
            self.stats["compile_seconds"] += dt
            per = self.stats["per_program"]
            per[program_id] = per.get(program_id, 0.0) + dt
        return entry

    def program_ids(self):
        return tuple(self._programs)
