"""Jit'd public wrapper for the RG-LRU scan kernel."""

from __future__ import annotations

from repro.kernels.rglru_scan.kernel import rglru_scan_fwd


def rglru_scan(a, b, *, bs: int = 256, bw: int = 512,
               interpret: bool = False):
    return rglru_scan_fwd(a, b, bs=bs, bw=bw, interpret=interpret)
