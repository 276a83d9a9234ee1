"""Jit'd public wrapper for the SSD chunk-scan kernel."""

from __future__ import annotations

from repro.kernels.ssd_scan.kernel import ssd_scan_fwd


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool = False):
    return ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
