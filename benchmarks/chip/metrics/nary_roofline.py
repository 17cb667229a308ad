"""N-ary bitwise kernel's share of its HBM roofline [%].

Useful bytes ``(N + 1) R C 4`` at the caller's ``(N, R, C)`` shape
(``kernel_bytes``) over the chip's peak bandwidth, divided by the device
time of the jitted entry (``jit_nary_bitwise``, padding included) in the
traced window.  Bandwidth bounds it: one operation per word read.
"""
from __future__ import annotations

KERNEL = "nary_bitwise"


def read(r):
    t = r.trace.program_s.get(f"jit_{KERNEL}", 0.0) if r.trace else 0.0
    nbytes = r.kernel_bytes.get(KERNEL, 0)
    if t <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / r.peaks["hbm_bytes_per_s"] / t
