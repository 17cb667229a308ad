"""Sense-amp resolve's share of its HBM roofline [%].

Bytes at the ``senseamp_resolve_trials`` interface (``kernel_bytes``) over
the chip's peak bandwidth, divided by the device time of the jitted entry
(``jit_senseamp_resolve_trials``) in the traced window.  Bandwidth bounds
it: the comparator does a few operations per byte.
"""
from __future__ import annotations

KERNEL = "senseamp_resolve_trials"


def read(r):
    t = r.trace.program_s.get(f"jit_{KERNEL}", 0.0) if r.trace else 0.0
    nbytes = r.kernel_bytes.get(KERNEL, 0)
    if t <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / r.peaks["hbm_bytes_per_s"] / t
