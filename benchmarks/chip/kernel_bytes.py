"""Bytes each measured kernel must move, from the shapes at its interface.

These are the useful bytes of one call as the caller hands it over:
padding, layout copies and the kernel's own intermediate stores are not
counted, so a kernel's roofline share reads the same work whatever
implements it.  A roofline share is these bytes over the chip's peak
bandwidth, divided by the kernel's device time.
"""
from __future__ import annotations

import math

F32 = 4
U32 = 4
BOOL = 1


def senseamp_resolve_trials(com_shape, ref_shape, static_shape) -> int:
    """``kernels.ops.senseamp_resolve_trials`` at its interface.

    Reads the compute slab ``(T, N_com, W)`` and reference slab
    ``(T, N_ref, W)`` (f32), the static offsets (``(W,)`` or ``(T, W)``
    f32), the normals ``(T, W)`` and uniforms ``(2, T, W)`` (f32); writes
    the resolved ``(T, W)`` bool plane.
    """
    t, _, w = com_shape
    reads = (math.prod(com_shape) + math.prod(ref_shape)
             + math.prod(static_shape) + 3 * t * w) * F32
    return reads + t * w * BOOL


def nary_bitwise(planes_shape) -> int:
    """``kernels.ops.nary_bitwise`` at the caller's ``(N, R, C)`` uint32
    shape: N planes read, one ``(R, C)`` plane written."""
    n, r, c = planes_shape
    return (n + 1) * r * c * U32
