"""Share of the window's host time spent inside the resolve entry [%].

The harness's ``resolve`` span wraps every call of
``kernels.ops.senseamp_resolve_trials`` from the simulator and ends when
the result is on the host: the host-to-device copy of the slabs, the
kernel and the copy back.  The rest of the window is the host simulator
episode.
"""
from __future__ import annotations


def read(r):
    s = r.span_s.get("resolve", 0.0)
    if s <= 0 or r.window_s <= 0:
        return None
    return 100.0 * s / r.window_s
