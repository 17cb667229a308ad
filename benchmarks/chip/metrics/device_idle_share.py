"""Share of the traced window in which no program ran on the device [%].

Nothing to read when no program ran on the device in the window."""
from __future__ import annotations


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
