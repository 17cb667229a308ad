"""Share of the window's host time spent planning resident execution [%].

The harness's ``plan`` span wraps every call of
``compiler.schedule_resident``: the polarity/residency search, or a
replan with frozen decisions where the search result is cached.
Nothing to read when no plan was made in the window.
"""
from __future__ import annotations


def read(r):
    s = r.span_s.get("plan", 0.0)
    if s <= 0 or r.window_s <= 0:
        return None
    return 100.0 * s / r.window_s
