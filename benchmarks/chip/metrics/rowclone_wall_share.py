"""Share of the window's host time spent inside RowClone [%].

The harness's ``rowclone`` span wraps every call of
``isa.PudIsa.clone_word``: the resident executor's in-bank copies (a
host gather of the trial-batched row state and a flip draw each).
Nothing to read when no RowClone ran in the window.
"""
from __future__ import annotations


def read(r):
    s = r.span_s.get("rowclone", 0.0)
    if s <= 0 or r.window_s <= 0:
        return None
    return 100.0 * s / r.window_s
