"""Backend compiles (persistent-cache loads included) inside the window."""
from __future__ import annotations


def read(r):
    return r.compiles_in_window
