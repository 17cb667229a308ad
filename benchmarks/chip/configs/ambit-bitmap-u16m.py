"""Plain reference for the bitmap-index cells.

Independent of the program: the query "users active every week of the past
w weeks" (and "... who are male") evaluated with numpy on the packed
uint32 planes, and its two counts.
"""
from __future__ import annotations

import numpy as np


def week_days(end_day: int, weeks: int) -> list[int]:
    """Day indices of the ``weeks`` weeks that end on ``end_day``."""
    first = end_day - 7 * weeks + 1
    if first < 0:
        raise ValueError(f"{weeks} weeks cannot end on day {end_day}")
    return list(range(first, end_day + 1))


def query(days: list[np.ndarray], gender: np.ndarray
          ) -> tuple[np.ndarray, np.ndarray, int, int]:
    """-> (active plane, male plane, active count, male count).

    ``days`` holds ``7 w`` daily planes, oldest first: a user is active in
    a week if active on any of its days, and counted if active in every
    week."""
    weeks = [np.bitwise_or.reduce(np.stack(days[i:i + 7]), axis=0)
             for i in range(0, len(days), 7)]
    active = np.bitwise_and.reduce(np.stack(weeks), axis=0)
    male = active & gender
    return active, male, popcount(active), popcount(male)


def popcount(plane: np.ndarray) -> int:
    return int(np.unpackbits(np.ascontiguousarray(plane).view(np.uint8))
               .sum(dtype=np.int64))
