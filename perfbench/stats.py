"""Rate arithmetic, kept with the benchmark so that the yardstick does
not move when the program's own metrics code does."""
from __future__ import annotations


def rate(work: float, seconds: float) -> float:
    """All the work of a window over all of its time."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return float(work) / float(seconds)
