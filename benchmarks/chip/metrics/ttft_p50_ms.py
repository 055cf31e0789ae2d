"""Median time to first token, in milliseconds, over every request that
arrived in the window, from when it was due."""
from benchmarks.chip.readers import pct, ttfts


def read(rec):
    p = pct(ttfts(rec), 50)
    return None if p is None else 1e3 * p
