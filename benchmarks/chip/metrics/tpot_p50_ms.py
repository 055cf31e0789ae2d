"""Median, in milliseconds, of each window arrival's time per output
token: (finish - first token) / (tokens - 1)."""
from benchmarks.chip.readers import pct, tpots


def read(rec):
    p = pct(tpots(rec), 50)
    return None if p is None else 1e3 * p
