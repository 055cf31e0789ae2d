"""99th percentile, in milliseconds, of how late the load generator
handed each window arrival to Gateway.submit after it was due."""
from benchmarks.chip.readers import pct


def read(rec):
    p = pct([r.submit - r.due for r in rec.measured if r.submit is not None],
            99)
    return None if p is None else 1e3 * p
