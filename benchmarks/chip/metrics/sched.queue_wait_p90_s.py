"""90th percentile, in seconds, of the wait of each window arrival from
when it was due to the scheduler's first plan that included it (the
program's first_scheduled_time)."""
from benchmarks.chip.readers import pct


def read(rec):
    return pct([r.request.first_scheduled_time - r.due for r in rec.measured
                if r.request.first_scheduled_time is not None], 90)
