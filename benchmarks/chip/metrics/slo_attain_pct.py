"""Share of the window's arrivals that met both the TTFT and the TPOT
limit of the configuration; failed, shed and unfinished requests miss."""
from benchmarks.chip.readers import met_slo


def read(rec):
    m = rec.measured
    return 100.0 * sum(met_slo(rec, r) for r in m) / len(m) if m else None
