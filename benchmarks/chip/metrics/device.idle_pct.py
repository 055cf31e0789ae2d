"""Share of the traced window in which no op ran on the chip."""
from benchmarks.chip.readers import idle_pct


def read(rec):
    return idle_pct(rec)
