"""``tpot_p50_ms`` again, under a bound of its own: the cells that list it
are those whose TPOT median holds within a few percent from run to run,
so that a bound set from the noisiest cell's spread does not hide a loss
in theirs."""
from benchmarks.chip.readers import pct, tpots


def read(rec):
    p = pct(tpots(rec), 50)
    return None if p is None else 1e3 * p
