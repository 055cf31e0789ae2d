"""Median, in milliseconds, over the window's arrivals, of how long the
first token took from the engine's stream buffer to the client: the
client's first token minus the program's first_token_emit_time (the
engine's poll, the gateway's pump and the stream).  None where the
program does not stamp the hand-off."""
from benchmarks.chip.readers import pct


def read(rec):
    lags = []
    for r in rec.measured:
        emit = getattr(r.request, "first_token_emit_time", None)
        if emit is not None and r.first is not None:
            lags.append(r.first - emit)
    p = pct(lags, 50)
    return None if p is None else 1e3 * p
