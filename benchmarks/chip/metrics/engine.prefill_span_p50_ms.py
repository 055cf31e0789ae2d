"""Median, in milliseconds, over the window's arrivals, of the program's
stretch from the first plan that included a request to the engine handing
its first token to the stream buffer (the program's first_token_emit_time
minus its first_scheduled_time, one clock): chunked prefill across
iterations and the first token's sampling.  None where the program does
not stamp the hand-off."""
from benchmarks.chip.readers import pct


def read(rec):
    spans = []
    for r in rec.measured:
        q = r.request
        emit = getattr(q, "first_token_emit_time", None)
        if emit is not None and q.first_scheduled_time is not None:
            spans.append(emit - q.first_scheduled_time)
    p = pct(spans, 50)
    return None if p is None else 1e3 * p
