"""Device milliseconds of the prefill-chunk programs per 1000 prompt
tokens prefilled, in the traced window.  The engine's own prefill ring
times the dispatch, which returns before the chip has run it, so the
time comes from the trace."""
from benchmarks.chip import trace_reduce as TR


def read(rec):
    tl = rec.timeline
    if tl is None:
        return None
    toks = sum(int(s.stats["tokens"]) for s in TR.host_spans(tl, "prefill"))
    dev_s = TR.module_seconds(tl, "prefill")
    return 1e6 * dev_s / toks if toks and dev_s > 0 else None
