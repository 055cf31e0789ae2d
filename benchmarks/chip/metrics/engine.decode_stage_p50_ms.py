"""Median, in milliseconds, over the decode steps that ended in the traced
window, of the host's staging inside the step's ``alise.engine.decode``
span: the sum of the ``alise.decode.stage`` spans it holds (the engine's
lane arrays, then the backend's page tables and their copy to the
device)."""
from benchmarks.chip import trace_reduce as TR
from benchmarks.chip.readers import pct


def read(rec):
    tl = rec.timeline
    if tl is None:
        return None
    staged = []
    for step in TR.program_spans(tl, "engine.decode"):
        stages = TR.within(tl, step, "decode.stage")
        if stages:
            staged.append(sum(s.dur for s in stages))
    p = pct(staged, 50)
    return None if p is None else 1e3 * p
