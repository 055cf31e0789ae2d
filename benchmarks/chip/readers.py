"""Arithmetic the metric readers share (``metrics/<name>.py`` each read one
number from the run record through these).

Latency arithmetic follows the gateway's own definitions: TTFT is taken
from when the request was due, not from when it was submitted, and TPOT is
(finish - first token) / (tokens - 1) per request.  Percentiles
interpolate linearly between order statistics (numpy's default).  Times
are the client's: when a token reached the benchmark's stream reader.

Device readers return None when the run was not traced or the trace holds
none of the work they read, so that a share of a roofline or of a peak is
never reported as 0 for want of data.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from . import trace_reduce as TR


def pct(xs: List[float], p: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), p)) if xs else None


def ttfts(rec) -> List[float]:
    return [r.first - r.due for r in rec.measured if r.first is not None]


def tpots(rec) -> List[float]:
    return [(r.finish - r.first) / (len(r.tokens) - 1)
            for r in rec.measured if r.ok and len(r.tokens) > 1]


def met_slo(rec, r) -> bool:
    """Finished, first token within the TTFT limit and, with two tokens or
    more, its TPOT within the TPOT limit.  Shed, timed-out, cancelled and
    unfinished requests miss."""
    if not r.ok or r.first is None:
        return False
    if r.first - r.due > rec.slo["ttft_s"]:
        return False
    if len(r.tokens) > 1:
        tpot = (r.finish - r.first) / (len(r.tokens) - 1)
        if tpot * 1e3 > rec.slo["tpot_ms"]:
            return False
    return True


def decode_work(rec):
    """(work, device seconds) of the decode steps in the traced window:
    the work from each annotated decode dispatch's lanes and context
    tokens, counted by the run's architecture module, the time from the
    decode programs' executions on the chip."""
    tl = rec.timeline
    if tl is None:
        return None
    spans = TR.host_spans(tl, "decode")
    dev_s = TR.module_seconds(tl, "decode")
    if not spans or dev_s <= 0:
        return None
    work = [rec.architecture.decode_step(
        rec.sizes, int(s.stats["lanes"]), int(s.stats["ctx"]))
        for s in spans]
    return work, dev_s


def prefill_work(rec):
    tl = rec.timeline
    if tl is None:
        return []
    return [rec.architecture.prefill_chunk(
        rec.sizes, int(s.stats["start"]), int(s.stats["tokens"]))
        for s in TR.host_spans(tl, "prefill")]


def decode_roofline_pct(rec) -> Optional[float]:
    got = decode_work(rec)
    if got is None or rec.peak is None:
        return None
    work, dev_s = got
    return 100.0 * sum(w.least_seconds(rec.peak) for w in work) / dev_s


def idle_pct(rec) -> Optional[float]:
    tl = rec.timeline
    if tl is None or TR.busy_s(tl) <= 0:
        return None
    return 100.0 * TR.idle_share(tl)
