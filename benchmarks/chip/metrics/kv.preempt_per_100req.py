"""Preemptions (swap-outs and drops) per 100 window arrivals, from each
request's preempt_count."""


def read(rec):
    m = rec.measured
    return 100.0 * sum(r.request.preempt_count for r in m) / len(m) if m \
        else None
