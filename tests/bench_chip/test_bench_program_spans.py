"""The readers of the program's first-token stamps on a tiny traced cell:
a request's time to first token splits, on one clock, into the queue wait
(first plan - due), the engine's prefill span (hand-off to the stream
buffer - first plan) and the delivery lag (client - hand-off); a program
without the hand-off stamp reads as nothing, not as zero."""
from types import SimpleNamespace

import pytest

from bench_chip_tiny import run_tiny, tiny_cell
from benchmarks.chip import harness

SPAN_METRICS = ("engine.prefill_span_p50_ms", "gateway.deliver_lag_p50_ms")


@pytest.fixture(scope="module")
def traced_run():
    cell = tiny_cell("granite-3-8b", "sharegpt-1.28rps")
    cell.per_layer += [{"name": n, "unit": "ms"} for n in SPAN_METRICS]
    got = {}
    out = run_tiny(cell, seed=2 ** 33 + 5, trace=True,
                   record_hook=lambda rec: got.setdefault("rec", rec))
    return out, got["rec"]


def test_ttft_splits_into_queue_prefill_and_delivery(traced_run):
    out, rec = traced_run
    assert out["correct"]
    split = 0
    for r in rec.measured:
        if r.first is None:
            continue
        q = r.request
        wait = q.first_scheduled_time - r.due
        prefill = q.first_token_emit_time - q.first_scheduled_time
        lag = r.first - q.first_token_emit_time
        assert prefill >= 0 and lag >= 0
        assert wait + prefill + lag == pytest.approx(r.first - r.due,
                                                     abs=1e-3)
        split += 1
    assert split == len(rec.measured) > 0


def test_span_metrics_are_reported_and_read_the_stamps(traced_run):
    out, rec = traced_run
    for name in SPAN_METRICS:
        assert out["metrics"][name]["unit"] == "ms"
        assert out["metrics"][name]["value"] >= 0
    prefill = sorted(1e3 * (r.request.first_token_emit_time
                            - r.request.first_scheduled_time)
                     for r in rec.measured)
    mid = prefill[len(prefill) // 2] if len(prefill) % 2 else \
        (prefill[len(prefill) // 2 - 1] + prefill[len(prefill) // 2]) / 2
    assert out["metrics"][SPAN_METRICS[0]]["value"] == pytest.approx(mid)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_stamp_reads_nothing(name):
    request = SimpleNamespace(first_scheduled_time=1.0)   # no emit stamp
    rec = SimpleNamespace(measured=[SimpleNamespace(
        request=request, due=0.5, first=2.0)])
    assert harness.reader(name)(rec) is None
    assert harness.reader(name)(SimpleNamespace(measured=[])) is None
