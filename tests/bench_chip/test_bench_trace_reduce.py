"""The trace reduction on a small trace recorded on a TPU v5e
(``fixture_trace.xplane.pb``, made by ``make_trace_fixture.py``): six mock
serving iterations, each a 3 ms host-only ``Scheduler.plan`` and a
``decode`` program, every other one also a ``prefill`` program."""
from pathlib import Path

import pytest

import bench_chip_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import harness, peaks, readers
from benchmarks.chip import trace_reduce as TR

FIXTURE = Path(__file__).with_name("fixture_trace.xplane.pb")


@pytest.fixture(scope="module")
def tl():
    return TR.load(str(FIXTURE))


def test_window_and_busy_union(tl):
    assert 0.02 < tl.window_s < 0.04
    chips = list(tl.modules)
    assert chips == ["/device:TPU:0"]
    mods = tl.modules[chips[0]]
    assert len(mods) == 9
    busy = TR.busy_s(tl)
    # ops run inside their programs, so their union is no longer than the
    # programs' sum, and the chip was busy for a sliver of the window
    assert 0 < busy <= sum(m.dur for m in mods) + 1e-9
    assert TR.idle_share(tl) == pytest.approx(1 - busy / tl.window_s)
    assert TR.idle_share(tl) > 0.9


def test_programs_take_the_role_of_their_dispatching_span(tl):
    roles = TR.roles(tl)
    assert sorted(roles.values()) == ["decode", "prefill"]
    dec = TR.module_events(tl, "decode")
    pre = TR.module_events(tl, "prefill")
    assert len(dec) == 6 and len(pre) == 3
    assert all("decode_step" in e.name for e in dec)
    assert TR.module_seconds(tl, "decode") == pytest.approx(
        sum(e.dur for e in dec))


def test_host_spans_carry_their_stats(tl):
    dec = TR.host_spans(tl, "decode")
    assert len(dec) == 6
    assert all(s.stats == {"lanes": 4, "ctx": 1000} for s in dec)
    assert [s.stats["tokens"] for s in TR.host_spans(tl, "prefill")] == \
        [64] * 3


def test_idle_gaps_go_to_the_host_span_open_in_them(tl):
    gaps = TR.idle_gaps(tl)
    assert sum(b - a for a, b in gaps) == pytest.approx(
        tl.window_s - TR.busy_s(tl))
    by_span = dict(TR.idle_by_host_span(tl))
    # six 3 ms sleeps in Scheduler.plan are most of the idle time
    assert max(by_span, key=by_span.get) == "Scheduler.plan"
    assert by_span["Scheduler.plan"] > 0.018


def test_top_ops_name_program_role_and_op(tl):
    top = TR.top_ops(tl)
    assert top and all(v > 0 for _, v in top)
    assert top[0][0].startswith("decode/")
    assert all(" = " not in k for k, _ in top)
    assert sum(v for _, v in top) <= TR.busy_s(tl) + 1e-9


def fixture_record(tl):
    class Rec:
        sizes = dict(num_layers=1, d_model=64, num_heads=4, num_kv_heads=2,
                     head_dim=16, d_ff=128, vocab_size=256,
                     tie_embeddings=True)
        peak = peaks.peak_of("TPU v5 lite")
        timeline = tl
        architecture = harness.architecture("dense_gqa")
    return Rec


def test_decode_readers_over_the_fixture(tl):
    Rec = fixture_record(tl)
    work = Rec.architecture.decode_step(Rec.sizes, 4, 1000)
    want = 100 * 6 * work.least_seconds(Rec.peak) / TR.module_seconds(
        tl, "decode")
    assert readers.decode_roofline_pct(Rec) == pytest.approx(want)
    assert readers.idle_pct(Rec) == pytest.approx(100 * TR.idle_share(tl))


def test_device_readers_match_the_recorded_readings(tl):
    # read before the work counts moved into archs/dense_gqa.py: the
    # move changed no reading
    Rec = fixture_record(tl)
    assert harness.reader("step.decode_roofline")(Rec) == 2.226303969068327
    assert harness.reader("step.decode_mfu_pct")(Rec) == 0.026873874441288435
    assert [(w.flops, w.bytes) for w in readers.prefill_work(Rec)] == \
        [(5283840, 115072)] * 3


def test_union_and_gaps_by_hand():
    ev = TR.Event
    tl = TR.Timeline(
        (0.0, 10.0),
        {"c": [ev("a", 1, 2), ev("b", 2, 2), ev("c", 6, 1)]}, {},
        [ev("window", 0, 10), ev("outer", 0, 10), ev("inner", 4.5, 1)])
    assert TR.busy_intervals(tl, "c") == [(1, 4), (6, 7)]
    assert TR.busy_s(tl) == 4
    assert TR.idle_gaps(tl) == [(0, 1), (4, 6), (7, 10)]
    assert TR.attribute(tl, 5.0) == "inner" and TR.attribute(tl, 8) == "outer"
    assert TR.attribute(tl, 11) == TR.NO_SPAN
    assert dict(TR.idle_by_host_span(tl)) == {"outer": 4, "inner": 2}
