"""The two channels through which a metric reader sees the program itself:
its ``alise.*`` spans, with their stats, in ``rec.timeline.program`` (kept
apart from the benchmark's own ``bench:*`` spans, so that no role or idle
attribution moves), and the engine's gauges at the start and the end of
serving in ``rec.gauges``.  A reader added as a new file reads both with
no change to the harness or the trace reduction."""
import collections
import warnings
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_chip_tiny import run_tiny, tiny_cell
from benchmarks.chip import harness, peaks, readers
from benchmarks.chip import trace_reduce as TR

NEW = ("engine.decode_stage_p50_ms", "kv.decode_width_mean")
FIXTURE = Path(__file__).with_name("fixture_trace.xplane.pb")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced tiny run, with its record, the engine it served with and
    the profiler trace it read."""
    keep = tmp_path_factory.mktemp("trace")
    cell = tiny_cell("granite-3-8b", "sharegpt-1.28rps")
    cell.per_layer += [{"name": n, "unit": "-"} for n in NEW]
    got = {}
    build = harness.build

    def capture(*a, **k):
        gw, engine, params = build(*a, **k)
        got["engine"], got["steps_at_setup"] = engine, len(engine.iter_times)
        return gw, engine, params

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "build", capture)
        out = run_tiny(cell, seed=2 ** 33 + 17, trace=True,
                       keep_trace=str(keep),
                       record_hook=lambda rec: got.setdefault("rec", rec))
    got["out"], got["xplane"] = out, sorted(keep.rglob("*.xplane.pb"))[-1]
    return SimpleNamespace(**got)


def test_program_spans_carry_the_decode_stats_of_the_step_they_hold(traced):
    assert traced.out["correct"]
    tl = traced.rec.timeline
    steps = TR.program_spans(tl, "engine.decode")
    assert len(steps) > 10
    for step in steps:
        assert {"lanes", "ctx", "width"} <= set(step.stats)
        inner = [h for h in TR.host_spans(tl, "decode")
                 if step.start <= h.start and h.end <= step.end]
        assert len(inner) == 1
        assert step.stats["lanes"] == inner[0].stats["lanes"]
        assert step.stats["ctx"] == inner[0].stats["ctx"]
        # the two staging spans, the engine's and the backend's
        assert len(TR.within(tl, step, "decode.stage")) == 2


def test_no_host_span_is_a_program_span(traced):
    tl = traced.rec.timeline
    assert not any(e.name.startswith(TR.PROGRAM) for e in tl.host)
    assert {e.name for e in tl.host} <= {
        "window", "Gateway.submit", "ServingEngine.step", "Scheduler.plan",
        "decode", "prefill", "kv.offload", "kv.upload"}
    assert {"engine.step", "sched.plan", "decode.sync"} <= {
        e.name for e in tl.program}
    assert not any(e.name.startswith(TR.PROGRAM) for e in tl.program)


def test_program_spans_leave_roles_idle_and_readings_as_they_were():
    # program spans over every stretch of the fixture trace, as
    # alise.decode.sync would lie over the chip's work: had they gone
    # into the host spans they would be innermost everywhere
    tl = TR.load(str(FIXTURE))
    lo, hi = tl.window
    cover = [TR.Event("decode.sync", lo + k * (hi - lo) / 50, (hi - lo) / 50)
             for k in range(50)]
    both = TR.Timeline(tl.window, tl.ops, tl.modules, tl.host, tl.enqueued,
                       cover)
    assert TR.roles(both) == TR.roles(tl)
    assert TR.idle_by_host_span(both) == TR.idle_by_host_span(tl)
    assert TR.top_ops(both) == TR.top_ops(tl)
    assert TR.program_spans(both, "decode.sync") == cover
    rec = SimpleNamespace(
        sizes=dict(num_layers=1, d_model=64, num_heads=4, num_kv_heads=2,
                   head_dim=16, d_ff=128, vocab_size=256,
                   tie_embeddings=True),
        peak=peaks.peak_of("TPU v5 lite"),
        timeline=both, architecture=harness.architecture("dense_gqa"))
    assert harness.reader("step.decode_roofline")(rec) == 2.226303969068327
    assert harness.reader("step.decode_mfu_pct")(rec) == \
        0.026873874441288435
    assert readers.idle_pct(rec) == pytest.approx(100 * TR.idle_share(tl))


def test_gauge_deltas_count_the_steps_decoded_after_set_up(traced):
    g = traced.rec.gauges
    assert set(g) == {"start", "end"} and set(g["start"]) == set(g["end"])
    width = [k for k in g["end"] if k.startswith("decode_width_")]
    assert width
    decoded = len(traced.engine.iter_times) - traced.steps_at_setup
    assert decoded > 10
    assert sum(g["end"][k] - g["start"][k] for k in width) == decoded


def test_new_metrics_are_reported_in_a_traced_run(traced):
    m = traced.out["metrics"]
    assert m["engine.decode_stage_p50_ms"]["value"] > 0
    g = traced.rec.gauges
    rungs = {int(k.split("_")[2]): g["end"][k] - g["start"][k]
             for k in g["end"] if k.startswith("decode_width_")}
    want = sum(w * n for w, n in rungs.items()) / sum(rungs.values())
    assert m["kv.decode_width_mean"]["value"] == pytest.approx(want)
    used = [w for w, n in rungs.items() if n]
    assert min(used) <= want <= max(used)


def _hand_timeline(program):
    ev = TR.Event
    return TR.Timeline((0.0, 10.0), {}, {}, [ev("window", 0, 10)], {},
                       program)


def test_decode_stage_reader_by_hand():
    ev = TR.Event
    read = harness.reader("engine.decode_stage_p50_ms")
    program = [
        ev("engine.decode", 1.0, 1.0), ev("decode.stage", 1.1, 0.1),
        ev("decode.stage", 1.3, 0.2),                       # 0.3 s
        ev("engine.decode", 3.0, 1.0), ev("decode.stage", 3.1, 0.1),
        # a stage on another thread inside the same stretch is not its
        ev("decode.stage", 3.4, 0.5, thread=1),             # 0.1 s
        ev("engine.decode", 5.0, 1.0), ev("decode.stage", 5.5, 0.4),
        ev("decode.stage", 5.95, 0.1),      # runs past the step's end
        # 0.4 s; a step that ended after the window is not read
        ev("engine.decode", 9.5, 1.0), ev("decode.stage", 9.6, 0.8)]
    rec = SimpleNamespace(timeline=_hand_timeline(program))
    assert read(rec) == pytest.approx(300.0)
    assert read(SimpleNamespace(timeline=None)) is None
    assert read(SimpleNamespace(timeline=_hand_timeline([]))) is None
    no_stage = [ev("engine.decode", 1.0, 1.0)]
    assert read(SimpleNamespace(timeline=_hand_timeline(no_stage))) is None


def test_decode_width_reader_by_hand():
    read = harness.reader("kv.decode_width_mean")
    start = {"decode_width_256_steps": 2.0, "decode_width_512_steps": 0.0,
             "decode_width_1024_steps": 7.0, "pool_free_pages": 90.0}
    end = {"decode_width_256_steps": 5.0, "decode_width_512_steps": 1.0,
           "decode_width_1024_steps": 7.0, "pool_free_pages": 12.0}
    rec = SimpleNamespace(gauges={"start": start, "end": end})
    assert read(rec) == pytest.approx((3 * 256 + 1 * 512) / 4)
    assert read(SimpleNamespace(gauges={})) is None
    assert read(SimpleNamespace(gauges={"start": start,
                                        "end": dict(start)})) is None
    assert read(SimpleNamespace(gauges={"start": {"pool_free_pages": 1.0},
                                        "end": {"pool_free_pages": 2.0}})) \
        is None


THROWAWAY = '''
from collections import defaultdict


def read(rec):
    spans = defaultdict(list)
    for e in rec.timeline.program:
        spans[e.name].append(e.stats)
    g = rec.gauges
    return dict(spans), {k: g["end"][k] - g["start"][k] for k in g["end"]}
'''


def test_a_reader_added_later_reads_any_program_span_and_gauge(
        traced, tmp_path):
    # a new metrics/<name>.py, loaded as the harness loads a reader
    path = tmp_path / "program.everything.py"
    path.write_text(THROWAWAY)
    spans, deltas = harness._load(path, "_metric_program.everything").read(
        traced.rec)
    from jax.profiler import ProfileData
    raw = collections.Counter()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(traced.xplane)).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    raw.update(e.name[len(TR.PROGRAM):] for e in line.events
                               if e.name.startswith(TR.PROGRAM))
    assert raw and {n: len(s) for n, s in spans.items()} == dict(raw)
    assert all({"lanes", "ctx", "width"} <= set(s)
               for s in spans["engine.decode"])
    assert all({"tokens", "start"} <= set(s)
               for s in spans.get("engine.prefill", []))
    assert set(deltas) == set(traced.engine.gauges())
    assert traced.rec.gauges["end"] == traced.engine.gauges()
