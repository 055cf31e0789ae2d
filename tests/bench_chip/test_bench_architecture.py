"""A configuration's ``"architecture"`` is the one place that says which
model mathematics a cell runs: served at toy widths through ``run_cell``,
a cell whose configuration names a test-only module takes its weights, its
reference and its work counts from that module, and the harness reaches
no other.  A configuration that names no architecture, or one with no
file, is refused when the cell is loaded, naming the file."""
import json
from pathlib import Path

import pytest

from bench_chip_tiny import CHIP, run_tiny, tiny_cell
from benchmarks.chip import harness, peaks, readers
from benchmarks.chip import trace_reduce as TR

HERE = Path(__file__).resolve().parent


def unreachable(*args, **kwargs):
    raise AssertionError("reached an architecture the cell does not name")


def test_a_cell_reaches_the_model_only_through_its_architecture(
        monkeypatch):
    cell = tiny_cell("granite-3-8b", "sharegpt-1.28rps")
    # the harness's own copy of dense_gqa fails if anything reaches it,
    # and only the test-only module can be found
    for fn in ("params", "summarize", "decode_step", "prefill_chunk"):
        monkeypatch.setattr(cell.architecture, fn, unreachable)
    monkeypatch.setattr(harness, "ARCHS", HERE / "archs")
    with pytest.raises(FileNotFoundError):
        harness.architecture("dense_gqa")
    cell.config["architecture"] = "recording"
    cell.architecture = harness.architecture("recording")
    calls = cell.architecture.CALLS
    calls.clear()
    got = {}
    out = run_tiny(cell, seed=2 ** 33 + 17,
                   record_hook=lambda rec: got.setdefault("rec", rec))
    assert out["correct"], out["compared"]
    assert calls == ["params", "summarize"]

    out = run_tiny(cell, seed=2 ** 33 + 17, control="fp8")
    # the fp8 forward, then the float32 reference over its choices
    assert calls[2:] == ["params", "summarize", "summarize"]
    assert out["control"]["gaps"]

    # the work counts, over the fixture trace recorded on a chip
    rec = got["rec"]
    assert rec.architecture is cell.architecture
    rec.timeline = TR.load(str(HERE / "fixture_trace.xplane.pb"))
    rec.peak = peaks.peak_of("TPU v5 lite")
    calls.clear()
    assert harness.reader("step.decode_roofline")(rec) > 0
    assert len(readers.prefill_work(rec)) == 3
    assert calls == ["decode_step"] * 6 + ["prefill_chunk"] * 3


def _bench_with(tmp_path, conf: dict) -> Path:
    cfg = tmp_path / "configs" / "c.json"
    cfg.parent.mkdir()
    cfg.write_text(json.dumps(conf))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "c", "file": "configs/c.json"}]
    bench["workloads"] = [{"name": "c.sharegpt", "config": "c",
                           "traffic": "sharegpt-1.28rps", "chips": 1}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return cfg


@pytest.mark.parametrize("name", ["no_such_arch", None])
def test_a_missing_architecture_is_refused_at_load_naming_the_file(
        tmp_path, name):
    conf = json.loads((CHIP / "configs" / "granite-3-8b.json").read_text())
    if name is None:
        del conf["architecture"]
    else:
        conf["architecture"] = name
    cfg = _bench_with(tmp_path, conf)
    if name is None:
        with pytest.raises(KeyError, match=str(cfg)):
            harness.load_cell("c.sharegpt", tmp_path)
    else:
        want = str(CHIP / "archs" / f"{name}.py")
        with pytest.raises(FileNotFoundError, match=want):
            harness.load_cell("c.sharegpt", tmp_path)


def test_every_configuration_names_an_architecture_file():
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = json.loads((CHIP.parents[1] / c["file"]).read_text())
        assert (CHIP / "archs" / f"{conf['architecture']}.py").is_file()
        for w in bench["workloads"]:
            if w["config"] == c["name"]:
                cell = harness.load_cell(w["name"])
                assert cell.architecture is harness.architecture(
                    conf["architecture"])
