"""BENCHMARK.json is well formed and everything it names is there: each
configuration, traffic mix and metric reader is a file found by name,
every cell reports set-up time, another end-to-end metric and a per-layer
metric, and each per-layer metric's cells report what it moves."""
import json
import re

import pytest

from bench_chip_tiny import REPO

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CHIP = REPO / "benchmarks" / "chip"


def _cells_of(metric):
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    assert (REPO / BENCH["command"][1]).is_file()
    n = len(BENCH["workloads"])
    # a full check: 2 + 14 runs a cell, run_seconds + 60 each, 180 s of
    # compiling a cell, 1200 spare, inside 43200 s even at 24 cells
    for cells in (n, 24):
        assert ((2 + 14 * cells) * (BENCH["run_seconds"] + 60)
                + 180 * cells + 1200) <= 43200


def test_entries_have_exactly_their_keys_and_valid_names():
    shapes = {"configs": {"name", "source", "file", "reduced", "why"},
              "workloads": {"name", "config", "traffic", "chips", "why"},
              "end_to_end": {"name", "unit", "better", "bound", "source"},
              "per_layer": {"name", "unit", "better", "source", "layer",
                            "moves"}}
    for group, keys in shapes.items():
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for e in BENCH[group]:
            assert set(e) - {"workloads"} == keys, e["name"]
            assert NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            if "bound" in e:
                assert 0.01 <= e["bound"] <= 0.25
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_and_metrics(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert (REPO / conf["file"]).is_file()
    assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
    assert w["chips"] in (1, 4)
    e2e = [m["name"] for m in BENCH["end_to_end"] if cell in _cells_of(m)]
    layer = [m for m in BENCH["per_layer"] if cell in _cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], cell)
    for name in e2e + [m["name"] for m in layer]:
        assert (CHIP / "metrics" / f"{name}.py").is_file(), name


def test_configs_name_their_cuts():
    for c in BENCH["configs"]:
        data = json.loads((REPO / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert {"ttft_s", "tpot_ms"} <= set(data["slo"])
        assert data["check"]["logit_gap_limit"] > 0


def test_rooflines_and_utilisations_are_shares():
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_steady_tpot_reads_what_tpot_p50_ms_reads():
    from types import SimpleNamespace

    from benchmarks.chip import harness
    rec = SimpleNamespace(measured=[
        SimpleNamespace(ok=True, first=1.0, finish=1.0 + 0.021 * k * (n - 1),
                        tokens=[0] * n)
        for k, n in ((1, 40), (2, 7), (3, 300), (1.5, 1))])
    want = harness.reader("tpot_p50_ms")(rec)
    assert want == pytest.approx(42.0)
    assert harness.reader("tpot_p50_ms.steady")(rec) == want
    steady = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "tpot_p50_ms.steady")
    shared = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "tpot_p50_ms")
    assert set(_cells_of(steady)) < set(_cells_of(shared))
    assert steady["bound"] < shared["bound"]
