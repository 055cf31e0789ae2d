"""Shared set-up of the benchmark's CPU tests: the cells of
``BENCHMARK.json`` cut to toy widths, so that a whole run (weights,
engine, gateway, window, reference check) takes seconds on a CPU.

The toy sizes and the served gap's limit at them belong to the
architecture, and are found by its name: ``tiny/<architecture>.json``
holds ``sizes``, laid over the configuration's ``arch`` block, and
``gap_limits``, of which the first whose ``when`` matches the toy sizes
applies."""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks.chip import harness  # noqa: E402

TINY_ENGINE = dict(max_slots=4, max_seq_len=128, max_new_tokens=48,
                   prefill_chunk=16)

CHIP = REPO / "benchmarks" / "chip"
E2E = ("ttft_p50_ms", "tpot_p50_ms", "slo_attain_pct", "setup_s")
LAYER = ("gateway.submit_lag_p99_ms", "sched.queue_wait_p90_s",
         "engine.prefill_ms_per_ktok", "engine.decode_iter_ms",
         "kv.preempt_per_100req", "kv.pool_peak_pct", "step.decode_roofline",
         "step.decode_mfu_pct", "device.idle_pct")


def tiny(architecture: str) -> dict:
    return json.loads((HERE / "tiny" / f"{architecture}.json").read_text())


def tiny_gap_limit(architecture: str, sizes: dict) -> float:
    for g in tiny(architecture)["gap_limits"]:
        if all(sizes.get(k) == v for k, v in g["when"].items()):
            return g["limit"]
    raise KeyError(f"no toy gap limit of {architecture} for {sizes}")


def tiny_cell(config: str, traffic: str, **arch) -> harness.Cell:
    """A cell of one configuration file and one traffic file, with every
    metric reader, cut to its architecture's toy widths."""
    conf = harness.load_json(CHIP / "configs" / f"{config}.json")
    name = conf["architecture"]
    conf["arch"].update(tiny(name)["sizes"], **arch)
    conf["engine"].update(TINY_ENGINE)
    conf["check"]["logit_gap_limit"] = tiny_gap_limit(name, conf["arch"])
    cell = harness.Cell(
        f"{config}.{traffic}", 1, conf,
        harness.load_json(CHIP / "traffic" / f"{traffic}.json"),
        [{"name": n, "unit": "-"} for n in E2E],
        [{"name": n, "unit": "-"} for n in LAYER],
        harness.architecture(name))
    cell.traffic.update(max_prompt=40, max_output=40, ramp_s=0.5, rate=4.0,
                        drain_cap_s=20.0)
    return cell


def run_tiny(cell: harness.Cell, seed: int = 7, seconds: float = 2.0,
             trace: bool = False, **kw) -> dict:
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            require_tpu=False, cache=False,
                            log=lambda s: None, **kw)
