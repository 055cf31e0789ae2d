"""Shared set-up of the benchmark's CPU tests: the cells of
``BENCHMARK.json`` cut to toy widths, so that a whole run (weights,
engine, gateway, window, reference check) takes seconds on a CPU."""
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks.chip import harness  # noqa: E402

# toy widths: every ratio the configurations have (GQA groups, head size
# against width) is kept, only smaller
TINY_ARCH = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                 head_dim=16, d_ff=128, vocab_size=512)
TINY_ENGINE = dict(max_slots=4, max_seq_len=128, max_new_tokens=48,
                   prefill_chunk=16)
# the widest served gap at these widths in bfloat16: granite-3-8b's tied
# embedding spreads its logits by about 0.14 and its gaps stay under 0.004
# (the fp8 control reads 0.03-0.1, a wrong token some tenths);
# internvl2-2b-lm's untied head spreads them by about 1.0 and its gaps
# reach 0.05
TINY_GAP_LIMIT = 0.015
TINY_GAP_LIMITS = {"granite-3-8b": TINY_GAP_LIMIT, "internvl2-2b-lm": 0.1}


CHIP = REPO / "benchmarks" / "chip"
E2E = ("ttft_p50_ms", "tpot_p50_ms", "slo_attain_pct", "setup_s")
LAYER = ("gateway.submit_lag_p99_ms", "sched.queue_wait_p90_s",
         "engine.prefill_ms_per_ktok", "engine.decode_iter_ms",
         "kv.preempt_per_100req", "kv.pool_peak_pct", "step.decode_roofline",
         "step.decode_mfu_pct", "device.idle_pct")


def tiny_cell(config: str, traffic: str, **arch) -> harness.Cell:
    """A cell of one configuration file and one traffic file, with every
    metric reader, cut to toy widths."""
    cell = harness.Cell(
        f"{config}.{traffic}", 1,
        harness.load_json(CHIP / "configs" / f"{config}.json"),
        harness.load_json(CHIP / "traffic" / f"{traffic}.json"),
        [{"name": n, "unit": "-"} for n in E2E],
        [{"name": n, "unit": "-"} for n in LAYER])
    c = cell.config
    c["arch"].update(TINY_ARCH, **arch)
    c["engine"].update(TINY_ENGINE)
    c["check"]["logit_gap_limit"] = TINY_GAP_LIMITS[c["name"]]
    t = cell.traffic
    t.update(max_prompt=40, max_output=40, ramp_s=0.5, rate=4.0,
             drain_cap_s=20.0)
    return cell


def run_tiny(cell: harness.Cell, seed: int = 7, seconds: float = 2.0,
             trace: bool = False, **kw) -> dict:
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            require_tpu=False, cache=False,
                            log=lambda s: None, **kw)
