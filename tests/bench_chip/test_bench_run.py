"""The benchmark's command and whole runs on the CPU at toy widths: the
command refuses a platform without a TPU before building anything; a
run, traced and not, prints the one-line result it is read by."""
import importlib.util
import json

import pytest

from bench_chip_tiny import REPO, run_tiny, tiny_cell


@pytest.fixture(scope="module")
def run_py():
    spec = importlib.util.spec_from_file_location(
        "bench_chip_run", REPO / "benchmarks" / "chip" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_platform_without_tpu(run_py, capsys):
    rc = run_py.main(["--workload", "granite-3-8b.sharegpt", "--seed",
                      str(2 ** 40), "--seconds", "10", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "needs 1 TPU chip" in out.err


def _check_shape(out, metrics):
    assert list(out)[-1] == "compared"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert set(out["metrics"]) == set(metrics)
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"}
    assert out["device"]["platform"] == "cpu"
    json.dumps(out, allow_nan=False)


def test_open_loop_untraced_reports_end_to_end():
    out = run_tiny(tiny_cell("internvl2-2b-lm", "mixed-2.8rps"),
                   seed=2 ** 32 + 9)
    _check_shape(out, {"ttft_p50_ms", "tpot_p50_ms", "slo_attain_pct",
                       "setup_s"})
    assert out["correct"] and out["failed"] == 0
    assert out["metrics"]["slo_attain_pct"]["value"] == 100.0


def test_traced_run_reports_per_layer():
    out = run_tiny(tiny_cell("granite-3-8b", "sharegpt-1.28rps"), seed=3,
                   trace=True)
    # the CPU has no device plane: the device readers find nothing to
    # read and are left out; the host ones remain
    _check_shape(out, {"gateway.submit_lag_p99_ms", "sched.queue_wait_p90_s",
                       "engine.decode_iter_ms", "kv.preempt_per_100req",
                       "kv.pool_peak_pct"})
    assert out["correct"]
    assert out["device"]["busy_s"] == 0.0 and out["device"]["window_s"] > 1
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
