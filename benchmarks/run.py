"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only SECTION] [--smoke]

Emits ``name,us_per_call,derived`` CSV on stdout; commentary on stderr.
Sections: e2e (Fig. 2+6), memory (Fig. 8), predictor (Table 2),
latency (Fig. 9), models (Table 3), kernels (§3.3), roofline (§g),
cluster (beyond-paper), gateway (online serving front-end, beyond-paper).

``--smoke`` runs every section with tiny shapes and asserts each one
produced at least one result row, writing a machine-readable summary to
``--out`` (default ``runs/bench_smoke.json``).  CI uses this to catch
import/API drift without timing noise; a missing row or a raised exception
fails the process.

``--perf-out PATH`` additionally writes a ``BENCH_<pr>.json``
perf-trajectory artifact: the headline throughput numbers (fused decode
tokens/s per backend, gateway wall tokens/s) plus every raw result row, so
future PRs can diff their artifact against a baseline's.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

from benchmarks import common
from benchmarks.common import note

# rows whose ``derived`` tok_per_s lands in the artifact's headline metrics
PERF_METRIC_PREFIXES = ("e2e/engine_decode/", "e2e/compile_count/",
                        "e2e/spec_decode/",
                        "gateway/wall/",
                        "gateway/trace/", "gateway/quality/",
                        "gateway/cluster_tier/",
                        "hol/prefill_interleave/", "hol/shared_prefix/",
                        "hol/packed_prefill/", "hol/spec_decode/",
                        "hol/predictor_quality/", "predictor/")


def _perf_metrics() -> dict:
    """Pull headline throughputs (and WARN regression flags / telemetry
    key-value rows) out of the emitted rows."""
    metrics = {}
    for name, _us, derived in common.ROWS:
        if not name.startswith(PERF_METRIC_PREFIXES):
            continue
        if derived.startswith("WARN"):
            metrics[name] = {"flag": derived}
            continue
        # keep EVERY numeric key=value pair (tok_per_s AND the ttft/tpot
        # milli-second metrics ride the same row — the perf diff tracks
        # both), falling back to bare "N.NNx" speedup rows
        kv = {k: float(v) for k, v in re.findall(
            r"([A-Za-z_][A-Za-z_0-9]*)=(-?[0-9.]+(?:e-?[0-9]+)?)(?:;|$)",
            derived)}
        if kv:
            metrics[name] = kv
        elif re.fullmatch(r"-?[0-9.]+x", derived):
            metrics[name] = {"speedup": float(derived.rstrip("x"))}
    return metrics


def write_perf_artifact(path: str, pr: str, summary: dict) -> None:
    out = Path(path)
    out.parent.mkdir(parents=True, exist_ok=True)
    # drop stale artifacts from earlier PRs/runs: CI uploads BENCH_*.json
    # by glob, so a leftover from a previous invocation would ride along
    # and pollute the perf-trajectory diff
    for stale in out.parent.glob("BENCH_*.json"):
        if stale != out:
            stale.unlink()
            note(f"[perf] removed stale artifact {stale}")
    out.write_text(json.dumps({
        "pr": pr,
        "timestamp": time.time(),
        "smoke": common.is_smoke(),
        "metrics": _perf_metrics(),
        "sections": summary,
        "rows": [{"name": n, "us_per_call": u, "derived": d}
                 for n, u, d in common.ROWS],
    }, indent=2))
    note(f"[perf] trajectory artifact -> {out}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; assert every section emits a result")
    ap.add_argument("--out", default="runs/bench_smoke.json",
                    help="smoke-mode summary JSON path")
    ap.add_argument("--perf-out", default=None,
                    help="write a BENCH_<pr>.json perf-trajectory artifact "
                         "here (decode tokens/s, gateway wall throughput)")
    ap.add_argument("--pr", default=None,
                    help="PR identifier recorded in the perf artifact "
                         "(default: $PR_NUMBER or 'local')")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    if args.smoke:
        common.set_smoke(True)

    from benchmarks import (bench_cluster, bench_e2e, bench_gateway,
                            bench_hol, bench_kernels, bench_latency,
                            bench_memory, bench_models, bench_predictor,
                            bench_roofline)
    sections = {
        "hol": bench_hol.run,
        "e2e": bench_e2e.run,
        "memory": bench_memory.run,
        "predictor": bench_predictor.run,
        "latency": bench_latency.run,
        "models": bench_models.run,
        "kernels": bench_kernels.run,
        "roofline": bench_roofline.run,
        "cluster": bench_cluster.run,
        "gateway": bench_gateway.run,
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    summary = {}
    print("name,us_per_call,derived")
    for name in chosen:
        note(f"=== bench section: {name} ===")
        t0 = time.time()
        rows_before = len(common.ROWS)
        err = None
        try:
            sections[name]()
        except Exception as e:  # keep the harness going; report the failure
            err = repr(e)
            note(f"[{name}] FAILED: {err}")
            print(f"{name}/FAILED,0.0,{err}")
        dt = time.time() - t0
        # the FAILED marker is printed directly (not via emit), so ROWS
        # counts exactly the section's real result rows
        n_rows = len(common.ROWS) - rows_before
        summary[name] = {"rows": n_rows, "seconds": round(dt, 2),
                         "error": err}
        note(f"=== {name} done in {dt:.1f}s ===")

    if args.perf_out:
        pr = args.pr or os.environ.get("PR_NUMBER") or "local"
        write_perf_artifact(args.perf_out, pr, summary)

    if args.smoke:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(summary, indent=2))
        note(f"[smoke] summary -> {out}")
        bad = {k: v for k, v in summary.items()
               if v["error"] or v["rows"] == 0}
        if bad:
            note(f"[smoke] FAILED sections: {sorted(bad)}")
            return 1
        note(f"[smoke] all {len(summary)} sections emitted results")
    return 0


if __name__ == "__main__":
    sys.exit(main())
