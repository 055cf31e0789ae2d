"""Calibration runs of one cell, several in one process: the knee sweep
(``--rates``), the control of the correctness check (``--control``) and
a look at where a run's latency tail comes from.  Not part of a benchmark
run.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --seconds 30 [--rates 1.2,1.6,2.0] [--control fp8]
        [--set engine.max_slots=64 ...]

For each rate (the traffic file's own rate without ``--rates``) and each
seed it makes one run exactly as ``run.py`` does, weights and all, and
prints one JSON line: the rate, the seed, the cell's end-to-end metrics,
the latency medians and tails, finished and failed counts, the KV tokens
the decode lanes held, the longest pauses (between decode iterations, in
the requests' token streams, and Python's garbage collections inside the
window), the widest served gap and, with ``--control``, the widest gap of
the control precision over the same sample.  ``--set`` overrides a value
of the configuration file.  Lines are also appended to ``--out``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
GC_PAUSES = []                  # (perf_counter at start, generation, s)


def _gc_timer(phase, info, _t0=[0.0]):
    if phase == "start":
        _t0[0] = time.perf_counter()
    else:
        GC_PAUSES.append((_t0[0], info["generation"],
                          time.perf_counter() - _t0[0]))


def pauses(rec) -> dict:
    """The longest pauses in the window, in milliseconds, each with its
    time in seconds from the window's start: host time between decode
    iterations, gaps in a request's token stream, garbage collections."""
    ws, we = rec.window
    it = rec.iters
    gaps = sorted(((b[0] - a[0] - a[3]) * 1e3, a[0] + a[3] - ws)
                  for a, b in zip(it, it[1:]))[::-1][:3]
    streams = []
    for r in rec.measured:
        if r.ok and len(r.times) > 1:
            d = [(b - a, a) for a, b in zip(r.times, r.times[1:])]
            g, at = max(d)
            streams.append(((r.finish - r.first) / (len(r.times) - 1) * 1e3,
                            len(r.times), g * 1e3, at - ws))
    collected = [(t - rec.clock_offset - ws, gen, s * 1e3)
                 for t, gen, s in GC_PAUSES
                 if ws <= t - rec.clock_offset < we]
    return {
        "iter_dt_max_ms": max((x[3] for x in it), default=0.0) * 1e3,
        "iter_gaps_ms": [[round(g, 1), round(t, 2)] for g, t in gaps],
        # the requests of the largest TPOT: TPOT, tokens, longest gap, when
        "tpot_top": [[round(a, 1), n, round(g, 1), round(t, 2)]
                     for a, n, g, t in sorted(streams)[::-1][:5]],
        "gc_count": [sum(1 for _, g, _ in collected if g == k)
                     for k in range(3)],
        "gc_ms_max": max((s for *_, s in collected), default=0.0),
        "gc_top": [[round(t, 2), g, round(s, 1)] for t, g, s in
                   sorted(collected, key=lambda x: -x[2])[:3]],
    }


def summary(rec) -> dict:
    from benchmarks.chip.readers import pct, tpots, ttfts
    m = rec.measured
    tp = tpots(rec)
    ctx = [x[1] for x in rec.iters]
    return {
        "measured": len(m), "finished": sum(r.ok for r in m),
        "ttft_p50_s": pct(ttfts(rec), 50), "ttft_p90_s": pct(ttfts(rec), 90),
        "tpot_p50_ms": None if not tp else 1e3 * pct(tp, 50),
        "tpot_p90_ms": None if not tp else 1e3 * pct(tp, 90),
        "decode_iter_ms": (1e3 * sum(x[3] for x in rec.iters)
                           / len(rec.iters)) if rec.iters else None,
        "lanes_mean": (sum(x[2] for x in rec.iters) / len(rec.iters)
                       if rec.iters else None),
        "preempts": sum(r.request.preempt_count for r in m),
        "last_finish_after_window_s": max(
            (r.finish - rec.window[1] for r in m if r.finish), default=None),
        "pool_tokens": rec.pool_tokens,
        "ctx_tokens_mean": sum(ctx) / len(ctx) if ctx else None,
        "ctx_tokens_peak": max(ctx, default=None),
        **pauses(rec),
    }


def override(conf: dict, assignments) -> None:
    """``group.key=value`` (a JSON value) into the configuration."""
    for a in assignments:
        path, value = a.split("=", 1)
        *groups, key = path.split(".")
        d = conf
        for g in groups:
            d = d[g]
        d[key] = json.loads(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--control", default=None, choices=("fp8",))
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", action="append", default=[], metavar="K=V")
    args = ap.parse_args(argv)
    gc.callbacks.append(_gc_timer)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [None])
    t0 = T_START
    for rate in rates:
        for seed in seeds:
            cell = harness.load_cell(args.workload, ROOT)
            override(cell.config, args.set)
            GC_PAUSES.clear()
            if rate is not None:
                cell.traffic["rate"] = rate
            extra = {}
            out = harness.run_cell(
                cell, seed, args.seconds, False, t0, control=args.control,
                record_hook=lambda r: extra.update(summary(r)),
                log=lambda s: print(s, file=sys.stderr))
            line = {"workload": args.workload, "set": args.set,
                    "rate": rate, "seed": seed,
                    "seconds": args.seconds, "correct": out["correct"],
                    "failed": out["failed"], **extra,
                    "metrics": {k: v["value"]
                                for k, v in out["metrics"].items()},
                    "compared": out["compared"],
                    "control": out.get("control"),
                    "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
            t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
