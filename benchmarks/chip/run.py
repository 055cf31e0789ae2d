"""The on-chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip this process finds, and
prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; last comes ``compared``, each number the correctness check
compared beside its limit, which also end standard error.  Exits 2, with
no result, where JAX finds no TPU or fewer chips than the cell asks for.
See ``harness.py`` for what a run does.
"""
import time

T_START = time.perf_counter()       # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="write the profiler trace to DIR and keep it")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload, ROOT)
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_START,
                               keep_trace=args.keep_trace,
                               log=lambda s: print(s, file=sys.stderr))
    except harness.NotOnChip as e:
        print(f"run.py: {e}; there is no fallback", file=sys.stderr)
        return 2
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
