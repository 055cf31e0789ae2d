"""Bring-up check: serve granite-3-8b at its published widths on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four one-chip replicas vs one

Everything goes through the normal entry points of ``repro.launch.serve``
at ``size="chip"``: every published width of granite-3.0-8b, 24 of its 40
layers (``src/repro/configs/granite_3_8b.py``), random weights from a
seed, a paged KV pool of 16 lanes x 2048 tokens, and chunked prefill
warmed up before serving.

One chip runs two phases:

1. ``serve_gateway``: one replica in wall-clock mode, 8 ShareGPT-shaped
   requests through admission, the router and the concurrent pump;
2. ``serve``: a closed batch of 8 requests.

Both phases keep prompts of 128-1024 tokens (the chip size's limits:
shorter ShareGPT prompts are lengthened, longer ones cut).

``--four-chips`` runs only this phase: the same 16 requests through one
replica on the first chip, then through four one-chip replicas behind
the ``ewt`` router (``devices="tpu"``).

Each phase checks that every request finished with its expected number of
tokens, that nothing compiled after warmup, and that each replica's
weights and page pool live on its own chip; the four-chip phase also
checks that greedy tokens match request by request.  Any failed check
raises.  Times printed are bring-up figures, not benchmarks.  The last
line of stdout is one JSON object naming the device, printed only after
every check passed.  On any platform but a TPU the script exits non-zero
before building anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ARCH = "granite-3-8b"
EOS = 1                         # EngineConfig.eos_token


def _tokens_ok(tokens, expected: int) -> bool:
    """The expected count is the request's trace length; a request ends
    early only when the model itself sampled EOS."""
    return len(tokens) == expected or (
        0 < len(tokens) < expected and tokens[-1] == EOS)


def check_streams(run) -> dict:
    """Gateway run: every stream finished (not shed, cancelled or timed
    out) with its expected token count.  Returns req_id -> tokens."""
    out = {}
    for stream in run.streams:
        req = stream.request
        kinds = [ev.kind for ev in stream.events_log]
        toks = stream.token_values
        if "finish" not in kinds or not _tokens_ok(toks, req.true_out_len):
            raise AssertionError(
                f"request {req.req_id}: events {kinds[-3:]}, {len(toks)} "
                f"tokens, expected {req.true_out_len}")
        out[req.req_id] = toks
    if len(out) != len(run.requests):
        raise AssertionError(f"{len(out)} streams for {len(run.requests)} "
                             "requests")
    return out


def check_batch(run) -> dict:
    out = {}
    for req in run.requests:
        toks = list(req.output_tokens)
        if req.finish_time is None or not _tokens_ok(toks, req.true_out_len):
            raise AssertionError(
                f"request {req.req_id}: finished={req.finish_time is not None}"
                f", {len(toks)} tokens, expected {req.true_out_len}")
        out[req.req_id] = toks
    return out


def check_placement(engine, device) -> None:
    """The replica's weights and its KV page pool are on ``device``."""
    import jax
    leaves = jax.tree.leaves(engine.params)
    places = {d for leaf in leaves for d in leaf.devices()}
    places |= engine.kv.pool.k.devices() | engine.kv.pool.v.devices()
    if places != {device}:
        raise AssertionError(f"replica {engine.device}: arrays on {places}, "
                             f"expected only {device}")


def check_compiles(run, label: str) -> None:
    if run.compiles:
        raise AssertionError(f"{label}: {run.compiles} compiles after warmup")


def _summary(label: str, run, tokens: dict) -> None:
    n_tok = sum(len(t) for t in tokens.values())
    plens = [r.prompt_len for r in run.requests]
    print(f"[chip_smoke] {label}: {len(tokens)}/{len(run.requests)} requests "
          f"finished, prompts {min(plens)}-{max(plens)} tokens, {n_tok} "
          f"tokens generated, {run.compiles} compiles after warmup")
    print(f"[chip_smoke] {label}: set-up {run.setup_s:.1f}s (weights, "
          f"engine, warmup; compiles {run.setup_compile_s:.1f}s summed "
          f"over programs, {run.setup_cache_hits} compile-cache hits), serving "
          f"{run.serve_s:.2f}s, bring-up wall {n_tok / run.serve_s:.1f} "
          f"tok/s (not a benchmark)")


def _engines(run):
    return [d.engine for d in run.server.router.drivers]


def _release(run) -> None:
    """Drop a phase's weights and KV pool before the next phase builds
    its own: one chip holds one replica."""
    del run.server, run.streams
    gc.collect()


def one_chip(size: str, device) -> None:
    from repro.launch.serve import serve, serve_gateway
    chunk = 256 if size == "chip" else 16
    run = serve_gateway(ARCH, size=size, dataset="sharegpt", rate=4.0,
                        n_requests=8, n_engines=1, max_slots=16,
                        virtual_dt=None, kv_backend="paged",
                        prefill_chunk=chunk, warmup=True)
    tokens = check_streams(run)
    check_compiles(run, "gateway")
    check_placement(_engines(run)[0], device)
    _summary("gateway", run, tokens)
    _release(run)

    run = serve(ARCH, size=size, n_requests=8, max_slots=16,
                kv_backend="paged", prefill_chunk=chunk, warmup=True)
    tokens = check_batch(run)
    check_compiles(run, "batch")
    check_placement(run.server, device)
    _summary("batch", run, tokens)
    _release(run)


def four_chips(size: str, devices: list, spec: str) -> None:
    from repro.launch.serve import serve_gateway
    chunk = 256 if size == "chip" else 16
    common = dict(size=size, dataset="sharegpt", rate=8.0, n_requests=16,
                  max_slots=16, virtual_dt=None, kv_backend="paged",
                  prefill_chunk=chunk, warmup=True, router="ewt")
    run = serve_gateway(ARCH, n_engines=1, devices="0", **common)
    single = check_streams(run)
    check_compiles(run, "one replica")
    check_placement(_engines(run)[0], devices[0])
    _summary("one replica", run, single)
    _release(run)

    run = serve_gateway(ARCH, n_engines=len(devices), devices=spec, **common)
    quad = check_streams(run)
    check_compiles(run, "four replicas")
    engines = _engines(run)
    for eng, dev in zip(engines, devices):
        check_placement(eng, dev)
    _summary("four replicas", run, quad)
    print("[chip_smoke] replicas: " + ", ".join(
        f"{eng.device} served {len(eng.sched.finished)}" for eng in engines))
    mismatched = [rid for rid in single if single[rid] != quad.get(rid)]
    if mismatched:
        raise AssertionError(f"greedy tokens differ between one and four "
                             f"replicas for requests {mismatched}")
    print(f"[chip_smoke] greedy tokens match request by request "
          f"({len(single)} requests, one replica vs four)")
    _release(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-replica phase (needs four chips)")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}); there is no fallback", file=sys.stderr)
        return 2
    print(f"[chip_smoke] device {dev.device_kind} x{len(devs)} "
          f"({dev.platform})")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.utils import use_compile_cache
    print(f"[chip_smoke] compile cache {use_compile_cache()}")
    if args.four_chips:
        if len(devs) < 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{len(devs)}")
        four_chips("chip", devs[:4], "tpu")
    else:
        one_chip("chip", dev)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[chip_smoke] peak HBM in use on {dev.device_kind} 0: "
              f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
