"""Serving launcher: end-to-end ALISE serving of a real JAX model.

Batch mode (pre-built request list, closed loop):

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
        --strategy alise --n-requests 16

Gateway mode (online front-end: Poisson trace replayed through SLO-aware
admission + multi-replica routing, streaming delivery):

    PYTHONPATH=src python -m repro.launch.serve --gateway --dataset alpaca \
        --rate 8 --n-requests 32 --n-engines 2

``--size smoke`` (the default) builds the architecture's tiny CPU config;
``--size chip`` builds its published widths cut in depth to one chip
(``configs/<arch>.py: chip_config()``) with 2048-token lanes.
"""
from __future__ import annotations

import argparse
import asyncio
import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import numpy as np

from repro.configs import get_chip_config, get_smoke_config
from repro.core.engine import EngineConfig, ServingEngine
from repro.core.predictor import OraclePredictor, RetrievalPredictor
from repro.core.request import Request, SLOClass, reset_request_counter
from repro.core.trace import TraceConfig, clamp_requests, generate_trace
from repro.distributed.placement import (assign_devices, device_label,
                                         place_params)
from repro.models.model import Model
from repro.serving.gateway import AdmissionConfig, Gateway, GatewayConfig
from repro.serving.kv_tier import HostKVTier
from repro.serving.prediction import OnlineQuantilePredictor
from repro.utils import use_compile_cache
from repro.utils.compile_counter import CompileCounter


@dataclass(frozen=True)
class ServeSize:
    """What one launcher size serves: the per-request limits the engine
    and the traffic are cut to (the model config comes with the size)."""
    max_seq_len: int            # KV positions per decode lane
    min_prompt: int             # shorter prompts are lengthened to this
    max_prompt: int             # prompt tokens kept per request
    max_new_tokens: int         # output tokens per request


SIZES = {
    # toy widths that run on a laptop CPU (tests, CI)
    "smoke": ServeSize(max_seq_len=96, min_prompt=4, max_prompt=24,
                       max_new_tokens=48),
    # published widths on one chip: prompt and output share a 2048 lane,
    # whose last position is never decoded (the engine's context stop)
    "chip": ServeSize(max_seq_len=2048, min_prompt=128, max_prompt=1024,
                      max_new_tokens=1023),
}


@dataclass
class ServeRun:
    """What one launcher run served, for callers that check it."""
    requests: List[Request]
    server: object                  # ServingEngine (batch) or Gateway
    streams: Optional[list]         # gateway: one RequestStream per request
    setup_s: float                  # weights, engines and their warmup
    serve_s: float                  # wall seconds serving the requests
    compiles: int                   # backend compiles while serving
    setup_compile_s: float          # backend compile/cache-load seconds
    setup_cache_hits: int           # persistent-cache loads during set-up


def _report_phases(tag: str, run: ServeRun) -> None:
    print(f"[{tag}] set-up {run.setup_s:.1f}s (compiles "
          f"{run.setup_compile_s:.1f}s summed over programs, "
          f"{run.setup_cache_hits} compile-cache hits); serving "
          f"{run.serve_s:.2f}s with {run.compiles} compiles")


def size_config(arch: str, size: str):
    """(ArchConfig, ServeSize) for ``arch`` at launcher size ``size``."""
    if size not in SIZES:
        raise ValueError(f"unknown size {size!r}; known: {sorted(SIZES)}")
    cfg = get_smoke_config(arch) if size == "smoke" else get_chip_config(arch)
    return cfg, SIZES[size]


def init_params(model: Model, seed: int):
    """Random weights from ``seed``, built by one compiled program: each
    weight is drawn and cast to its dtype inside the program, so no
    float32 copy of the whole layer stack is ever materialized."""
    return jax.jit(model.init)(jax.random.PRNGKey(seed))


def _mk_predictor(kind: str, seed: int = 0):
    if kind == "oracle":
        return OraclePredictor()
    if kind == "online":
        return OnlineQuantilePredictor(seed=seed)
    return RetrievalPredictor(seed=seed)


def build_requests(cfg, n: int, seed: int = 0,
                   size: ServeSize = SIZES["smoke"]):
    """``n`` closed-loop requests with prompts of ``min_prompt`` up to
    ``max_prompt`` tokens and short outputs."""
    rng = np.random.default_rng(seed)
    reset_request_counter()
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(size.min_prompt, size.max_prompt))
        out = int(rng.choice([3, 5, 8, 30, 40], p=[0.3, 0.25, 0.2, 0.15, 0.1]))
        reqs.append(Request(
            prompt_len=plen, arrival_time=0.0, true_out_len=out,
            prompt_tokens=rng.integers(2, cfg.vocab_size, plen).tolist()))
    return reqs


def _export_trace(bus, quality: dict, trace_out: str) -> None:
    """Write the Chrome/Perfetto trace (+ a .prom gauge dump) and print
    the scheduler-quality highlights derived from the same stream."""
    from repro.serving.observability import (render_prometheus,
                                             write_chrome_trace)
    obj = write_chrome_trace(bus, trace_out)
    print(f"[trace] wrote {len(obj['traceEvents'])} trace events -> "
          f"{trace_out} (load in https://ui.perfetto.dev)")
    with open(trace_out + ".prom", "w") as f:
        f.write(render_prometheus(bus))
    print(f"[trace] wrote Prometheus gauges -> {trace_out}.prom")
    q = quality.get("queueing", {})
    e = quality.get("estimate_error", {})
    for label, d in [("ttft decomposition p50 (s): ",
                      {k: v.get("p50") for k, v in q.items()
                       if isinstance(v, dict) and v.get("n", 0)}),
                     ("EWT err (s) ", e.get("ewt_signed_s", {})),
                     ("len err (tok) ", e.get("len_signed_tok", {}))]:
        if isinstance(d, dict) and d and d.get("n", 1):
            stats = ", ".join(f"{k}={v:.3f}" for k, v in d.items()
                              if isinstance(v, float))
            print(f"[quality] {label}{stats}")


def serve(arch: str = "granite-3-8b", strategy: str = "alise",
          n_requests: int = 12, max_slots: int = 4, seed: int = 0,
          predictor_kind: str = "oracle", quantize: bool = True,
          kv_backend: str = "dense", prefill_chunk: Optional[int] = None,
          iter_token_budget=None, prefix_cache: bool = False,
          target_tpot: float = 0.05, trace_out: Optional[str] = None,
          prefill_buckets=None, prefill_pack: bool = False,
          prefill_pack_width: int = 4,
          warmup: bool = False, chunk_attn: str = "masked",
          spec_decode: bool = False, spec_k: int = 3,
          size: str = "smoke") -> ServeRun:
    with CompileCounter() as counter:
        t0 = time.perf_counter()
        cfg, sz = size_config(arch, size)
        model = Model(cfg, attn_chunk=32, remat=False,
                      chunk_attn_impl=chunk_attn)
        params = init_params(model, seed)
        predictor = _mk_predictor(predictor_kind, seed)
        autotune = iter_token_budget == "auto"
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=max_slots, max_seq_len=sz.max_seq_len,
            max_new_tokens=sz.max_new_tokens,
            strategy=strategy, quantize_offload=quantize,
            kv_backend=kv_backend, prefill_chunk=prefill_chunk,
            iter_token_budget=None if autotune else iter_token_budget,
            prefix_cache=prefix_cache,
            prefill_buckets=prefill_buckets, prefill_pack=prefill_pack,
            prefill_pack_width=prefill_pack_width,
            spec_decode=spec_decode, spec_k=spec_k,
            warmup_compile=warmup), predictor=predictor)
        if trace_out:
            from repro.serving.observability import EventBus
            eng.attach_bus(EventBus(clock="wall"), "engine0")
        if autotune:
            # profile a small warmup batch, then pick the budget whose
            # predicted mixed-iteration time matches the target TPOT
            eng.serve(build_requests(cfg, max(4, max_slots), seed + 1, sz))
            budget = eng.autotune_token_budget(target_tpot)
            print(f"[serve] auto-tuned iter_token_budget={budget} "
                  f"(target TPOT {target_tpot*1e3:.1f}ms)")
        reqs = build_requests(cfg, n_requests, seed, sz)
        setup_s = time.perf_counter() - t0
        compile_s, hits = counter.seconds, counter.cache_hits
        counter.reset()
        t0 = time.perf_counter()
        eng.serve(reqs)
        run = ServeRun(reqs, eng, None, setup_s, time.perf_counter() - t0,
                       counter.count, compile_s, hits)
    lat = [r.e2e_latency for r in reqs if r.e2e_latency is not None]
    norm = [r.normalized_latency for r in reqs if r.normalized_latency]
    print(f"[serve] {strategy}: {len(lat)}/{len(reqs)} finished; "
          f"mean latency {np.mean(lat):.3f}s; "
          f"normalized {np.mean(norm)*1e3:.1f} ms/token; "
          f"preemptions {sum(r.preempt_count for r in reqs)}")
    _report_phases("serve", run)
    lm = eng.fit_latency_model()
    print(f"[serve] fitted latency model: t0={lm.t0:.2e}s/tok "
          f"alpha={lm.alpha:.2e} beta={lm.beta:.2e}")
    if trace_out:
        from repro.serving.observability import analyze_quality
        _export_trace(eng.bus, analyze_quality(eng.bus), trace_out)
    return run


def serve_gateway(arch: str = "granite-3-8b", strategy: str = "alise",
                  dataset: str = "alpaca", rate: float = 8.0,
                  n_requests: int = 32, n_engines: int = 2,
                  max_slots: int = 4, router: str = "ewt",
                  interactive_frac: float = 0.25, seed: int = 0,
                  predictor_kind: str = "oracle",
                  virtual_dt: Optional[float] = 0.05,
                  pump: str = "concurrent",
                  ttft_target_interactive: Optional[float] = None,
                  ttft_target_batch: Optional[float] = None,
                  ttft_miss_policy: str = "shed",
                  ttft_quantile: float = 0.5,
                  kv_backend: str = "dense",
                  prefill_chunk: Optional[int] = None,
                  iter_token_budget: Optional[int] = None,
                  prefix_cache: bool = False,
                  trace_out: Optional[str] = None,
                  metrics_interval: Optional[float] = None,
                  prefill_buckets=None, prefill_pack: bool = False,
                  prefill_pack_width: int = 4,
                  warmup: bool = False, chunk_attn: str = "masked",
                  spec_decode: bool = False, spec_k: int = 3,
                  kv_tier: bool = False, tier_bytes: float = 256e6,
                  tier_quantize: bool = False,
                  devices: Optional[str] = None,
                  size: str = "smoke") -> ServeRun:
    """Replay a synthetic Poisson trace through the online Gateway and print
    per-class TTFT/E2E percentiles (and SLO attainment when targets are
    set).  ``virtual_dt=None`` serves in wall clock; ``pump`` selects the
    concurrent per-engine pump or the lockstep barrier there.

    ``devices`` places each engine replica on its own JAX device
    (round-robin over the resolved spec; see distributed/placement.py) so
    the concurrent pump overlaps replica *compute*, not just swap DMA.
    ``kv_tier`` joins every replica to one shared host-RAM prefix pool
    (serving/kv_tier.py): re-routed sessions import a peer's prefix pages
    at DMA cost instead of re-prefilling."""
    with CompileCounter() as counter:
        t0 = time.perf_counter()
        cfg, sz = size_config(arch, size)
        model = Model(cfg, attn_chunk=32, remat=False,
                      chunk_attn_impl=chunk_attn)
        params = init_params(model, seed)

        dev_list = assign_devices(n_engines, devices)
        # only commit params per replica when placement is explicit or
        # there is real device diversity — the single-device default
        # stays the uncommitted layout (bit-identical to prior releases)
        place = devices is not None or len({(d.platform, d.id)
                                            for d in dev_list}) > 1
        tier = None
        if kv_tier:
            tier = HostKVTier(tier_bytes, EngineConfig().page_size,
                              quantize=tier_quantize)

        def mk_engine(i: int):
            predictor = _mk_predictor(predictor_kind, seed)
            dev = dev_list[i % len(dev_list)] if place else None
            # the engine runs (and allocates its KV) where its params live
            eng = ServingEngine(model, place_params(params, dev),
                                EngineConfig(
                max_slots=max_slots, max_seq_len=sz.max_seq_len,
                max_new_tokens=sz.max_new_tokens,
                strategy=strategy, quantize_offload=False,
                kv_backend=kv_backend, prefill_chunk=prefill_chunk,
                iter_token_budget=iter_token_budget,
                prefix_cache=prefix_cache,
                prefill_buckets=prefill_buckets,
                prefill_pack=prefill_pack,
                prefill_pack_width=prefill_pack_width,
                spec_decode=spec_decode, spec_k=spec_k,
                warmup_compile=warmup), predictor=predictor)
            if tier is not None:
                eng.attach_tier(tier)
            return eng

        reset_request_counter()
        trace = generate_trace(TraceConfig(dataset=dataset, rate=rate,
                                           duration=1e9,
                                           max_requests=n_requests,
                                           seed=seed))
        reqs = clamp_requests(trace.requests, vocab=cfg.vocab_size,
                              min_prompt=sz.min_prompt,
                              max_prompt=sz.max_prompt,
                              max_new=sz.max_new_tokens)
        rng = np.random.default_rng(seed)
        for r in reqs:
            if rng.random() < interactive_frac:
                r.slo_class = SLOClass.INTERACTIVE

        engines = [mk_engine(i) for i in range(n_engines)]
        gw = Gateway(engines,
                     GatewayConfig(virtual_dt=virtual_dt,
                                   router_policy=router,
                                   concurrent_pump=(pump == "concurrent"),
                                   trace=bool(trace_out),
                                   metrics_interval_s=metrics_interval,
                                   heartbeat=metrics_interval is not None),
                     admission=AdmissionConfig(
                         max_queue_depth=max(8 * n_engines * max_slots, 32),
                         defer_high_watermark=4 * n_engines * max_slots,
                         ttft_target_interactive=ttft_target_interactive,
                         ttft_target_batch=ttft_target_batch,
                         ttft_miss_policy=ttft_miss_policy,
                         ttft_quantile=ttft_quantile))
        setup_s = time.perf_counter() - t0
        compile_s, hits = counter.seconds, counter.cache_hits
        counter.reset()
        t0 = time.perf_counter()
        streams = asyncio.run(gw.replay(reqs))
        run = ServeRun(reqs, gw, streams, setup_s, time.perf_counter() - t0,
                       counter.count, compile_s, hits)
    done = sum(1 for s in streams if s.finished)
    clock = "virtual" if virtual_dt is not None else f"wall/{pump}"
    placement = (" [" + ", ".join(
        device_label(dev_list[i % len(dev_list)]) for i in range(n_engines))
        + "]") if place else ""
    print(f"[gateway] {strategy}/{router} x{n_engines} engines{placement} "
          f"({clock}), {dataset}@{rate}/s: {done}/{len(reqs)} streams "
          f"finished")
    print(gw.metrics.format())
    _report_phases("gateway", run)
    if tier is not None:
        s = tier.stats
        print(f"[kv-tier] {tier.bytes / 1e6:.1f}/{tier.capacity_bytes / 1e6:.1f} MB, "
              f"{s.published_pages} pages published, {s.imports} imports "
              f"({s.imported_pages} pages, {s.hit_bytes / 1e6:.1f} MB), "
              f"{s.evicted_pages} evicted")
    if trace_out:
        _export_trace(gw.bus, gw.quality(), trace_out)
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--size", default="smoke", choices=sorted(SIZES),
                    help="'smoke': the architecture's tiny CPU config; "
                         "'chip': its published widths, depth cut to one "
                         "chip, with 2048-token lanes")
    ap.add_argument("--strategy", default="alise",
                    choices=["alise", "orca", "vllm", "alise-recompute",
                             "alise-defer"])
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--predictor", default="oracle",
                    choices=["oracle", "retrieval", "online"],
                    help="length predictor: 'oracle' (true lengths), "
                         "'retrieval' (static hashed-ngram KNN), or "
                         "'online' (hit-aware p50/p90 quantile regressor "
                         "that learns from served traffic and calibrates "
                         "its p90 coverage online)")
    ap.add_argument("--kv-backend", default="dense",
                    choices=["dense", "paged"],
                    help="device KV storage: dense slotted cache or the "
                         "paged block pool (Pallas paged-attention path)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max prompt tokens per prefill chunk (chunked, "
                         "resumable prefill; default: monolithic). Long "
                         "prompts no longer stall resident decode lanes "
                         "for a whole-prompt dispatch")
    ap.add_argument("--iter-token-budget", default=None,
                    help="scheduler token budget per iteration (decode "
                         "lane = 1 token, prefill chunk = its span; "
                         "an integer, or 'auto' to fit it from the "
                         "profiled latency model against --target-tpot; "
                         "default: unbounded)")
    ap.add_argument("--target-tpot", type=float, default=0.05,
                    help="TPOT target (s) for --iter-token-budget auto")
    ap.add_argument("--prefill-buckets", default=None, metavar="B1,B2,...",
                    help="fixed menu of prefill chunk-shape buckets "
                         "(comma-separated token counts); chunks are "
                         "rounded up to the nearest bucket (padding "
                         "masked) so serve time never dispatches a "
                         "novel shape. Default: pow2 ladder up to "
                         "--prefill-chunk when packing/warmup is on")
    ap.add_argument("--prefill-pack", action="store_true",
                    help="concatenate several short requests' prefill "
                         "chunks into one bucketed dispatch with segment "
                         "masking (greedy outputs unchanged)")
    ap.add_argument("--prefill-pack-width", type=int, default=4,
                    help="max requests per packed prefill dispatch")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile every bucketed prefill / pack / "
                         "swap / decode shape at engine startup so the "
                         "serve path never hits a JIT compile; measured "
                         "bucket costs feed the EWT latency model")
    ap.add_argument("--chunk-attn", default="masked",
                    choices=["masked", "flash"],
                    help="chunk-attention implementation: dense masked "
                         "attention or the flash_prefill Pallas "
                         "prefix-KV kernel")
    ap.add_argument("--spec-decode", action="store_true",
                    help="verify-k speculative decoding: model-free "
                         "n-gram/prefix-index drafts scored k+1 positions "
                         "at a time in one fused dispatch; outputs are "
                         "bit-identical to plain decoding (greedy and "
                         "sampled). Pair with --warmup so every k-shape "
                         "is pre-compiled")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens per decode lane per verify-k "
                         "dispatch (paged backend: must be < page size)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request shared-prefix KV cache: repeated "
                         "prompt prefixes (multi-turn chats, shared "
                         "system prompts) reuse cached KV instead of "
                         "re-prefilling; greedy outputs are unchanged")
    ap.add_argument("--kv-tier", action="store_true",
                    help="gateway mode: join every replica to one shared "
                         "host-RAM prefix pool — re-routed sessions import "
                         "a peer's prefix pages at DMA cost instead of "
                         "re-prefilling (implies --prefix-cache)")
    ap.add_argument("--tier-bytes", type=float, default=256e6,
                    help="shared tier payload capacity in bytes "
                         "(default 256e6; LRU-evicts unpinned pages)")
    ap.add_argument("--tier-quantize", action="store_true",
                    help="store tier payloads INT8 via the kv_quant path "
                         "(~2x prefixes per byte; lossy — greedy tier-"
                         "on/off bit-identity no longer holds)")
    ap.add_argument("--devices", default=None, metavar="SPEC",
                    help="gateway mode: place each engine replica on its "
                         "own JAX device, round-robin over SPEC — 'auto' "
                         "(all devices), a platform ('cpu'), or an "
                         "explicit list ('cpu:0,cpu:2' or '0,2').  Use "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for a multi-device CPU fallback")
    ap.add_argument("--gateway", action="store_true",
                    help="online mode: replay a Poisson trace through the "
                         "streaming gateway instead of a pre-built batch")
    ap.add_argument("--dataset", default="alpaca",
                    choices=["alpaca", "sharegpt"])
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--n-engines", type=int, default=2)
    ap.add_argument("--router", default="ewt",
                    choices=["ewt", "join_shortest_queue", "round_robin",
                             "prefix_ewt"])
    ap.add_argument("--interactive-frac", type=float, default=0.25)
    ap.add_argument("--wall", action="store_true",
                    help="gateway mode: serve in wall clock (default is "
                         "deterministic virtual-clock replay)")
    ap.add_argument("--pump", default="concurrent",
                    choices=["concurrent", "lockstep"],
                    help="wall-clock pump: per-engine executor tasks or the "
                         "lockstep barrier")
    ap.add_argument("--ttft-target-interactive", type=float, default=None,
                    help="TTFT SLO target (s) for interactive traffic; "
                         "enables TTFT-attainment admission")
    ap.add_argument("--ttft-target-batch", type=float, default=None)
    ap.add_argument("--ttft-miss-policy", default="shed",
                    choices=["shed", "defer", "observe"])
    ap.add_argument("--ttft-quantile", type=float, default=0.5,
                    help="backlog quantile the TTFT admission gate prices: "
                         "0.5 = the routing/EWT p50 surface (default); "
                         "0.9 = the calibrated-P90 remaining-length "
                         "surface (conservative exactly when the length "
                         "predictor is uncertain; needs --predictor "
                         "online to differ from 0.5)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the full request lifecycle on the "
                         "observability event bus and export a Chrome/"
                         "Perfetto trace JSON to PATH after serving "
                         "(plus PATH.prom gauge dump and scheduler-"
                         "quality highlights)")
    ap.add_argument("--metrics-interval", type=float, default=None,
                    metavar="SECONDS",
                    help="gateway mode: print a one-line metrics "
                         "heartbeat every SECONDS (gauges are sampled "
                         "at the same cadence when tracing)")
    args = ap.parse_args()
    use_compile_cache()
    buckets = None
    if args.prefill_buckets:
        buckets = tuple(sorted({int(x) for x in
                                args.prefill_buckets.split(",") if x.strip()}))
    budget = args.iter_token_budget
    if budget is not None and budget != "auto":
        budget = int(budget)
    if args.gateway and budget == "auto":
        print("[serve] --iter-token-budget auto is batch-mode only "
              "(per-replica profiling); gateway runs unbounded")
    if args.kv_tier and not args.prefix_cache:
        args.prefix_cache = True       # the tier extends the prefix cache
    if (args.kv_tier or args.devices) and not args.gateway:
        print("[serve] --kv-tier/--devices are gateway-mode only "
              "(batch mode runs a single replica); ignoring")
    if args.gateway:
        serve_gateway(args.arch, args.strategy, args.dataset, args.rate,
                      args.n_requests, args.n_engines, args.max_slots,
                      router=args.router,
                      interactive_frac=args.interactive_frac,
                      predictor_kind=args.predictor,
                      virtual_dt=None if args.wall else 0.05,
                      pump=args.pump,
                      ttft_target_interactive=args.ttft_target_interactive,
                      ttft_target_batch=args.ttft_target_batch,
                      ttft_miss_policy=args.ttft_miss_policy,
                      ttft_quantile=args.ttft_quantile,
                      kv_backend=args.kv_backend,
                      prefill_chunk=args.prefill_chunk,
                      iter_token_budget=(None if budget == "auto"
                                         else budget),
                      prefix_cache=args.prefix_cache,
                      prefill_buckets=buckets,
                      prefill_pack=args.prefill_pack,
                      prefill_pack_width=args.prefill_pack_width,
                      warmup=args.warmup,
                      chunk_attn=args.chunk_attn,
                      spec_decode=args.spec_decode, spec_k=args.spec_k,
                      kv_tier=args.kv_tier, tier_bytes=args.tier_bytes,
                      tier_quantize=args.tier_quantize,
                      devices=args.devices,
                      trace_out=args.trace_out,
                      metrics_interval=args.metrics_interval,
                      size=args.size)
    else:
        if args.metrics_interval is not None:
            print("[serve] --metrics-interval is gateway-mode only "
                  "(batch serving prints a final summary)")
        serve(args.arch, args.strategy, args.n_requests, args.max_slots,
              predictor_kind=args.predictor, kv_backend=args.kv_backend,
              prefill_chunk=args.prefill_chunk,
              iter_token_budget=budget, prefix_cache=args.prefix_cache,
              prefill_buckets=buckets, prefill_pack=args.prefill_pack,
              prefill_pack_width=args.prefill_pack_width,
              warmup=args.warmup, chunk_attn=args.chunk_attn,
              spec_decode=args.spec_decode, spec_k=args.spec_k,
              target_tpot=args.target_tpot, trace_out=args.trace_out,
              size=args.size)


if __name__ == "__main__":
    main()
