"""Online gateway vs batch baseline: TTFT/TPOT percentiles and goodput as a
function of arrival rate, plus the wall-clock pump comparison.

Virtual-clock sections replay the same Poisson trace in the same virtual
clock domain (one ``virtual_dt`` per engine iteration), so latency
percentiles are directly comparable:

  * baseline — one engine, no admission control, every request batch-class
               (the closed-loop serving path with arrival gating);
  * gateway  — SLO classes (25% interactive), watermark admission, and
               EWT routing across 2 engine replicas.

The **wall** section compares the lockstep pump (one barrier round over all
replicas per iteration) against the concurrent per-engine pump (one asyncio
task per replica, steps through a thread executor) on an identical
swap-churn workload: tight HBM plus ``realtime_swap`` models the
device<->host DMA a production engine waits on during offload/upload.
Lockstep serializes those stalls across replicas; the concurrent pump
overlaps one replica's swap stall with the others' compute, so wall-clock
token throughput rises (on many-core hosts the XLA compute overlap adds
further).  Token counts are asserted identical across both pumps.

The **cluster_tier** section measures the shared host-RAM KV tier:
N sessions each prefill turn 1 on replica 0 and are then re-routed to
replica 1 for turn 2.  Tier-off, replica 1 re-prefills the whole
conversation; tier-on it imports replica 0's published prefix pages at
DMA cost and prefills only the suffix.  Asserts bit-identical greedy
outputs tier-on/off, zero refcount leaks after the replicas drain, and
zero serve-time recompiles.

``derived`` reports per-class TTFT p50/p99, TPOT p50, goodput, SLO
attainment, and the wall-clock speedup.
"""
from __future__ import annotations

import asyncio
import time

from benchmarks.common import emit, note, pick

RATES = (2.0, 6.0, 12.0)
N_REQUESTS = 24
VIRTUAL_DT = 0.05


def _mk_requests(cfg, dataset: str, rate: float, seed: int,
                 interactive: bool, n_requests: int):
    """Identical token workload on both sides (same lengths, same arrivals);
    ``interactive`` only toggles the SLO *label* on the short-output subset,
    so baseline-vs-gateway deltas measure admission+routing, not workload."""
    import numpy as np

    from repro.core.request import SLOClass, reset_request_counter
    from repro.core.trace import TraceConfig, clamp_requests, generate_trace
    reset_request_counter()
    trace = generate_trace(TraceConfig(dataset=dataset, rate=rate,
                                       duration=1e9,
                                       max_requests=n_requests, seed=seed))
    reqs = clamp_requests(trace.requests, vocab=cfg.vocab_size,
                          max_prompt=12, max_new=16)
    rng = np.random.default_rng(seed)
    for r in reqs:
        if rng.random() < 0.25:
            r.true_out_len = min(r.true_out_len, 6)   # latency-critical mix
            if interactive:
                r.slo_class = SLOClass.INTERACTIVE
    return reqs


def run_wall_pump_comparison(model, params, cfg) -> dict:
    """Lockstep vs concurrent per-engine pump, same workload, wall clock."""
    import numpy as np

    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.core.quantization import kv_bytes_per_token
    from repro.core.request import Request, reset_request_counter
    from repro.serving.gateway import Gateway, GatewayConfig

    acfg = model.cfg
    bpt = kv_bytes_per_token(acfg.num_layers, acfg.num_kv_heads, acfg.hd)
    n_reqs = pick(20, 6)
    out_len = pick(24, 8)
    reps = pick(3, 1)

    def mk_engine():
        return ServingEngine(model, params, EngineConfig(
            max_slots=4, max_seq_len=96, max_new_tokens=32,
            strategy="alise", quantize_offload=True,
            hbm_bytes=1.5 * 96 * bpt,      # ~1.5 resident jobs: swap churn
            swap_bw=1e4, realtime_swap=True),
            predictor=OraclePredictor())

    def mk_reqs():
        reset_request_counter()
        rng = np.random.default_rng(0)
        return [Request(prompt_len=32, arrival_time=round(i * 0.02, 3),
                        true_out_len=out_len,
                        prompt_tokens=rng.integers(
                            2, cfg.vocab_size, 32).tolist())
                for i in range(n_reqs)]

    # warm the jit caches outside the timed region
    warm = mk_engine()
    warm.submit(mk_reqs()[0], 0.0)
    for i in range(3):
        warm.step(i * 0.01)

    dev_ids: list = []

    def trial(concurrent: bool) -> float:
        gw = Gateway([mk_engine(), mk_engine()],
                     GatewayConfig(virtual_dt=None,
                                   concurrent_pump=concurrent))
        # per-replica placement labels for the regression-flag row: a
        # pump underperforming because both replicas share one device is
        # a different bug than executor overhead on distinct devices
        dev_ids[:] = [d.device or "?" for d in gw.router.drivers]
        t0 = time.perf_counter()
        streams = asyncio.run(gw.replay(mk_reqs()))
        wall = time.perf_counter() - t0
        toks = sum(len(s.token_values) for s in streams)
        assert toks == n_reqs * out_len, \
            f"token count drift: {toks} != {n_reqs * out_len}"
        return wall

    walls = {True: [], False: []}
    for rep in range(reps):
        order = (False, True) if rep % 2 == 0 else (True, False)
        for mode in order:
            walls[mode].append(trial(mode))
    lock = float(np.median(walls[False]))
    conc = float(np.median(walls[True]))
    toks = n_reqs * out_len
    speedup = lock / conc
    emit("gateway/wall/lockstep", lock * 1e6,
         f"tok_per_s={toks/lock:.1f};reps={reps}")
    emit("gateway/wall/concurrent", conc * 1e6,
         f"tok_per_s={toks/conc:.1f};reps={reps}")
    emit("gateway/wall/speedup", 0.0, f"{speedup:.2f}x")
    # regression flag: the concurrent pump exists to beat lockstep on this
    # swap-churn workload — if it doesn't, say so loudly in the result rows
    # and the perf artifact instead of burying a <1.0x in the table
    flagged = speedup < 1.0
    if flagged:
        emit("gateway/wall/pump_flag", 0.0,
             f"WARN:concurrent_pump_slower_than_lockstep;"
             f"speedup={speedup:.2f}x;reps={reps};"
             f"devices={','.join(dev_ids)}")
        note(f"[gateway] WARNING: concurrent pump UNDERPERFORMS lockstep "
             f"({speedup:.2f}x < 1.0x) on the swap-churn workload "
             f"(replicas on {','.join(dev_ids)}) — "
             f"executor/step-lock overhead is eating the overlap win")
    note(f"[gateway] wall pump x2 replicas (swap-churn): lockstep "
         f"{toks/lock:.1f} tok/s -> concurrent {toks/conc:.1f} tok/s "
         f"({speedup:.2f}x)")
    return {"lockstep_s": lock, "concurrent_s": conc, "speedup": speedup,
            "pump_flagged": flagged}


def run_trace_export(model, params, cfg) -> dict:
    """Traced 2-replica virtual-clock replay: export the Chrome/Perfetto
    timeline, schema-validate it, and distill the scheduler-quality
    telemetry (EWT error, queueing decomposition, length error, HoL) into
    result rows.  Smoke mode writes ``runs/trace_smoke.json`` — CI asserts
    it is non-empty and uploads it as a workflow artifact."""
    from pathlib import Path

    from benchmarks.common import is_smoke
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.serving.gateway import (AdmissionConfig, Gateway,
                                       GatewayConfig)
    from repro.serving.observability import validate_chrome_trace

    n_requests = pick(24, 10)
    rate = pick(12.0, 16.0)          # smoke: higher rate -> defers kick in

    def mk_engine():
        return ServingEngine(model, params, EngineConfig(
            max_slots=2, max_seq_len=64, max_new_tokens=16,
            strategy="alise", quantize_offload=False),
            predictor=OraclePredictor())

    reqs = _mk_requests(cfg, "alpaca", rate, seed=0, interactive=True,
                        n_requests=n_requests)
    gw = Gateway([mk_engine(), mk_engine()],
                 GatewayConfig(virtual_dt=VIRTUAL_DT, router_policy="ewt",
                               trace=True, metrics_interval_s=0.5),
                 admission=AdmissionConfig(
                     max_queue_depth=32, defer_high_watermark=6,
                     ttft_target_interactive=1.0,
                     ttft_target_batch=8.0))
    t0 = time.perf_counter()
    asyncio.run(gw.replay(reqs))
    wall_us = (time.perf_counter() - t0) * 1e6

    path = Path("runs") / ("trace_smoke.json" if is_smoke()
                           else "trace_gateway.json")
    path.parent.mkdir(parents=True, exist_ok=True)
    obj = gw.write_trace(str(path))           # strict: raises on bad schema
    evs = obj["traceEvents"]
    errs = validate_chrome_trace(obj)
    assert not errs, f"trace schema violations: {errs[:3]}"
    assert evs, "trace export produced no events"
    # per-replica lanes: pid 0 = gateway, >=1 per engine replica
    lanes = {e["pid"] for e in evs}
    assert len(lanes) >= 3, f"expected gateway + 2 replica lanes: {lanes}"
    req_spans = [e for e in evs
                 if e["ph"] == "X" and e["name"].startswith("req ")]
    assert req_spans, "no synthesized per-request lifecycle spans"

    q = gw.quality()
    emit("gateway/trace/export", wall_us,
         f"events={len(evs)};lanes={len(lanes)};"
         f"req_spans={len(req_spans)};path={path}")
    ewt, lerr = (q["estimate_error"]["ewt_signed_s"],
                 q["estimate_error"]["len_signed_tok"])
    qd = q["queueing"]
    emit("gateway/quality/ewt_err", 0.0,
         f"n={ewt['n']};mean={ewt['mean']:.3f};p50={ewt['p50']:.3f};"
         f"p90={ewt['p90']:.3f}")
    emit("gateway/quality/len_err", 0.0,
         f"n={lerr['n']};mean={lerr['mean']:.2f};p90={lerr['p90']:.2f}")
    emit("gateway/quality/queueing", 0.0,
         f"ttft_p50={qd['ttft']['p50']:.3f};"
         f"defer_p50={qd['defer']['p50']:.3f};"
         f"sched_wait_p50={qd['sched_wait']['p50']:.3f};"
         f"prefill_p50={qd['prefill_exec']['p50']:.4f};"
         f"other_p50={qd['other']['p50']:.3f}")
    emit("gateway/quality/hol", 0.0,
         f"total_s={q['hol_blocked_total_s']:.3f};"
         f"preempts={q['scheduler']['preemptions']};"
         f"demotions={q['scheduler']['demotions']}")
    note(f"[gateway/trace] {len(evs)} events, {len(lanes)} lanes, "
         f"{len(req_spans)} request spans -> {path}; EWT err p50 "
         f"{ewt['p50']:+.3f}s over n={ewt['n']}")
    return {"path": str(path), "events": len(evs), "quality": q}


def run_cluster_tier(model, params, cfg) -> dict:
    """Cross-replica prefix reuse through the shared host-RAM KV tier.

    N independent sessions: turn 1 serves on replica 0 (publishing its
    prefix pages into the tier at finish); turn 2 resends the whole
    conversation but lands on replica 1 — the re-route a cluster router
    performs under load imbalance.  Tier-off, replica 1 holds nothing
    and re-prefills every token; tier-on it imports replica 0's pages
    (upload-DMA shape, no prefill compute) and prefills only the
    uncached suffix.  Wall-clock TTFT of the re-routed turn is the
    metric; greedy outputs must be bit-identical tier-on/off, replicas
    must drain without refcount leaks (tier pins included), and the
    measured passes must trigger zero serve-time recompiles.
    """
    import numpy as np

    from benchmarks.common import is_smoke
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.core.request import Request, reset_request_counter
    from repro.serving.kv_tier import HostKVTier
    from repro.utils.compile_counter import CompileCounter

    n_sessions = pick(6, 3)
    prefix_len = pick(64, 32)          # turn-1 prompt: unique per session
    user_len, out_len = 5, 6

    rng = np.random.default_rng(42)
    prompts1 = [rng.integers(2, cfg.vocab_size,
                             prefix_len + user_len).tolist()
                for _ in range(n_sessions)]
    follows = [rng.integers(2, cfg.vocab_size, user_len).tolist()
               for _ in range(n_sessions)]
    warm1 = rng.integers(2, cfg.vocab_size, prefix_len + user_len).tolist()
    warm2 = rng.integers(2, cfg.vocab_size, user_len).tolist()

    def mk_engines(tier_on: bool):
        tier = HostKVTier(64e6, page_size=8) if tier_on else None
        engs = []
        for _ in range(2):
            eng = ServingEngine(model, params, EngineConfig(
                max_slots=2, max_seq_len=160, max_new_tokens=8,
                strategy="alise", quantize_offload=False, prefill_chunk=6,
                kv_backend="paged", page_size=8, prefix_cache=True),
                predictor=OraclePredictor())
            if tier is not None:
                eng.attach_tier(tier)
            engs.append(eng)
        return engs, tier

    def ttft_serve(eng, req):
        """Submit + step to completion; wall seconds to the first token."""
        t, ttft = 0.0, 0.0
        eng.submit(req, t)
        t0 = time.perf_counter()
        while not req.done:
            if req.output_tokens and ttft == 0.0:
                ttft = time.perf_counter() - t0
            eng.step(t)
            t += 1e-3
        return ttft or (time.perf_counter() - t0)

    def session(e0, e1, p1, follow):
        """Turn 1 on e0, turn 2 (whole conversation) re-routed to e1."""
        r1 = Request(prompt_len=len(p1), arrival_time=0.0,
                     true_out_len=out_len, prompt_tokens=list(p1))
        e0.serve([r1])
        conv = list(p1) + list(r1.output_tokens) + list(follow)
        r2 = Request(prompt_len=len(conv), arrival_time=0.0,
                     true_out_len=out_len, prompt_tokens=list(conv))
        ttft = ttft_serve(e1, r2)
        return ttft, [list(r1.output_tokens), list(r2.output_tokens)]

    results: dict = {}
    outs: dict = {}
    tiers: dict = {}
    engines: dict = {}
    for mode, tier_on in (("off", False), ("on", True)):
        reset_request_counter()
        (e0, e1), tier = mk_engines(tier_on)
        session(e0, e1, warm1, warm2)      # jit + tier-import shape warmup
        ttfts, outputs = [], []
        with CompileCounter() as counter:
            for p1, fl in zip(prompts1, follows):
                ttft, toks = session(e0, e1, p1, fl)
                ttfts.append(ttft)
                outputs.append(toks)
        assert counter.count == 0, (
            f"{counter.count} serve-time recompiles during measured "
            f"cluster_tier ({mode}) sessions: {counter.events}")
        outs[mode] = outputs
        tiers[mode] = tier
        engines[mode] = (e0, e1)
        results[mode] = {"ttft_p50": float(np.median(ttfts)),
                         "ttft_mean": float(np.mean(ttfts))}

    assert outs["on"] == outs["off"], \
        "shared KV tier changed greedy outputs"
    tier = tiers["on"]
    assert tier.stats.imports >= n_sessions, tier.stats.as_dict()
    assert tier.pinned_pages() == 0, "tier handles leaked pins after drain"
    for mode in ("off", "on"):
        for eng in engines[mode]:
            assert not eng.kv.pool.page_table, \
                f"replica pages leaked after drain (tier {mode})"

    off, on = results["off"]["ttft_p50"], results["on"]["ttft_p50"]
    speedup = off / max(on, 1e-9)
    if not is_smoke():
        assert speedup > 1.0, (
            f"tier import did not beat re-prefill on the re-routed turn: "
            f"{off*1e3:.1f}ms -> {on*1e3:.1f}ms")
    st = tier.stats
    emit("gateway/cluster_tier/off", off * 1e6,
         f"ttft_ms={off*1e3:.2f};sessions={n_sessions};"
         f"prompt={prefix_len + user_len}")
    emit("gateway/cluster_tier/on", on * 1e6,
         f"ttft_ms={on*1e3:.2f};imports={st.imports};"
         f"imported_pages={st.imported_pages};hit_bytes={st.hit_bytes};"
         f"published_pages={st.published_pages}")
    emit("gateway/cluster_tier/speedup", 0.0, f"{speedup:.2f}x")
    note(f"[gateway/cluster_tier] re-routed-turn TTFT "
         f"{off*1e3:.1f}ms -> {on*1e3:.1f}ms ({speedup:.2f}x) over "
         f"{n_sessions} sessions; {st.imported_pages} pages imported, "
         f"{st.published_pages} published, bit-identical outputs")
    results["speedup"] = speedup
    results["imports"] = st.imports
    return results


def run(arch: str = "granite-3-8b") -> dict:
    import jax

    from repro.configs import get_smoke_config
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.core.request import SLOClass
    from repro.models.model import Model
    from repro.serving.gateway import (AdmissionConfig, Gateway,
                                       GatewayConfig)

    cfg = get_smoke_config(arch)
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    rates = pick(RATES, (6.0,))
    n_requests = pick(N_REQUESTS, 8)

    def mk_engine():
        return ServingEngine(model, params, EngineConfig(
            max_slots=2, max_seq_len=64, max_new_tokens=16,
            strategy="alise", quantize_offload=False),
            predictor=OraclePredictor())

    def replay(reqs, n_engines, admission):
        gw = Gateway([mk_engine() for _ in range(n_engines)],
                     GatewayConfig(virtual_dt=VIRTUAL_DT,
                                   router_policy="ewt"),
                     admission=admission)
        t0 = time.perf_counter()
        asyncio.run(gw.replay(reqs))
        return gw.metrics, (time.perf_counter() - t0) * 1e6

    results = {}
    for rate in rates:
        # --- batch baseline: 1 engine, wide-open admission, all batch-class
        reqs = _mk_requests(cfg, "alpaca", rate, seed=0, interactive=False,
                            n_requests=n_requests)
        m_base, wall_us = replay(reqs, 1, AdmissionConfig())
        sb = m_base.per_class[SLOClass.BATCH].summary()
        emit(f"gateway/baseline/rate{rate}", wall_us,
             f"ttft_p50={sb['ttft_p50']:.3f};ttft_p99={sb['ttft_p99']:.3f};"
             f"tpot_p50={sb['tpot_p50']:.4f};"
             f"goodput={m_base.goodput():.2f};done={sb['completed']}")

        # --- gateway: 2 replicas, SLO classes, watermark + TTFT admission
        reqs = _mk_requests(cfg, "alpaca", rate, seed=0, interactive=True,
                            n_requests=n_requests)
        m_gw, wall_us = replay(reqs, 2, AdmissionConfig(
            max_queue_depth=32, defer_high_watermark=12,
            ttft_target_interactive=1.0))
        si = m_gw.per_class[SLOClass.INTERACTIVE].summary()
        sb2 = m_gw.per_class[SLOClass.BATCH].summary()
        emit(f"gateway/on/interactive/rate{rate}", wall_us,
             f"ttft_p50={si['ttft_p50']:.3f};ttft_p99={si['ttft_p99']:.3f};"
             f"tpot_p50={si['tpot_p50']:.4f};done={si['completed']};"
             f"shed={si['shed']};slo_attainment={si['slo_attainment']:.3f}")
        emit(f"gateway/on/batch/rate{rate}", wall_us,
             f"ttft_p50={sb2['ttft_p50']:.3f};ttft_p99={sb2['ttft_p99']:.3f};"
             f"goodput={m_gw.goodput():.2f};done={sb2['completed']};"
             f"shed={sb2['shed']}")
        note(f"[gateway] rate={rate:5.1f} | baseline ttft_p50="
             f"{sb['ttft_p50']:.3f}s | gw interactive ttft_p50="
             f"{si['ttft_p50']:.3f}s batch={sb2['ttft_p50']:.3f}s | "
             f"goodput {m_base.goodput():.2f} -> {m_gw.goodput():.2f} req/s | "
             f"interactive SLO {si['slo_attainment']*100:.0f}%")
        results[rate] = {"baseline": sb, "interactive": si, "batch": sb2}

    # --- traced replay: timeline export + scheduler-quality telemetry
    results["trace"] = run_trace_export(model, params, cfg)
    # --- wall-clock pump comparison (the concurrent-pump payoff)
    results["wall"] = run_wall_pump_comparison(model, params, cfg)
    # --- shared host-RAM KV tier: cross-replica prefix import
    results["cluster_tier"] = run_cluster_tier(model, params, cfg)
    return results


if __name__ == "__main__":
    run()
