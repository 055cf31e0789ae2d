"""Paper Fig. 2 (motivation): end-to-end latency, FCFS vs ALISE speculative
scheduling, OPT-13B on ShareGPT with rising request rates.

Plus ``hol/prefill_interleave/*``: the execution-level head-of-line story —
a long-prompt arrival lands on an engine with resident decode lanes, served
monolithic vs chunked (token-budgeted IterationPlan).  Reports decode-lane
TPOT p99 (the stall a whole-prompt prefill dispatch inflicts on resident
lanes), the long prompt's TTFT, and decode tok/s, on both KV backends;
greedy outputs are asserted bit-identical chunked-vs-monolithic.

Plus ``hol/shared_prefix/*``: the cross-request shared-prefix KV cache —
N sessions x M turns over a common system prompt, dense vs paged, cache
on vs off.  Asserts bit-identity (on/off and across backends), zero page
refcount leaks after drain, and (full sizes) a >= 2x TTFT p50 win on
cache-hit turns.

Plus ``hol/spec_decode/*``: speculative verify-k decoding on a
regeneration workload — a served batch is re-sent and the radix draft
source replays the published continuation out of the shared-prefix page
index (paged backend, prefix cache on).  Times the decode phase only,
alternating spec-off/on passes; asserts greedy bit-identity, zero
serve-time recompiles, and (full sizes) >= 1.5x decode tok/s.

Reading the numbers on the 2-core CI box: the paged backend shows the
chunked TPOT-p99 win clearly (~2x); on the dense backend the smoke model
is so small that per-dispatch XLA-CPU overhead (full-cache output copies,
no buffer donation on CPU) rivals the prefill compute itself, so the
dense ratio sits near 1x and is load-noisy — the compute-bound regime
that motivates chunking grows with model size and context.
"""
from __future__ import annotations

import time

from benchmarks.common import emit, note, pick
from repro.core.simulator import run_sim

RATES = (0.5, 1.0, 2.0, 3.0, 4.0, 5.0)


def run_prefill_interleave(arch: str = "granite-3-8b") -> dict:
    """Real-engine interleaving benchmark: monolithic vs chunked prefill."""
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.core.request import Request, reset_request_counter
    from repro.models.model import Model

    cfg = get_smoke_config(arch)
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    long_prompt = pick(160, 40)
    out_res = pick(48, 10)
    chunk = pick(16, 8)
    max_seq = 256
    n_res = 3

    def mk_reqs():
        reset_request_counter()
        rng = np.random.default_rng(0)
        reqs = [Request(prompt_len=8, arrival_time=0.0, true_out_len=out_res,
                        prompt_tokens=rng.integers(
                            2, cfg.vocab_size, 8).tolist())
                for _ in range(n_res)]
        reqs.append(Request(
            prompt_len=long_prompt, arrival_time=0.0, true_out_len=4,
            prompt_tokens=rng.integers(
                2, cfg.vocab_size, long_prompt).tolist()))
        return reqs

    backends = {"dense": dict(),
                "paged": dict(kv_backend="paged", page_size=16)}
    modes = {"mono": dict(),
             "chunked": dict(prefill_chunk=chunk,
                             iter_token_budget=chunk + 2 * n_res)}
    results: dict = {}
    tokens_of: dict = {}
    for bname, bkw in backends.items():
        for mode, mkw in modes.items():
            eng = ServingEngine(model, params, EngineConfig(
                max_slots=8, max_seq_len=max_seq, max_new_tokens=out_res,
                strategy="alise", quantize_offload=False, **bkw, **mkw),
                predictor=OraclePredictor())
            # warm the jit caches (prefill buckets + fused decode)
            eng.serve(mk_reqs())
            reqs = mk_reqs()
            long_r = reqs[-1]
            eng.stream_events = True
            events = []
            first_long = [None]
            t0 = time.perf_counter()

            def pump(stop_fn, max_iters=20000):
                for _ in range(max_iters):
                    if stop_fn():
                        return
                    eng.step(time.perf_counter() - t0)
                    events.extend(eng.poll_events())
                    # engine event stamps are step-*start* times; observe
                    # the long prompt's first token host-side so monolithic
                    # TTFT includes the prefill dispatch it waited on
                    if first_long[0] is None and long_r.generated >= 1:
                        first_long[0] = time.perf_counter() - t0

            for r in reqs[:n_res]:
                eng.submit(r, 0.0)
            pump(lambda: all(r.generated >= 3 for r in reqs[:n_res]))
            t_arrival = time.perf_counter() - t0
            eng.submit(long_r, t_arrival)
            pump(lambda: not eng.sched.live)
            wall = time.perf_counter() - t0

            res_ids = {r.req_id for r in reqs[:n_res]}
            stamps: dict = {}
            for ev in events:
                if ev.kind == "token" and ev.req_id in res_ids:
                    stamps.setdefault(ev.req_id, []).append(ev.t)
            gaps = [b - a for ts in stamps.values()
                    for a, b in zip(ts, ts[1:])]
            tpot_p99 = float(np.percentile(gaps, 99)) if gaps else 0.0
            ttft_long = (first_long[0] or wall) - t_arrival
            toks = sum(r.generated for r in reqs)
            tok_s = toks / max(wall, 1e-9)
            results[(bname, mode)] = dict(tpot_p99=tpot_p99,
                                          ttft_long=ttft_long, tok_s=tok_s)
            tokens_of[(bname, mode)] = {r.req_id: list(r.output_tokens)
                                        for r in reqs}
            emit(f"hol/prefill_interleave/{bname}/{mode}", tpot_p99 * 1e6,
                 f"tpot_p99_ms={tpot_p99*1e3:.2f};"
                 f"ttft_long_ms={ttft_long*1e3:.2f};tok_per_s={tok_s:.1f}")
        # acceptance: greedy outputs bit-identical chunked vs monolithic
        assert tokens_of[(bname, "mono")] == tokens_of[(bname, "chunked")], \
            f"{bname}: chunked prefill changed greedy outputs"
        ratio = (results[(bname, "mono")]["tpot_p99"]
                 / max(results[(bname, "chunked")]["tpot_p99"], 1e-9))
        emit(f"hol/prefill_interleave/{bname}/tpot_p99_improvement", 0.0,
             f"{ratio:.2f}x")
        note(f"[prefill_interleave] {bname}: TPOT p99 "
             f"{results[(bname, 'mono')]['tpot_p99']*1e3:.2f}ms mono -> "
             f"{results[(bname, 'chunked')]['tpot_p99']*1e3:.2f}ms chunked "
             f"({ratio:.2f}x); long-prompt TTFT "
             f"{results[(bname, 'mono')]['ttft_long']*1e3:.1f} -> "
             f"{results[(bname, 'chunked')]['ttft_long']*1e3:.1f}ms")
    assert tokens_of[("dense", "chunked")] == tokens_of[("paged", "chunked")], \
        "chunked greedy outputs diverge across KV backends"
    return results


def run_shared_prefix(arch: str = "granite-3-8b") -> dict:
    """Shared-prefix cache benchmark: N sessions x M turns over a common
    system prompt (every turn resends the whole conversation), served on
    both KV backends with the cache on and off.  Reports per-turn TTFT
    (p50 over cache-hit turns, i.e. turns >= 2), throughput, and hit
    stats; asserts greedy bit-identity on-vs-off and across backends,
    zero refcount leaks after the pool drains, and — at full (non-smoke)
    sizes — a >= 2x TTFT p50 win on cache-hit turns."""
    import time as _time

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.core.request import Request, reset_request_counter
    from repro.models.model import Model

    cfg = get_smoke_config(arch)
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    sys_len = pick(96, 16)
    user_len = pick(12, 4)
    out_len = pick(10, 4)
    n_sessions = pick(4, 2)
    n_turns = pick(3, 2)
    max_seq = pick(384, 96)
    chunk = pick(24, 8)
    page = 8
    rng = np.random.default_rng(0)
    system = rng.integers(2, cfg.vocab_size, sys_len).tolist()
    msgs = [[rng.integers(2, cfg.vocab_size, user_len).tolist()
             for _ in range(n_turns)] for _ in range(n_sessions)]

    configs = {("dense", "off"): dict(),
               ("dense", "on"): dict(prefix_cache=True),
               ("paged", "off"): dict(kv_backend="paged"),
               ("paged", "on"): dict(kv_backend="paged", prefix_cache=True)}
    results: dict = {}
    tokens_of: dict = {}
    for (bname, cname), kw in configs.items():
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=8, max_seq_len=max_seq, max_new_tokens=out_len,
            strategy="alise", quantize_offload=False, prefill_chunk=chunk,
            page_size=page, **kw), predictor=OraclePredictor())
        # warm the jit caches off the clock — including the cache-hit path
        # (fetch-gather page buckets, stripe writes): serve a throwaway
        # session with the same turn structure but different tokens
        reset_request_counter()
        wrng = np.random.default_rng(999)
        whist = wrng.integers(2, cfg.vocab_size, sys_len + user_len).tolist()
        for _ in range(n_turns):
            wreq = Request(prompt_len=len(whist), arrival_time=0.0,
                           true_out_len=out_len, prompt_tokens=list(whist))
            eng.serve([wreq])
            whist = whist + list(wreq.output_tokens) + wrng.integers(
                2, cfg.vocab_size, user_len).tolist()
        if eng.kv.prefix is not None:
            eng.kv.prefix.drop_all()       # measured run starts cold
        reset_request_counter()
        hists = [list(system) + msgs[s][0] for s in range(n_sessions)]
        ttft_first, ttft_hit = [], []
        outs = []
        toks = 0
        t0 = _time.perf_counter()
        for turn in range(n_turns):
            reqs = [Request(prompt_len=len(h), arrival_time=0.0,
                            true_out_len=out_len, prompt_tokens=list(h))
                    for h in hists]
            eng.serve(reqs)
            for s, r in enumerate(reqs):
                (ttft_first if turn == 0 else ttft_hit).append(
                    r.first_token_time)
                outs.append(list(r.output_tokens))
                toks += r.generated
                hists[s] = hists[s] + list(r.output_tokens)
                if turn + 1 < n_turns:
                    hists[s] += msgs[s][turn + 1]
        wall = _time.perf_counter() - t0
        p50 = float(np.median(ttft_hit)) if ttft_hit else 0.0
        # cold (turn-0, guaranteed-miss) TTFT shows the cache's miss-path
        # overhead: probe + publish cost with no hit to amortize it
        cold_p50 = float(np.median(ttft_first)) if ttft_first else 0.0
        tok_s = toks / max(wall, 1e-9)
        st = eng.kv.prefix_stats()
        results[(bname, cname)] = dict(ttft_hit_p50=p50, tok_s=tok_s,
                                       ttft_cold_p50=cold_p50,
                                       stats=st.as_dict() if st else {})
        tokens_of[(bname, cname)] = outs
        emit(f"hol/shared_prefix/{bname}/{cname}", p50 * 1e6,
             f"ttft_hit_p50_ms={p50*1e3:.2f};"
             f"ttft_cold_p50_ms={cold_p50*1e3:.2f};tok_per_s={tok_s:.1f};"
             f"hit_tokens={st.hit_tokens if st else 0}")
        if bname == "paged" and cname == "on":
            # acceptance: zero refcount leaks after the pool drains —
            # every page is free, index-held (ref 1), or the scratch page
            pool = eng.kv.pool
            assert not pool.page_table, "pages leaked to dead requests"
            index_pages = {n.page for n in eng.kv.prefix.index.nodes}
            for p, refs in pool.refs.items():
                assert (p == eng.kv.scratch_page or
                        (p in index_pages and refs == 1)), (p, refs)
            eng.kv.prefix.drop_all()
            assert sorted(pool.free_pages + [eng.kv.scratch_page]) \
                == list(range(pool.cfg.num_pages)), "refcount leak"

    for bname in ("dense", "paged"):
        # acceptance: greedy outputs bit-identical with the cache on vs off
        assert tokens_of[(bname, "off")] == tokens_of[(bname, "on")], \
            f"{bname}: prefix cache changed greedy outputs"
        ratio = (results[(bname, "off")]["ttft_hit_p50"]
                 / max(results[(bname, "on")]["ttft_hit_p50"], 1e-9))
        emit(f"hol/shared_prefix/{bname}/ttft_hit_improvement", 0.0,
             f"{ratio:.2f}x")
        note(f"[shared_prefix] {bname}: hit-turn TTFT p50 "
             f"{results[(bname, 'off')]['ttft_hit_p50']*1e3:.2f}ms off -> "
             f"{results[(bname, 'on')]['ttft_hit_p50']*1e3:.2f}ms on "
             f"({ratio:.2f}x); stats {results[(bname, 'on')]['stats']}")
        if not pick(False, True):      # full sizes: assert the 2x win
            assert ratio >= 2.0, \
                f"{bname}: TTFT p50 win {ratio:.2f}x < 2x on hit turns"
    assert tokens_of[("dense", "on")] == tokens_of[("paged", "on")], \
        "prefix-cache greedy outputs diverge across KV backends"
    return results


def run_packed_prefill(arch: str = "granite-3-8b") -> dict:
    """Bucketed+packed prefill vs plain chunked on a burst of short
    prompts (the high-arrival-rate interactive regime): several requests'
    chunks ride one pre-compiled bucket dispatch, so the tail of the burst
    reaches its first token sooner.  Reports TTFT p50 / p99 and decode
    tok/s; asserts greedy bit-identity packed-vs-plain."""
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.core.request import Request, reset_request_counter
    from repro.models.model import Model

    cfg = get_smoke_config(arch)
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    # deliberately NOT pick()-scaled: the packing win lives in the
    # budget-constrained queueing regime (a real burst), and smoke-sized
    # bursts drain in one iteration where TTFT p50 is sub-ms noise
    n_reqs = 16
    out_len = 16
    chunk = 16
    budget = 48

    def mk_reqs():
        reset_request_counter()
        rng = np.random.default_rng(7)
        lens = rng.integers(4, 15, n_reqs)
        return [Request(prompt_len=int(p), arrival_time=0.0,
                        true_out_len=out_len,
                        prompt_tokens=rng.integers(
                            2, cfg.vocab_size, int(p)).tolist())
                for p in lens]

    modes = {"plain": dict(),
             "packed": dict(prefill_pack=True, warmup_compile=True)}
    results: dict = {}
    tokens_of: dict = {}
    for mode, mkw in modes.items():
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=8, max_seq_len=64, max_new_tokens=out_len,
            strategy="alise", quantize_offload=False, prefill_chunk=chunk,
            iter_token_budget=budget, **mkw), predictor=OraclePredictor())
        eng.serve(mk_reqs())                     # warm the jit caches
        reqs = mk_reqs()
        t0 = time.perf_counter()
        eng.serve(reqs)
        wall = time.perf_counter() - t0
        ttfts = np.array([r.first_token_time for r in reqs
                          if r.first_token_time is not None])
        toks = sum(r.generated for r in reqs)
        tok_s = toks / max(wall, 1e-9)
        results[mode] = dict(ttft_p50=float(np.percentile(ttfts, 50)),
                             ttft_p99=float(np.percentile(ttfts, 99)),
                             tok_s=tok_s)
        tokens_of[mode] = {r.req_id: list(r.output_tokens) for r in reqs}
        emit(f"hol/packed_prefill/{mode}",
             results[mode]["ttft_p50"] * 1e6,
             f"ttft_p50_ms={results[mode]['ttft_p50']*1e3:.2f};"
             f"ttft_p99_ms={results[mode]['ttft_p99']*1e3:.2f};"
             f"tok_per_s={tok_s:.1f}")
    assert tokens_of["packed"] == tokens_of["plain"], \
        "packed prefill changed greedy outputs"
    ratio = (results["plain"]["ttft_p50"]
             / max(results["packed"]["ttft_p50"], 1e-9))
    emit("hol/packed_prefill/ttft_p50_improvement", 0.0, f"{ratio:.2f}x")
    note(f"[packed_prefill] burst of {n_reqs} short prompts: TTFT p50 "
         f"{results['plain']['ttft_p50']*1e3:.2f}ms plain -> "
         f"{results['packed']['ttft_p50']*1e3:.2f}ms packed "
         f"({ratio:.2f}x); tok/s {results['plain']['tok_s']:.1f} -> "
         f"{results['packed']['tok_s']:.1f}")
    return results


def run_spec_decode(arch: str = "granite-3-8b") -> dict:
    """Speculative verify-k decoding on a regeneration workload: a batch of
    requests is served cold (publishing its prompt+output pages into the
    shared-prefix radix index), then the *same* requests are re-sent — the
    multi-turn / retry / replay regime where the radix draft source reads
    the published continuation straight out of the page index and drafts
    accept at high rate.  Measures the decode phase only (prefill is
    drained off the clock — the criterion is decode tok/s), alternating
    spec-off / spec-on passes so host noise lands on both sides, and
    asserts greedy bit-identity plus zero serve-time recompiles.  Full
    sizes must show >= 1.5x decode tok/s.

    The model runs float32 here: the random-init smoke checkpoint produces
    occasional *exact* bf16 logit ties, and an exact tie cannot resolve
    identically across two differently-shaped XLA programs (the (B,1)
    decode vs (B,k+1) verify dispatch), which would turn the bit-identity
    assert into a coin flip.  Real checkpoints don't emit exact ties;
    float32 makes them vanishingly rare.  Dense-backend spec rows (n-gram
    drafts, no radix index) live in e2e/spec_decode."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.core.request import Request, reset_request_counter
    from repro.models.model import Model
    from repro.utils.compile_counter import CompileCounter

    cfg = dataclasses.replace(get_smoke_config(arch),
                              param_dtype="float32",
                              compute_dtype="float32")
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    n_reqs = pick(8, 4)
    out_len = pick(128, 16)
    max_seq = pick(224, 64)
    passes = pick(4, 1)

    def mk_reqs(seed, out):
        reset_request_counter()
        rng = np.random.default_rng(seed)
        return [Request(prompt_len=12, arrival_time=0.0, true_out_len=out,
                        prompt_tokens=rng.integers(
                            2, cfg.vocab_size, 12).tolist())
                for _ in range(n_reqs)]

    def mk_engine(spec: bool) -> ServingEngine:
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=n_reqs, max_seq_len=max_seq, max_new_tokens=out_len,
            strategy="alise", quantize_offload=False, prefill_chunk=16,
            kv_backend="paged", page_size=16, prefix_cache=True,
            spec_decode=spec, spec_k=3, warmup_compile=True),
            predictor=OraclePredictor())
        eng.serve(mk_reqs(999, 4))       # generic shape warmup
        eng.serve(mk_reqs(0, out_len))   # cold pass: publishes pages
        eng.serve(mk_reqs(0, out_len))   # re-send: warms cache-hit prefill
        return eng

    def decode_pass(eng):
        """One re-send of the published batch; returns decode-phase tok/s.
        Prefill (and its first token) runs off the clock."""
        reqs = mk_reqs(0, out_len)
        t = 0.0
        for r in reqs:
            eng.submit(r, now=t)
        while any(len(r.output_tokens) == 0 for r in reqs):
            eng.step(t)
            t += 1e-3
        t0 = time.perf_counter()
        while not all(r.done for r in reqs):
            eng.step(t)
            t += 1e-3
        wall = time.perf_counter() - t0
        dtoks = sum(len(r.output_tokens) for r in reqs) - len(reqs)
        stats = dict(
            drafted=sum(r.spec_drafted for r in reqs),
            accepted=sum(r.spec_accepted for r in reqs),
            iters=sum(r.spec_iters for r in reqs),
            toks=sum(len(r.output_tokens) for r in reqs))
        return dtoks / max(wall, 1e-9), \
            [list(r.output_tokens) for r in reqs], stats

    eng_off, eng_on = mk_engine(False), mk_engine(True)
    tok_s = {"off": 0.0, "on": 0.0}
    tokens_of: dict = {}
    stats_of: dict = {}
    with CompileCounter() as counter:
        for _ in range(passes):      # alternate: noise hits both sides
            for sname, eng in (("off", eng_off), ("on", eng_on)):
                tps, toks, stats = decode_pass(eng)
                tok_s[sname] = max(tok_s[sname], tps)
                tokens_of[sname] = toks
                stats_of[sname] = stats
    assert counter.count == 0, (
        f"{counter.count} serve-time recompiles during measured "
        f"spec-decode passes: {counter.events}")
    assert tokens_of["on"] == tokens_of["off"], \
        "speculative decoding changed greedy outputs"
    results: dict = {}
    for sname in ("off", "on"):
        st = stats_of[sname]
        results[sname] = dict(tok_s=tok_s[sname], **st)
        tpi = st["toks"] / st["iters"] if st["iters"] else 1.0
        emit(f"hol/spec_decode/regen/{sname}",
             1e6 / max(tok_s[sname], 1e-9),
             f"tok_per_s={tok_s[sname]:.1f};drafted={st['drafted']};"
             f"accepted={st['accepted']};"
             f"tokens_per_iter={tpi:.2f}")
    ratio = tok_s["on"] / max(tok_s["off"], 1e-9)
    emit("hol/spec_decode/regen/speedup", 0.0, f"{ratio:.2f}x")
    st = stats_of["on"]
    note(f"[spec_decode] regen: {tok_s['off']:.1f} decode tok/s off -> "
         f"{tok_s['on']:.1f} on ({ratio:.2f}x); accepted "
         f"{st['accepted']}/{st['drafted']} drafts, "
         f"{st['toks'] / max(st['iters'], 1):.2f} tok/iter")
    if not pick(False, True):      # full sizes: assert the 1.5x win
        assert ratio >= 1.5, (
            f"spec decode {ratio:.2f}x < 1.5x decode tok/s on the "
            f"regeneration workload")
    return results


def run_predictor_quality(model: str = "opt-13b") -> dict:
    """``hol/predictor_quality/*``: how much of the static-prior ->
    oracle scheduling-quality gap the online quantile predictor closes.

    A heterogeneous alpaca+sharegpt mix (short chatty traffic interleaved
    with long-tailed generation) is served through the same ALISE
    simulator under three length predictors: *static* (constant prior —
    what a predictor-less MLFQ prices), *learned* (the online hit-aware
    p50/p90 quantile regressor, pretrained on disjoint history and
    learning on from served feedback), and *oracle* (true lengths — the
    quality ceiling).  Reports p99 E2E latency and SLO attainment
    (fraction of submitted requests finishing within a per-request
    ``5s + 50ms/token`` E2E budget) per predictor, the fraction of the
    static->oracle p99 gap the learned predictor closes (asserted >= 0.5
    at full sizes), and the learned predictor's empirical p90 coverage
    (asserted sane in every mode)."""
    import numpy as np

    from repro.core.predictor import DefaultPredictor, OraclePredictor
    from repro.core.simulator import ServingSimulator, SimConfig
    from repro.core.trace import SyntheticTrace, TraceConfig, generate_trace
    from repro.serving.prediction import OnlineQuantilePredictor

    # moderate load on purpose: saturated regimes flatten the static->
    # oracle gap into queueing noise, undersaturated ones have no gap at
    # all — at ~3 req/s over 90s the oracle's SRTF ordering is worth
    # several seconds of p99 E2E, a gap a predictor can meaningfully close
    duration = pick(90.0, 5.0)
    mix = (("alpaca", 2.0, 0), ("sharegpt", 1.0, 1))
    reqs, mix_cfg = [], None
    for ds, rate, seed in mix:
        tc = TraceConfig(dataset=ds, rate=rate, duration=duration, seed=seed)
        reqs.extend(generate_trace(tc).requests)
        mix_cfg = mix_cfg or tc
    reqs.sort(key=lambda r: r.arrival_time)
    trace = SyntheticTrace(requests=reqs, cfg=mix_cfg)

    def e2e_target(r):
        return 5.0 + 0.05 * r.true_out_len

    def mk_learned():
        hist_t, hist_l = [], []
        for ds, _, seed in mix:
            htc = TraceConfig(dataset=ds, rate=10.0, duration=1e9,
                              max_requests=pick(512, 64), seed=seed + 10_000)
            for r in generate_trace(htc).requests:
                hist_t.append(r.prompt_tokens)
                hist_l.append(r.true_out_len)
        p = OnlineQuantilePredictor(seed=0)
        p.pretrain(hist_t, np.asarray(hist_l, np.float32))
        return p

    kinds = (("static", DefaultPredictor()), ("learned", mk_learned()),
             ("oracle", OraclePredictor()))
    out: dict = {}
    for kname, pred in kinds:
        t0 = time.perf_counter()
        sim = ServingSimulator(SimConfig(model=model, strategy="alise",
                                         seed=0), trace, predictor=pred)
        res = sim.run()
        wall_us = (time.perf_counter() - t0) * 1e6
        done = res.requests
        attained = sum(1 for r in done
                       if r.e2e_latency is not None
                       and r.e2e_latency <= e2e_target(r))
        att = attained / max(len(reqs), 1)
        out[kname] = dict(p99_e2e_s=res.p99_latency, attainment=att,
                          completed=res.completed)
        emit(f"hol/predictor_quality/{kname}", wall_us,
             f"p99_e2e_ms={res.p99_latency * 1e3:.1f};"
             f"attainment={att:.3f};mean_e2e_ms={res.mean_latency * 1e3:.1f};"
             f"completed={res.completed}")
        note(f"[predictor_quality] {kname:8s}: p99 E2E "
             f"{res.p99_latency:6.2f}s, attainment {att:.3f} "
             f"({res.completed}/{len(reqs)} done)")
    learned = dict(kinds)["learned"]
    cov = learned.coverage("batch")
    pb90 = learned.pinball(0.9)
    emit("hol/predictor_quality/learned_calibration", 0.0,
         f"cov90={-1.0 if cov is None else cov:.3f};"
         f"pinball90={-1.0 if pb90 is None else pb90:.3f};"
         f"repredicts={learned.stats['repredicts']};"
         f"updates={learned.stats['updates']}")
    assert cov is not None and 0.5 <= cov <= 1.0, (
        f"learned p90 coverage {cov} is not sane — calibration broken")
    gap = out["static"]["p99_e2e_s"] - out["oracle"]["p99_e2e_s"]
    closed = ((out["static"]["p99_e2e_s"] - out["learned"]["p99_e2e_s"])
              / gap if gap > 1e-9 else 1.0)
    emit("hol/predictor_quality/gap_closed", 0.0,
         f"gap_closed={closed:.3f};static_p99_ms="
         f"{out['static']['p99_e2e_s'] * 1e3:.1f};oracle_p99_ms="
         f"{out['oracle']['p99_e2e_s'] * 1e3:.1f}")
    note(f"[predictor_quality] learned closes {closed:.1%} of the "
         f"static->oracle p99 gap ({gap:.2f}s wide)")
    if not pick(False, True):      # full sizes: the headline claim
        assert closed >= 0.5, (
            f"online predictor closes only {closed:.1%} of the "
            f"static->oracle p99 E2E gap (need >= 50%)")
    out["gap_closed"] = closed
    out["cov90"] = cov
    return out


def run(model: str = "opt-13b") -> dict:
    out = {}
    duration = pick(60.0, 6.0)
    for rate in pick(RATES, (1.0,)):
        t0 = time.perf_counter()
        fcfs = run_sim(model=model, strategy="orca", dataset="sharegpt",
                       rate=rate, duration=duration, seed=0)
        alise = run_sim(model=model, strategy="alise", dataset="sharegpt",
                        rate=rate, duration=duration, seed=0)
        wall_us = (time.perf_counter() - t0) * 1e6
        out[rate] = (fcfs.mean_latency, alise.mean_latency)
        emit(f"hol/rate{rate}", wall_us,
             f"fcfs_s={fcfs.mean_latency:.2f};alise_s={alise.mean_latency:.2f};"
             f"ratio={fcfs.mean_latency/max(alise.mean_latency,1e-9):.2f}")
        note(f"[fig2] rate={rate:4.1f} FCFS={fcfs.mean_latency:7.2f}s "
             f"ALISE={alise.mean_latency:7.2f}s "
             f"({fcfs.mean_latency/max(alise.mean_latency,1e-9):.2f}x)")
    out["prefill_interleave"] = run_prefill_interleave()
    out["shared_prefix"] = run_shared_prefix()
    out["packed_prefill"] = run_packed_prefill()
    out["spec_decode"] = run_spec_decode()
    out["predictor_quality"] = run_predictor_quality(model)
    return out


if __name__ == "__main__":
    run()
