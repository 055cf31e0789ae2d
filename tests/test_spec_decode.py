"""Speculative (verify-k) decoding: drafts must never change outputs.

The fused verify-k dispatch scores spec_k draft tokens plus the fed token
in one jitted call and accepts the longest exact-match prefix, so greedy
outputs must be bit-identical spec-on vs spec-off on both KV backends —
including under preemption and with the shared-prefix cache on.  For
temperature sampling the invariant is *within-program* determinism: with
the same (request, token-index) RNG keys, draft acceptance must reproduce
the token stream the verify program produces with no drafts at all (the
(B,1) decode program and the (B,K1) verify program are distinct XLA
programs whose logits differ in the last float bits, so cross-program
bitwise comparison is only meaningful for greedy argmax).

Also covers the draft sources themselves, the prefix-cache dedupe-on-
publish satellite, and cache-aware deferred release at the gateway.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.engine import EngineConfig, ServingEngine
from repro.core.predictor import OraclePredictor
from repro.core.request import Request, SLOClass, reset_request_counter
from repro.models.model import Model
from repro.serving.draft import (ChainDraftSource, DraftSource,
                                 NGramDraftSource, RadixDraftSource)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("granite-3-8b")
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n=6, seed=0, out=24):
    """Mixed-length prompts with a repeated motif so n-gram drafts hit."""
    rng = np.random.default_rng(seed)
    reset_request_counter()
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 16))
        toks = rng.integers(2, cfg.vocab_size, plen).tolist()
        reqs.append(Request(prompt_len=len(toks), arrival_time=0.0,
                            true_out_len=out, prompt_tokens=toks))
    return reqs


def _serve(model, params, cfg, *, spec, seed=0, n=6, draft=None, **kw):
    reqs = _requests(cfg, n=n, seed=seed)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=6, max_seq_len=96, max_new_tokens=48, strategy="alise",
        prefill_chunk=8, quantize_offload=False, spec_decode=spec,
        spec_k=3, **kw), predictor=OraclePredictor())
    if draft is not None and eng._spec_ok:
        eng._draft = draft
    eng.serve(reqs)
    return reqs, eng


class NullDraft(DraftSource):
    """Proposes nothing: the verify program runs with n_drafts == 0."""

    def propose(self, rid, tokens, k):
        return []


class ReplayDraft(DraftSource):
    """Oracle drafts: replays a known output stream, so acceptance is
    (nearly) total and the accept path is exercised at full width."""

    def __init__(self, outs, plens):
        self.outs, self.plens = outs, plens

    def propose(self, rid, tokens, k):
        gen = len(tokens) - self.plens[rid]
        return list(self.outs[rid][gen:gen + k])


# --------------------------------------------------------- greedy identity
@pytest.mark.parametrize("backend_kw", [
    dict(),
    dict(kv_backend="paged", page_size=8),
], ids=["dense", "paged"])
def test_spec_greedy_bit_identity(model_and_params, backend_kw):
    """Acceptance: greedy outputs bit-identical spec-on vs spec-off on both
    KV backends, with drafts actually accepted along the way."""
    cfg, model, params = model_and_params
    base, _ = _serve(model, params, cfg, spec=False, **backend_kw)
    spec, eng = _serve(model, params, cfg, spec=True, **backend_kw)
    assert eng._spec_ok
    assert [list(r.output_tokens) for r in spec] == \
        [list(r.output_tokens) for r in base]
    accepted = sum(r.spec_accepted for r in spec)
    drafted = sum(r.spec_drafted for r in spec)
    assert drafted > 0, "n-gram source never proposed a draft"
    assert accepted > 0, "no draft was ever accepted"
    # accept-rate telemetry feeds EWT: tokens/iter in [1, spec_k + 1]
    for r in spec:
        assert 1.0 <= r.spec_tokens_per_iter() <= 4.0


@pytest.mark.parametrize("backend_kw", [
    dict(),
    dict(kv_backend="paged", page_size=8),
], ids=["dense", "paged"])
def test_spec_identity_under_preemption(model_and_params, backend_kw):
    """Forced preemption mid-generation (2 lanes, staged arrivals, SRTF
    reorders) must not perturb spec-on greedy outputs: speculative
    scratch state is dropped with the lane and rebuilt on resume.

    Two assertions: spec-on vs spec-off cross-config identity, and the
    stronger within-program invariant — real drafts vs no drafts at all
    through the same verify dispatch.  The seed is pinned to a scenario
    with no *exact* bf16 logit ties: this random-init smoke model falls
    into repetitive cycles where two vocab entries tie bitwise, and an
    exact tie cannot resolve identically across two differently-shaped
    XLA programs (each breaks it with its own last-bit fusion noise) —
    real checkpoints don't produce exact ties."""
    cfg, model, params = model_and_params

    def staged(spec, draft=None):
        reqs = _requests(cfg, n=6, seed=2, out=40)
        # bimodal output lengths so SRTF actually reorders
        for i, r in enumerate(reqs):
            r.true_out_len = 40 if i < 2 else 3
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=2, max_seq_len=64, max_new_tokens=48,
            strategy="alise", quantize_offload=False, spec_decode=spec,
            spec_k=3, **backend_kw), predictor=OraclePredictor())
        if draft is not None and eng._spec_ok:
            eng._draft = draft
        t = 0.0
        for r in reqs[:2]:
            eng.submit(r, t)
        for _ in range(5):
            eng.step(t)
            t += 0.1
        for r in reqs[2:]:
            eng.submit(r, t)
        for _ in range(800):
            if not eng.sched.live:
                break
            eng.step(t)
            t += 0.1
        assert not eng.sched.live, "engine did not drain"
        return reqs

    base = staged(spec=False)
    null = staged(spec=True, draft=NullDraft())
    spec = staged(spec=True)
    assert sum(r.preempt_count for r in spec) > 0, "no preemption exercised"
    # drafts never change what the verify program emits (bitwise, always)
    assert [list(r.output_tokens) for r in spec] == \
        [list(r.output_tokens) for r in null]
    # and the whole spec path reproduces the non-speculative engine
    assert [list(r.output_tokens) for r in spec] == \
        [list(r.output_tokens) for r in base]


def test_spec_identity_with_prefix_cache(model_and_params):
    """Shared-prefix cache on (paged backend): published pages feed the
    radix draft source and the prefill fast path; outputs stay identical
    to the spec-off, cache-off reference."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(7)
    shared = rng.integers(2, cfg.vocab_size, 12).tolist()

    def mk():
        reset_request_counter()
        return [Request(prompt_len=12 + i, arrival_time=0.0,
                        true_out_len=16,
                        prompt_tokens=shared + list(range(2, 2 + i)))
                for i in range(4)]

    def run(**kw):
        reqs = mk()
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=4, max_seq_len=96, max_new_tokens=24,
            strategy="alise", prefill_chunk=8, quantize_offload=False,
            spec_k=3, **kw), predictor=OraclePredictor())
        # sequential: earlier finishes publish before later prompts prefill
        for r in reqs:
            eng.serve([r])
        return reqs, eng

    base, _ = run()
    spec, eng = run(spec_decode=True, kv_backend="paged", page_size=8,
                    prefix_cache=True)
    assert [list(r.output_tokens) for r in spec] == \
        [list(r.output_tokens) for r in base]
    assert sum(r.spec_accepted for r in spec) > 0


# --------------------------------------- temperature determinism (sat. 3)
def test_temperature_drafts_vs_no_drafts(model_and_params):
    """Verify-k with temperature: the per-(request, token-index) RNG keys
    make acceptance decisions draft-agnostic — the same verify program fed
    real n-gram drafts and fed no drafts at all must emit token-for-token
    identical streams."""
    cfg, model, params = model_and_params
    kw = dict(greedy=False, temperature=0.8, top_k=40, seed=11)
    base, _ = _serve(model, params, cfg, spec=True, draft=NullDraft(), **kw)
    spec, _ = _serve(model, params, cfg, spec=True, **kw)
    assert [list(r.output_tokens) for r in spec] == \
        [list(r.output_tokens) for r in base]
    # n-gram drafts rarely match temperature samples on a random-init
    # model; high-acceptance temperature coverage is the oracle test below
    assert sum(r.spec_drafted for r in spec) > 0


@pytest.mark.parametrize("backend_kw", [
    dict(),
    dict(kv_backend="paged", page_size=8),
], ids=["dense", "paged"])
def test_temperature_oracle_draft_replay(model_and_params, backend_kw):
    """Oracle drafts (replaying the no-draft run's own outputs) must be
    accepted at high rate and still reproduce the stream exactly — the
    strongest form of the sampling-determinism invariant, on both
    backends."""
    cfg, model, params = model_and_params
    kw = dict(greedy=False, temperature=0.8, top_k=40, seed=11, **backend_kw)
    base, _ = _serve(model, params, cfg, spec=True, draft=NullDraft(), **kw)
    outs = {r.req_id: list(r.output_tokens) for r in base}
    plens = {r.req_id: r.prompt_len for r in base}
    spec, _ = _serve(model, params, cfg, spec=True,
                     draft=ReplayDraft(outs, plens), **kw)
    assert [list(r.output_tokens) for r in spec] == \
        [outs[r.req_id] for r in spec]
    accepted = sum(r.spec_accepted for r in spec)
    assert accepted >= 20, f"oracle drafts barely accepted ({accepted})"


# ------------------------------------------------------------ compile gate
def test_no_serve_time_recompiles_with_spec(model_and_params):
    """Every spec-k shape is warmed: after engine construction (warmup on)
    a mixed-length serve with speculation on triggers zero backend
    compiles on either KV backend."""
    from repro.utils.compile_counter import CompileCounter
    cfg, model, params = model_and_params
    for bkw in (dict(), dict(kv_backend="paged", page_size=8)):
        reqs = _requests(cfg, n=6, seed=3)
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=4, max_seq_len=96, max_new_tokens=12,
            strategy="alise", prefill_chunk=8, quantize_offload=False,
            spec_decode=True, spec_k=3, warmup_compile=True, **bkw),
            predictor=OraclePredictor())
        assert eng._spec_ok and eng.latency.verify_cost is not None
        with CompileCounter() as counter:
            eng.serve(reqs)
        assert counter.count == 0, (
            f"{bkw or 'dense'}: {counter.count} serve-time recompiles with "
            f"spec decode on: {counter.events}")
        assert sum(r.spec_drafted for r in reqs) > 0


# ------------------------------------------------------------ draft sources
def test_ngram_draft_source_incremental():
    src = NGramDraftSource(max_n=3)
    toks = [5, 6, 7, 8, 5, 6, 7]
    # longest indexed suffix [5, 6, 7] last continued with 8, 5, ...
    assert src.propose(1, toks, 3) == [8, 5, 6]
    assert src.propose(1, toks + [8], 2) == [5, 6]
    # unseen suffix: no draft
    assert src.propose(1, [1, 2, 3], 3) == []
    # fewer than k available never pads
    assert src.propose(2, [9, 9], 3) == [9]
    src.release(1)
    assert 1 not in src._state


def test_radix_draft_source_continuation():
    from repro.serving.prefix_cache import RadixPageIndex
    idx = RadixPageIndex(page_size=4)
    seq = list(range(100, 116))                      # 4 full pages
    idx.insert(seq, 16, page_of=lambda i: i)
    src = RadixDraftSource(idx)
    # mid-page: the published page's tail is the draft (page-bounded)
    assert src.propose(1, seq[:6], 3) == seq[6:8]
    # page-aligned: most recent child branch continues
    assert src.propose(1, seq[:8], 4) == seq[8:12]
    # divergence predicts nothing
    assert src.propose(1, seq[:5] + [1], 3) == []
    # chain composition: radix first, n-gram fallback
    chain = ChainDraftSource(RadixDraftSource(idx), NGramDraftSource())
    assert chain.propose(1, seq[:6], 3) == seq[6:8]
    assert chain.propose(1, [7, 8, 7, 8, 7], 2) == [8, 7]


# ------------------------------------------- prefix-cache dedupe (sat. 1)
def test_publish_dedupes_concurrent_identical():
    """Two requests that prefilled the same prompt privately (neither hit
    the index) publish in turn: the second publish must adopt the already-
    indexed pages and free its duplicates — zero net page growth."""
    from repro.serving.kv_cache import PagedKVConfig, PagedKVPool
    from repro.serving.prefix_cache import PagedPrefixCache
    pool = PagedKVPool(PagedKVConfig(num_pages=16, page_size=4,
                                     num_kv_heads=1, head_dim=8,
                                     num_layers=1))
    cache = PagedPrefixCache(pool, page_size=4)
    toks = list(range(100, 112))                     # 3 full pages
    pool.allocate(1, 12)
    pool.allocate(2, 12)
    assert cache.publish(1, toks, 12) == 3
    held_before, _ = cache.held_pages()
    used_before = 16 - len(pool.free_pages)
    second = cache.publish(2, toks, 12)
    assert cache.stats.deduped_pages == 3
    # request 2 now maps the survivor pages; its private copies are freed
    assert pool.page_table[2] == pool.page_table[1]
    assert 16 - len(pool.free_pages) == used_before - 3
    assert cache.held_pages()[0] == held_before
    # survivor refcounts cover index + both requests
    for p in pool.page_table[1]:
        assert pool.refs[p] == 3
    pool.free(1)
    pool.free(2)
    assert cache.reclaim(16) == 3
    assert not pool.refs and len(pool.free_pages) == 16
    assert second == 0                               # no new index pages


# -------------------------------------- cache-aware release order (sat. 2)
def test_release_slack_weighs_prefix_hint():
    from repro.serving.gateway import AdmissionConfig
    from repro.serving.gateway.admission import AdmissionController
    ctrl = AdmissionController(AdmissionConfig(prefix_hint_weight=1e-3))
    reset_request_counter()
    cold = Request(prompt_len=8, arrival_time=0.0, true_out_len=4,
                   prompt_tokens=list(range(8)), slo_class=SLOClass.BATCH)
    warm = Request(prompt_len=8, arrival_time=1.0, true_out_len=4,
                   prompt_tokens=list(range(8)), slo_class=SLOClass.BATCH)
    warm.cached_prefix_hint = 64
    # no TTFT target: warm sorts ahead of cold despite arriving later
    assert ctrl.release_slack(warm, None) < ctrl.release_slack(cold, None)
    # weight 0 restores pure arrival order (both +inf-like, tie on key[0])
    ctrl0 = AdmissionController(AdmissionConfig())
    assert ctrl0.release_slack(warm, None) == ctrl0.release_slack(cold, None)


def test_gateway_releases_cache_warm_request_first(model_and_params):
    """A deferred request whose prefix got published while it was parked
    re-probes warm at release time and jumps the colder head-of-line."""
    from repro.serving.gateway import AdmissionConfig, Gateway, GatewayConfig
    cfg, model, params = model_and_params

    def mk_engine():
        return ServingEngine(model, params, EngineConfig(
            max_slots=2, max_seq_len=96, max_new_tokens=8,
            strategy="alise", prefill_chunk=8, quantize_offload=False,
            kv_backend="paged", page_size=8, prefix_cache=True),
            predictor=OraclePredictor())

    gw = Gateway([mk_engine()], GatewayConfig(virtual_dt=0.05),
                 AdmissionConfig(prefix_hint_weight=1e-3))
    rng = np.random.default_rng(5)
    shared = rng.integers(2, cfg.vocab_size, 16).tolist()
    reset_request_counter()
    # publish the shared prefix on the replica
    seed_req = Request(prompt_len=16, arrival_time=0.0, true_out_len=4,
                       prompt_tokens=list(shared))
    eng = gw.router.drivers[0].engine
    eng.serve([seed_req])
    assert eng.prefix_probe(shared) > 0
    cold = Request(prompt_len=16, arrival_time=0.0, true_out_len=4,
                   prompt_tokens=rng.integers(2, cfg.vocab_size, 16).tolist(),
                   slo_class=SLOClass.BATCH)
    warm = Request(prompt_len=16, arrival_time=1.0, true_out_len=4,
                   prompt_tokens=list(shared), slo_class=SLOClass.BATCH)
    gw.deferred.extend([cold, warm])
    order = gw._release_order(t=2.0)
    assert [r.req_id for r in order] == [warm.req_id, cold.req_id]
    assert warm.cached_prefix_hint > 0 and cold.cached_prefix_hint == 0
    # cache-oblivious config (weight 0) keeps arrival order
    gw.admission.cfg.prefix_hint_weight = 0.0
    assert [r.req_id for r in gw._release_order(t=2.0)] == \
        [cold.req_id, warm.req_id]
