"""Bucketed, packed, pre-compiled prefill — shape-stability contract.

Pins the PR's acceptance invariants:
  * greedy outputs bit-identical bucketed-vs-exact and packed-vs-unpacked
    on the dense AND paged KV backends (prompts shorter than the smallest
    bucket and chunks whose round-up straddles a page boundary included);
  * packs whose members are preempted mid-prefill resume and still produce
    identical tokens;
  * the flash_prefill chunk-attention path matches the masked reference;
  * after ``warmup()`` a mixed-length serve replay triggers ZERO backend
    compiles (the CI compile-count gate) and the measured bucket cost
    table feeds the EWT latency model.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.engine import EngineConfig, ServingEngine, default_bucket_menu
from repro.core.predictor import OraclePredictor
from repro.core.request import Request, reset_request_counter
from repro.models.model import Model
from repro.serving.observability import EventBus
from repro.utils.compile_counter import CompileCounter


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("granite-3-8b")
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# mixed lengths: 3 < smallest bucket (8); 9/15 round up across the
# page_size=8 boundary (9 -> 16 spans pages 0-1); 17+ needs several chunks
_PROMPTS = (3, 8, 9, 15, 17, 23, 5, 12)
_OUTS = (6, 6, 4, 4, 4, 3, 6, 4)


def _mk_requests(cfg, prompts=_PROMPTS, outs=_OUTS, seed=3):
    reset_request_counter()
    rng = np.random.default_rng(seed)
    return [Request(prompt_len=p, arrival_time=0.0, true_out_len=o,
                    prompt_tokens=rng.integers(2, cfg.vocab_size, p).tolist())
            for p, o in zip(prompts, outs)]


def _serve(cfg, model, params, prompts=_PROMPTS, outs=_OUTS, bus=None,
           **eng_kw):
    defaults = dict(max_slots=4, max_seq_len=64, max_new_tokens=16,
                    strategy="alise", quantize_offload=False)
    defaults.update(eng_kw)
    reqs = _mk_requests(cfg, prompts=prompts, outs=outs)
    eng = ServingEngine(model, params, EngineConfig(**defaults),
                        predictor=OraclePredictor())
    if bus is not None:
        eng.attach_bus(bus, "engine0")
    eng.serve(reqs)
    return {r.req_id: list(r.output_tokens) for r in reqs}, reqs, eng


def test_default_bucket_menu_pow2_ladder():
    assert default_bucket_menu(16) == (8, 16)
    assert default_bucket_menu(17) == (8, 16, 32)
    assert default_bucket_menu(1) == (8,)


def test_short_prompt_below_smallest_bucket(model_and_params):
    """A 3-token prompt still dispatches (rounded up to bucket 8) and its
    greedy output matches the exact-shape run."""
    cfg, model, params = model_and_params
    exact, _, _ = _serve(cfg, model, params, prompts=(3,), outs=(6,),
                         prefill_chunk=16)
    bucketed, reqs, _ = _serve(cfg, model, params, prompts=(3,), outs=(6,),
                               prefill_chunk=16, prefill_buckets=(8, 16))
    assert bucketed == exact
    assert all(len(r.output_tokens) == 6 for r in reqs)


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_bucketed_vs_exact_bit_identity(model_and_params, backend):
    cfg, model, params = model_and_params
    kw = dict(kv_backend=backend, prefill_chunk=16, iter_token_budget=48)
    if backend == "paged":
        kw["page_size"] = 8
    exact, _, _ = _serve(cfg, model, params, **kw)
    bucketed, _, _ = _serve(cfg, model, params,
                            prefill_buckets=(8, 16), **kw)
    assert bucketed == exact


def test_bucket_roundup_straddles_page_boundary(model_and_params):
    """A 9-token chunk rounds up to bucket 16 on the paged backend with
    page_size=8: the dispatch spans two pages while only 9 rows are real.
    The padding must never leak into allocated pages."""
    cfg, model, params = model_and_params
    kw = dict(kv_backend="paged", page_size=8, prefill_chunk=16)
    exact, _, _ = _serve(cfg, model, params, prompts=(9, 15), outs=(6, 6),
                         **kw)
    bucketed, reqs, _ = _serve(cfg, model, params, prompts=(9, 15),
                               outs=(6, 6), prefill_buckets=(8, 16), **kw)
    assert bucketed == exact
    assert all(len(r.output_tokens) == 6 for r in reqs)


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_packed_vs_unpacked_bit_identity(model_and_params, backend):
    cfg, model, params = model_and_params
    kw = dict(kv_backend=backend, prefill_chunk=16, iter_token_budget=48)
    if backend == "paged":
        kw["page_size"] = 8
    plain, _, _ = _serve(cfg, model, params, **kw)
    bus = EventBus(clock="wall")
    packed, _, _ = _serve(cfg, model, params, bus=bus,
                          prefill_pack=True, **kw)
    assert packed == plain
    packs = [e for e in bus.snapshot() if e.kind == "prefill_chunk"
             and e.data.get("pack_size", 1) > 1]
    assert packs, "no packed dispatch ever ran — packing is inert"
    assert all(e.data.get("bucket", 0) > 0 for e in packs)


def _staged_pack_run(cfg, model, params, pack: bool):
    """Two long prompts start prefilling, then shorter jobs arrive under a
    tight HBM cap: ALISE demotes the partially-prefilled residents (swap
    mid-prefill) and they later resume their remaining chunks."""
    from repro.core.quantization import kv_bytes_per_token
    bpt = kv_bytes_per_token(cfg.num_layers, cfg.num_kv_heads, cfg.hd)
    prompts = (23, 23, 9, 9, 9, 9)
    outs = (40, 40, 3, 3, 3, 3)
    reqs = _mk_requests(cfg, prompts=prompts, outs=outs)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=48, strategy="alise",
        quantize_offload=False, prefill_chunk=8, iter_token_budget=16,
        hbm_bytes=2 * 55 * bpt, prefill_pack=pack),
        predictor=OraclePredictor())
    t = 0.0
    for r in reqs[:2]:
        eng.submit(r, t)
    # 2 iterations x 16-token budget prefill 16/23 tokens of each long
    # prompt: the shorts arrive while both residents are MID-prefill
    for _ in range(2):
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
    return {r.req_id: list(r.output_tokens) for r in reqs}, reqs


def test_pack_members_preempt_mid_prefill(model_and_params):
    """Requests preempted between chunks (swapped out mid-prefill) resume
    through the packed path to identical greedy outputs."""
    cfg, model, params = model_and_params
    plain, _ = _staged_pack_run(cfg, model, params, pack=False)
    packed, reqs = _staged_pack_run(cfg, model, params, pack=True)
    assert packed == plain
    assert all(r.output_tokens for r in reqs)
    assert sum(r.preempt_count for r in reqs) > 0, (
        "scenario no longer preempts — tighten it")


def test_flash_chunk_attn_matches_masked(model_and_params):
    cfg, model, params = model_and_params
    flash = Model(cfg, attn_chunk=32, remat=False, chunk_attn_impl="flash")
    masked_out, _, _ = _serve(cfg, model, params, prefill_chunk=16,
                              prefill_buckets=(8, 16))
    flash_out, _, _ = _serve(cfg, flash, params, prefill_chunk=16,
                             prefill_buckets=(8, 16))
    assert flash_out == masked_out


def test_warmup_populates_bucket_cost_table(model_and_params):
    cfg, model, params = model_and_params
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=4, max_seq_len=64, max_new_tokens=16,
        strategy="alise", quantize_offload=False,
        prefill_chunk=16, prefill_pack=True, warmup_compile=True),
        predictor=OraclePredictor())
    assert eng.latency.bucket_costs
    for b in default_bucket_menu(16):
        assert eng.latency.bucket_costs[b] > 0.0
    # the cost table prices a bucketed chunk at its dispatch cost
    t = eng.latency.prefill_chunk_time(0, 5, bucket=8)
    assert t == pytest.approx(eng.latency.bucket_costs[8])


@pytest.mark.parametrize("backend,quant", [("dense", True),
                                           ("paged", False)])
def test_zero_compiles_after_warmup(model_and_params, backend, quant):
    """The CI compile gate: after explicit warmup, a mixed-length serve
    replay (chunked + packed + swaps + decode) must trigger ZERO backend
    compiles — every serve-time shape came from the warmed menu."""
    cfg, model, params = model_and_params
    kw = dict(kv_backend=backend, quantize_offload=quant,
              prefill_chunk=16, iter_token_budget=48,
              prefill_pack=True, warmup_compile=True)
    if backend == "paged":
        kw["page_size"] = 8
    reqs = _mk_requests(cfg)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=4, max_seq_len=64, max_new_tokens=16,
        strategy="alise", **kw), predictor=OraclePredictor())
    with CompileCounter() as counter:
        with counter.expect_no_compiles(f"serve[{backend},quant={quant}]"):
            eng.serve(reqs)
    assert all(r.output_tokens for r in reqs)


def test_zero_compiles_after_warmup_committed_replica(model_and_params):
    """A replica whose params are committed to a device (explicit
    placement) and which was built under a caller's default-device scope
    still serves with zero compiles: warmup and serving both run under the
    engine's own device scope, and the default device is part of jit's
    cache key."""
    from repro.distributed.placement import place_params
    cfg, model, params = model_and_params
    dev = jax.devices()[-1]
    with jax.default_device(dev):
        eng = ServingEngine(model, place_params(params, dev), EngineConfig(
            max_slots=4, max_seq_len=64, max_new_tokens=16, strategy="alise",
            kv_backend="paged", page_size=8, quantize_offload=False,
            prefill_chunk=16, warmup_compile=True),
            predictor=OraclePredictor())
    assert eng.kv.pool.k.devices() == {dev}
    reqs = _mk_requests(cfg)
    with CompileCounter() as counter:
        with counter.expect_no_compiles("serve[committed replica]"):
            eng.serve(reqs)
    assert all(r.output_tokens for r in reqs)


def test_scheduler_rounds_chunks_to_buckets(model_and_params):
    """Every planned chunk carries a bucket from the menu that covers its
    span, and packs only group equal-bucket chunks within the width."""
    cfg, model, params = model_and_params
    bus = EventBus(clock="wall")
    _, _, eng = _serve(cfg, model, params, bus=bus, prefill_chunk=16,
                       prefill_pack=True, iter_token_budget=48)
    menu = eng._buckets
    assert menu == default_bucket_menu(16)
    chunks = [e for e in bus.snapshot() if e.kind == "prefill_chunk"]
    assert chunks
    for e in chunks:
        b = e.data["bucket"]
        assert b in menu
        assert e.data["tokens"] <= b
        assert e.data["pack_size"] <= 4


def test_compile_counter_detach_unregisters():
    """A detached counter leaves jax's listener list and counts nothing
    more, while a counter still attached sees the same compile."""
    from jax._src import monitoring

    first = CompileCounter()
    first.detach()
    first.detach()                      # idempotent
    assert first._on_event not in monitoring.get_event_duration_listeners()
    assert first._on_hit not in monitoring.get_event_listeners()
    with CompileCounter() as second:
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert second.count >= 1 and second.seconds > 0.0
    assert first.count == 0
    assert second._on_event not in monitoring.get_event_duration_listeners()
    assert second._on_hit not in monitoring.get_event_listeners()


@pytest.mark.parametrize("env_dir", [None, "outside"])
def test_use_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (and the code then sets nothing), else to the fixed .jax_cache/
    at the repo root."""
    from repro.utils import REPO_ROOT, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = use_compile_cache()
        assert path == str(REPO_ROOT / ".jax_cache")
        assert (REPO_ROOT / "src" / "repro" / "utils").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
