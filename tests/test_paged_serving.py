"""Integration: PagedKVPool + Pallas paged attention = exact decode attention,
and the paged KV backend = bit-exact greedy serving.

This validates the vLLM-baseline substrate end-to-end: paged allocation,
per-token KV writes, block-table construction, attention through the kernel,
request-level snapshot/restore (the swap unit ALISE moves between tiers) —
plus the serving-level invariant: a ServingEngine on the paged backend
produces greedy outputs bit-identical to the dense slotted backend, with and
without forced preemption/swapping — and the decode page-table width that
follows the live context: its ladder, the step at a trimmed width against
the full one, and warm-up compiling every rung — and the decode step's
inputs staged as one packed buffer in one host-to-device copy.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.engine import EngineConfig, ServingEngine
from repro.core.predictor import OraclePredictor
from repro.core.quantization import kv_bytes_per_token
from repro.core.request import Request, reset_request_counter
from repro.kernels.paged_attention import (paged_attention_ref,
                                           paged_decode_attention)
from repro.models.model import Model
from repro.serving import kv_cache
from repro.serving.kv_cache import (KVBackendConfig, PagedKVBackend,
                                    PagedKVConfig, PagedKVPool,
                                    decode_table_width, decode_width_ladder,
                                    lane_buffer, unpack_lanes)
from repro.serving.observability import EventBus, render_prometheus
from repro.utils.compile_counter import CompileCounter

KEY = jax.random.PRNGKey(0)


def _fill(pool, req_id, n_tokens, layer=0, seed=1):
    rng = np.random.default_rng(seed + req_id)
    ks = rng.standard_normal((n_tokens, pool.cfg.num_kv_heads,
                              pool.cfg.head_dim)).astype(np.float32)
    vs = rng.standard_normal((n_tokens, pool.cfg.num_kv_heads,
                              pool.cfg.head_dim)).astype(np.float32)
    pool.allocate(req_id, n_tokens)
    for t in range(n_tokens):
        pool.write_tokens(req_id, layer, t, jnp.asarray(ks[t]),
                          jnp.asarray(vs[t]))
    return ks, vs


def test_paged_pool_attention_matches_dense():
    cfg = PagedKVConfig(num_pages=32, page_size=8, num_kv_heads=2,
                        head_dim=64, num_layers=1)
    pool = PagedKVPool(cfg)
    lengths = [13, 21, 5]
    dense_k, dense_v = {}, {}
    for rid, n in enumerate(lengths):
        dense_k[rid], dense_v[rid] = _fill(pool, rid, n)

    B, H = len(lengths), 4
    q = jax.random.normal(KEY, (B, H, cfg.head_dim))
    tables, lens = pool.block_table_array(list(range(B)))
    out = paged_decode_attention(q, pool.k[0], pool.v[0], tables, lens,
                                 interpret=True)

    # dense reference per request
    for rid, n in enumerate(lengths):
        k = jnp.asarray(dense_k[rid])[None]          # (1, n, KVH, d)
        v = jnp.asarray(dense_v[rid])[None]
        G = H // cfg.num_kv_heads
        qg = q[rid].reshape(cfg.num_kv_heads, G, cfg.head_dim)
        s = jnp.einsum("kgd,tkd->kgt", qg, k[0]) / (cfg.head_dim ** 0.5)
        w = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("kgt,tkd->kgd", w, v[0]).reshape(H, cfg.head_dim)
        np.testing.assert_allclose(np.asarray(out[rid]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_snapshot_restore_roundtrip_exact():
    cfg = PagedKVConfig(num_pages=16, page_size=8, num_kv_heads=2,
                        head_dim=32, num_layers=2)
    pool = PagedKVPool(cfg)
    _fill(pool, 0, 19)
    before = pool.snapshot(0)
    pool.free(0)
    assert pool.utilization() == 0.0
    pool.restore(0, before)
    after = pool.snapshot(0)
    np.testing.assert_array_equal(before["k"], after["k"])
    np.testing.assert_array_equal(before["v"], after["v"])
    assert before["tokens"] == after["tokens"]


def test_extend_allocates_new_page_on_boundary():
    cfg = PagedKVConfig(num_pages=8, page_size=4, num_kv_heads=1,
                        head_dim=8, num_layers=1)
    pool = PagedKVPool(cfg)
    pool.allocate(0, 4)                       # exactly one page
    assert len(pool.page_table[0]) == 1
    new_page = pool.extend(0)
    assert new_page is not None               # crossed the boundary
    assert len(pool.page_table[0]) == 2
    assert pool.extend(0) is None             # still inside page 2


def test_paged_kernel_parity_at_page_boundaries():
    """Kernel vs jnp oracle at sequence lengths exactly at / +-1 of
    page_size multiples — the off-by-one regime where page skipping
    (pl.when) and in-page masking interact."""
    page, maxp, KVH, H, d = 8, 4, 2, 4, 64
    num_pages = 32
    lengths = [page - 1, page, page + 1, 2 * page, 2 * page + 1, 3 * page - 1]
    B = len(lengths)
    ks = jax.random.split(KEY, 4)
    kc = jax.random.normal(ks[0], (num_pages, page, KVH, d), jnp.float32)
    vc = jax.random.normal(ks[1], (num_pages, page, KVH, d), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, d), jnp.float32)
    tables = jax.random.randint(ks[3], (B, maxp), 0, num_pages)
    lens = jnp.asarray(lengths, jnp.int32)
    out = paged_decode_attention(q, kc, vc, tables, lens, interpret=True)
    ref = paged_attention_ref(q, kc, vc, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pool_extend_free_under_swap_churn():
    """Allocator invariants under a random mix of allocate / extend /
    snapshot+free / restore: page conservation, no page shared between
    requests, lengths consistent with table sizes."""
    cfg = PagedKVConfig(num_pages=24, page_size=4, num_kv_heads=1,
                        head_dim=8, num_layers=2)
    pool = PagedKVPool(cfg)
    rng = np.random.default_rng(0)
    swapped = {}                               # rid -> snapshot
    live = []

    def check():
        used = [p for pages in pool.page_table.values() for p in pages]
        assert len(used) == len(set(used)), "page shared between requests"
        assert sorted(used + pool.free_pages) == list(range(cfg.num_pages))
        for rid, pages in pool.page_table.items():
            assert len(pages) == pool.pages_needed(pool.lengths[rid])

    for step in range(300):
        op = rng.integers(4)
        if op == 0 and len(live) + len(swapped) < 6:
            rid = int(rng.integers(1000, 2000)) * 1000 + step
            n = int(rng.integers(1, 9))
            if pool.can_allocate(n):
                _fill(pool, rid, n)
                live.append(rid)
        elif op == 1 and live:
            rid = live[rng.integers(len(live))]
            if pool.free_pages or pool.lengths[rid] % cfg.page_size:
                pool.extend(rid)
        elif op == 2 and live:                 # swap out
            rid = live.pop(rng.integers(len(live)))
            swapped[rid] = pool.snapshot(rid)
            pool.free(rid)
        elif op == 3 and swapped:              # swap in
            rid = next(iter(swapped))
            snap = swapped[rid]
            if pool.can_allocate(snap["tokens"]):
                pool.restore(rid, swapped.pop(rid))
                live.append(rid)
                after = pool.snapshot(rid)
                np.testing.assert_array_equal(snap["k"], after["k"])
        check()
    for rid in live:
        pool.free(rid)
    assert len(pool.free_pages) == cfg.num_pages
    assert pool.utilization() == 0.0


# ---------------------------------------------------- engine-level parity

@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("granite-3-8b")
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _mk_requests(cfg, outs, prompt_lens):
    reset_request_counter()
    rng = np.random.default_rng(3)
    return [Request(prompt_len=p, arrival_time=0.0, true_out_len=o,
                    prompt_tokens=rng.integers(2, cfg.vocab_size, p).tolist())
            for p, o in zip(prompt_lens, outs)]


# prompts exactly at / +-1 of the page_size=8 boundary
_PROMPTS = (7, 8, 9, 15, 16, 17)
_OUTS = (40, 40, 3, 3, 3, 3)


def _dense_reference(cfg, model, params):
    reqs = _mk_requests(cfg, _OUTS, _PROMPTS)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=8, max_seq_len=64, max_new_tokens=48, strategy="vllm",
        quantize_offload=False), predictor=OraclePredictor())
    eng.serve(reqs)
    return {r.req_id: list(r.output_tokens) for r in reqs}


def _staged_paged_run(cfg, model, params, quant):
    """Two tight lanes + staged arrivals: forces preemption and paged
    offload/upload through the Pallas kv_quant path when quant is set."""
    bpt = kv_bytes_per_token(cfg.num_layers, cfg.num_kv_heads, cfg.hd)
    reqs = _mk_requests(cfg, _OUTS, _PROMPTS)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=48, strategy="alise",
        quantize_offload=quant, hbm_bytes=2 * 56 * bpt,
        kv_backend="paged", page_size=8), predictor=OraclePredictor())
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
    return reqs, eng


def test_paged_engine_bit_identical_to_dense(model_and_params):
    """Acceptance: greedy outputs identical across dense and paged backends
    (page-boundary prompt lengths, no preemption)."""
    cfg, model, params = model_and_params
    ref = _dense_reference(cfg, model, params)
    reqs = _mk_requests(cfg, _OUTS, _PROMPTS)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=8, max_seq_len=64, max_new_tokens=48, strategy="vllm",
        quantize_offload=False, kv_backend="paged", page_size=8),
        predictor=OraclePredictor())
    eng.serve(reqs)
    for r in reqs:
        assert ref[r.req_id] == list(r.output_tokens)


def test_paged_engine_preemption_invariance(model_and_params):
    """Acceptance: greedy outputs identical dense-unpreempted vs
    paged-under-forced-swap (page-granular offload/upload)."""
    cfg, model, params = model_and_params
    ref = _dense_reference(cfg, model, params)
    reqs, eng = _staged_paged_run(cfg, model, params, quant=False)
    assert sum(r.preempt_count for r in reqs) > 0, "no preemption forced"
    for r in reqs:
        assert ref[r.req_id] == list(r.output_tokens)


def test_paged_quantized_swap_bounded_divergence(model_and_params):
    """INT8 page offload (Pallas kv_quant kernels): token divergence stays
    bounded, everything still completes."""
    cfg, model, params = model_and_params
    ref = _dense_reference(cfg, model, params)
    reqs, eng = _staged_paged_run(cfg, model, params, quant=True)
    total = sum(len(ref[r.req_id]) for r in reqs)
    mismatched = 0
    for r in reqs:
        a, b = ref[r.req_id], list(r.output_tokens)
        mismatched += sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
    assert mismatched / total < 0.5


def test_paged_engine_kernel_impl_matches(model_and_params):
    """The Pallas paged-attention kernel path produces the same greedy
    tokens as the gather reference path."""
    cfg, model, params = model_and_params
    outs, prompts = (4, 4), (8, 9)
    by_impl = {}
    for impl in ("gather", "kernel"):
        reqs = _mk_requests(cfg, outs, prompts)
        eng = ServingEngine(model, params, EngineConfig(
            max_slots=2, max_seq_len=32, max_new_tokens=8, strategy="vllm",
            quantize_offload=False, kv_backend="paged", page_size=8,
            paged_attn_impl=impl), predictor=OraclePredictor())
        eng.serve(reqs)
        by_impl[impl] = {r.req_id: list(r.output_tokens) for r in reqs}
    assert by_impl["gather"] == by_impl["kernel"]


# ------------------------------------------------- decode page-table width

@pytest.mark.parametrize("max_seq_len,page_size", [
    (2048, 16), (64, 8), (32, 8), (160, 16), (48, 16), (8, 8)])
def test_decode_width_ladder(max_seq_len, page_size):
    """Rising rungs, each a multiple of an eighth of the full table (at
    least one page), ending at the full table."""
    ladder = decode_width_ladder(max_seq_len, page_size)
    full = max_seq_len // page_size
    step = max(1, full // 8)
    assert all(a < b for a, b in zip(ladder, ladder[1:]))
    assert ladder[-1] == full
    assert all(w % step == 0 for w in ladder[:-1])
    assert ladder[0] == min(step, full)
    if (max_seq_len, page_size) == (2048, 16):
        assert ladder == (16, 32, 48, 64, 80, 96, 112, 128)


def test_decode_table_width_covers_active_lanes():
    """The width is the smallest rung covering every lane's pages."""
    ladder = decode_width_ladder(2048, 16)
    rng = np.random.default_rng(0)
    for _ in range(200):
        pages = rng.integers(1, 129, int(rng.integers(1, 33))).tolist()
        w = decode_table_width(ladder, pages)
        assert w in ladder and w >= max(pages)
        assert all(r < max(pages) for r in ladder if r < w)
    assert decode_table_width(ladder, []) == ladder[0]


def _paged_backend(model, max_seq_len=128, page_size=8, slots=4):
    cfg = KVBackendConfig(max_slots=slots, max_seq_len=max_seq_len,
                          page_size=page_size, quantize_offload=False)
    return PagedKVBackend(model, cfg, 64, jax.devices()[0])


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8, 15])
def test_decode_width_at_page_boundaries(model_and_params, k):
    """A lane holding k*page - 1, k*page or k*page + 1 tokens feeds one
    more: it holds k, k + 1 and k + 1 pages after the extend, and the step
    takes the rung covering that (rungs of 2 pages at 16 pages a table)."""
    _, model, _ = model_and_params
    kv = _paged_backend(model)
    pg = kv.cfg.page_size
    assert kv.decode_widths == (2, 4, 6, 8, 10, 12, 14, 16)
    for rid, (tokens, pages) in enumerate(
            ((k * pg - 1, k), (k * pg, k + 1), (k * pg + 1, k + 1))):
        kv.pool.allocate(rid, tokens)
        rung = min(w for w in kv.decode_widths if w >= pages)
        assert kv.decode_width([rid]) == rung * pg
        kv.pool.extend(rid, 1)
        assert len(kv.pool.page_table[rid]) == pages
        kv.pool.free(rid)


def test_all_inactive_decode_takes_smallest_rung(model_and_params):
    _, model, params = model_and_params
    kv = _paged_backend(model)
    assert kv.decode_width([]) == kv.decode_widths[0] * kv.cfg.page_size
    B = kv.cfg.max_slots
    zeros = np.zeros((B,), np.int32)
    kv.decode(params, np.zeros((B, 1), np.int32), np.zeros((B,), bool),
              zeros, zeros, np.full((B,), 1 << 30, np.int32), zeros)
    steps = kv.decode_width_steps
    assert steps[kv.decode_widths[0] * kv.cfg.page_size] == 1
    assert sum(steps.values()) == 1


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_trimmed_decode_step_matches_full_width(model_and_params, attn_impl):
    """The fused step over page tables cut to the ladder's width returns
    the tokens, reasons and KV of the full-width step, and logits within
    float32 round-off, for random lane lengths."""
    cfg, model, params = model_and_params
    page, maxp, n_pages = 8, 16, 80
    ladder = decode_width_ladder(maxp * page, page)
    rng = np.random.default_rng(7)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    shape = (cfg.num_layers, n_pages, page, cfg.num_kv_heads, cfg.hd)
    kv = {"k": jax.random.normal(keys[0], shape, model.kv_dtype),
          "v": jax.random.normal(keys[1], shape, model.kv_dtype)}
    for cap in (5 * page, 10 * page, maxp * page - 1):
        B = 5
        lens = rng.integers(0, cap, B).astype(np.int32)
        active = rng.random(B) < 0.8
        active[0] = True
        lens[~active] = 0
        pages = -(-(lens + 1) // page)           # after the step's extend
        free = rng.permutation(np.arange(1, n_pages))
        full = np.zeros((B, maxp), np.int32)      # page 0: the scratch page
        wp = np.zeros((B,), np.int32)
        used = 0
        for b in range(B):
            if active[b]:
                full[b, :pages[b]] = free[used:used + pages[b]]
                used += pages[b]
                wp[b] = full[b, lens[b] // page]
        wo = np.where(active, lens % page, 0).astype(np.int32)
        width = decode_table_width(ladder, pages[active])
        assert width < maxp or cap == maxp * page - 1
        tokens = jnp.asarray(rng.integers(2, cfg.vocab_size, (B, 1)),
                             jnp.int32)
        gen = jnp.ones((B,), jnp.int32)
        args = (jnp.asarray(lens), jnp.asarray(wp), jnp.asarray(wo))
        lane = (jnp.asarray(active), gen, jnp.asarray(lens + 1),
                jnp.full((B,), 1 << 30, jnp.int32),
                jnp.arange(B, dtype=jnp.int32), jax.random.PRNGKey(0))
        out = {}
        for name, table in (("full", full), ("trim", full[:, :width])):
            h, _ = model.forward_paged(
                params, kv, tokens, jnp.asarray(table), wp[:, None],
                wo[:, None], jnp.asarray(lens), attn_impl=attn_impl,
                interpret=True)
            logits = model.logits(params, h[:, -1])
            tok, reason, new_kv = kv_cache.paged_decode(
                model, params, kv, tokens, jnp.asarray(table), *args, *lane,
                attn_impl=attn_impl, interpret=True,
                max_seq_len=maxp * page)
            out[name] = (np.asarray(logits), np.asarray(tok),
                         np.asarray(reason), np.asarray(new_kv["k"]))
        np.testing.assert_allclose(out["trim"][0], out["full"][0],
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(out["trim"][1:], out["full"][1:]):
            np.testing.assert_array_equal(a, b)


def _width_run(model, params, cfg, full_width: bool, **kw):
    reqs = _mk_requests(cfg, (56, 48, 40), (7, 15, 23))
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=3, max_seq_len=64, max_new_tokens=56, strategy="vllm",
        quantize_offload=False, kv_backend="paged", page_size=8, **kw),
        predictor=OraclePredictor())
    if full_width:      # every step at the full table, as before the ladder
        eng.kv.decode_widths = (eng.kv.max_pages_per_seq,)
        eng.kv.decode_width_steps = {64: 0}
    eng.serve(reqs)
    return {r.req_id: list(r.output_tokens) for r in reqs}, eng


def test_engine_across_rungs_serves_full_width_tokens(model_and_params):
    """Lanes that grow from 1 to 8 pages cross every rung and serve the
    greedy tokens of the full-width step."""
    cfg, model, params = model_and_params
    trimmed, eng = _width_run(model, params, cfg, full_width=False)
    full, ref = _width_run(model, params, cfg, full_width=True)
    assert trimmed == full
    assert sum(1 for n in eng.kv.decode_width_steps.values() if n) >= 3
    assert ref.kv.decode_width_steps[64] == len(ref.iter_times)


def test_warmup_compiles_every_decode_width(model_and_params):
    """After warm-up, a request whose context grows across every rung
    compiles nothing, and ``decode_width_steps`` (gauges and Prometheus
    text too) counts a step at each rung it crossed."""
    cfg, model, params = model_and_params
    reset_request_counter()
    req = Request(prompt_len=3, arrival_time=0.0, true_out_len=60,
                  prompt_tokens=[5, 6, 7])
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=60, strategy="alise",
        quantize_offload=False, kv_backend="paged", page_size=8,
        prefill_chunk=16, warmup_compile=True), predictor=OraclePredictor())
    steps = eng.kv.decode_width_steps
    assert set(steps) == {8 * w for w in range(1, 9)}
    assert not any(steps.values())              # warm-up steps not counted
    with CompileCounter() as counter:
        with counter.expect_no_compiles("serve across every decode width"):
            eng.serve([req])
    assert len(req.output_tokens) == 60
    assert all(steps[w] > 0 for w in steps), steps
    assert sum(steps.values()) == len(eng.iter_times)
    g = eng.gauges()
    assert all(g[f"decode_width_{w}_steps"] == n for w, n in steps.items())
    bus = EventBus()
    bus.gauge(g, replica="e0", t=0.0)
    text = render_prometheus(bus)
    assert f'alise_decode_width_64_steps{{replica="e0"}} {float(steps[64])}' \
        in text


# ---------------------------------------- decode inputs in one packed copy

_IMAX = np.iinfo(np.int32).max


def _lane_columns(case: str, rng, B: int = 5):
    """The arrays one decode step stages, in its argument order: the fed
    token(s), an optional page table (filled in place through its view),
    then the per-lane arrays, with a bool ``active`` and ``true_len`` at
    the int32 maximum on most lanes."""
    def ints(*tail):
        return rng.integers(-(1 << 30), 1 << 30, (B,) + tail).astype(
            np.int32)
    active = rng.random(B) < 0.5
    active[:2] = True, False
    true_len = np.full((B,), _IMAX, np.int32)
    true_len[1] = 7
    lanes = [active, ints(), ints(), true_len, ints()]
    if case == "lanes":
        return [ints(1)] + lanes, {}
    if case == "token_block":
        return [ints(4), ints()] + lanes, {}
    rung = {"table_rung_2": 2, "table_rung_16": 16}[case]
    return [ints(1), ints(rung), ints(), ints(), ints()] + lanes, {1: rung}


@pytest.mark.parametrize("case", ["lanes", "token_block", "table_rung_2",
                                  "table_rung_16"])
def test_lane_buffer_round_trip(case):
    """Packing a step's host arrays into one int32 buffer and unpacking it
    inside a program returns each array exactly, in its own columns: bool
    columns as bool, ``true_len`` at the int32 maximum unchanged, and a page
    table built in place through its view."""
    cols, filled = _lane_columns(case, np.random.default_rng(11))
    B = len(cols[0])
    buf, layout, views = lane_buffer(
        B, *((c.shape[1:] if i in filled else c) for i, c in enumerate(cols)))
    for i in filled:
        views[i][...] = cols[i]
    assert buf.dtype == np.int32
    assert [t for t, _ in layout] == [c.shape[1:] for c in cols]
    assert [b for _, b in layout] == [c.dtype == bool for c in cols]
    np.testing.assert_array_equal(buf, np.concatenate(
        [c.reshape(B, -1).astype(np.int32) for c in cols], axis=1))
    out = jax.jit(unpack_lanes, static_argnums=(1,))(jnp.asarray(buf),
                                                     layout)
    assert len(out) == len(cols)
    for got, want in zip(out, cols):
        assert got.shape == want.shape
        assert got.dtype == (jnp.bool_ if want.dtype == bool else jnp.int32)
        np.testing.assert_array_equal(np.asarray(got), want)
    assert int(out[-2][0]) == _IMAX          # true_len, lane 0


@pytest.mark.parametrize("backend,prompt,rung", [
    ("dense", 3, None), ("dense", 41, None),
    ("paged", 3, 8), ("paged", 41, 48)])
def test_decode_step_stages_in_one_copy(model_and_params, monkeypatch,
                                        backend, prompt, rung):
    """Every decode step of an engine makes exactly one host-to-device copy
    while staging (counted at ``jax.device_put`` and ``jnp.asarray``), on
    either backend and at two page-table rungs, and after N steps the
    ``decode_h2d_transfers`` gauge has grown by N."""
    cfg, model, params = model_and_params
    reqs = _mk_requests(cfg, (6,), (prompt,))
    kw = dict(kv_backend="paged", page_size=8) if backend == "paged" else {}
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=8, strategy="vllm",
        quantize_offload=False, warmup_compile=True, **kw),
        predictor=OraclePredictor())
    start = eng.gauges()["decode_h2d_transfers"]
    copies, staging = [], [False]

    def counting(real):
        def call(*a, **k):
            copies.append(staging[0])
            return real(*a, **k)
        return call
    monkeypatch.setattr(kv_cache.jax, "device_put",
                        counting(jax.device_put))
    monkeypatch.setattr(kv_cache.jnp, "asarray", counting(jnp.asarray))
    decode, per_step = eng.kv.decode, []

    def counted_decode(*a, **k):
        before = sum(copies)
        staging[0] = True
        try:
            return decode(*a, **k)
        finally:
            staging[0] = False
            per_step.append(sum(copies) - before)
    monkeypatch.setattr(eng.kv, "decode", counted_decode)
    eng.serve(reqs)
    assert len(reqs[0].output_tokens) == 6
    assert per_step and per_step == [1] * len(eng.iter_times)
    assert eng.gauges()["decode_h2d_transfers"] - start == len(per_step)
    if rung is not None:
        assert {w for w, n in eng.kv.decode_width_steps.items() if n} \
            == {rung}


@pytest.mark.parametrize("layout", ["one-page-dense-view", "scattered-pages"])
def test_one_forward_widths(model_and_params, layout):
    """``Model.forward_paged`` agrees with itself across widths and row
    counts, over a dense stripe cache seen as one page per slot and over a
    pool whose pages are scattered: decoding a run one token at a time
    gives the logits (and the KV) of one width-C call over it, and the
    chunk, pack-of-one and verify programs built on it agree with that
    call."""
    cfg, _, _ = model_and_params
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    model = Model(cfg, attn_chunk=32, remat=False, kv_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    P, C, Smax = 5, 6, 32
    rng = np.random.default_rng(11)
    prefix = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, P)), jnp.int32)
    run = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, C)), jnp.int32)
    if layout == "one-page-dense-view":
        B, slot, page, n_pages = 3, 1, Smax, 3
        table = np.array([[slot]], np.int32)

        def targets(pos):
            return np.full((1, len(pos)), slot, np.int32), np.array([pos])
    else:
        page, n_pages, scratch = 4, 12, 5
        pages = [7, 2, 9, 4, 11, 0, 8, 3]
        table = np.array([pages], np.int32)

        def targets(pos):
            return (np.array([[pages[p // page] for p in pos]], np.int32),
                    np.array([[p % page for p in pos]], np.int32))
    shape = (cfg.num_layers, n_pages, page, cfg.num_kv_heads, cfg.hd)
    kv0 = {"k": jnp.zeros(shape), "v": jnp.zeros(shape)}

    def forward(kv, tokens, pos0):
        wp, wo = targets(range(pos0, pos0 + tokens.shape[1]))
        return model.forward_paged(params, kv, tokens, jnp.asarray(table),
                                   jnp.asarray(wp), jnp.asarray(wo),
                                   jnp.asarray([pos0]), interpret=True)

    _, kv1 = forward(kv0, prefix, 0)
    h, kv_wide = forward(kv1, run, P)
    wide = model.logits(params, h)[0]                         # (C, V)
    kv = kv1
    for i in range(C):
        h1, kv = forward(kv, run[:, i:i + 1], P + i)
        np.testing.assert_allclose(model.logits(params, h1[:, -1])[0],
                                   wide[i], rtol=1e-5, atol=1e-5)
    for key in "kv":
        np.testing.assert_allclose(kv[key], kv_wide[key], rtol=1e-5,
                                   atol=1e-5)

    # chunk, pack of one (a dummy row and two pad columns) and verify
    wp, wo = targets(range(P, P + C))
    if layout == "one-page-dense-view":
        # the dummy row's slot B is out of range: its writes are dropped
        chunk, _, _ = kv_cache.dense_prefill(
            model, params, kv1["k"], kv1["v"], run, jnp.asarray(slot),
            jnp.asarray(P), jnp.asarray(C), interpret=True)
        packed, _, _ = kv_cache.dense_prefill(
            model, params, kv1["k"], kv1["v"],
            jnp.pad(run, ((0, 1), (0, 2))), jnp.asarray([slot, B]),
            jnp.asarray([P, 0]), jnp.asarray([C, 0]), interpret=True)
        tokens = jnp.zeros((B, C), jnp.int32).at[slot].set(run[0])
        lanes = jnp.arange(B) == slot
        cache = {**kv1, "lengths": jnp.where(lanes, P, 0)}
        s, n_emit, _, _ = kv_cache.dense_verify(
            model, params, cache, tokens, jnp.where(lanes, C - 1, 0), lanes,
            jnp.zeros((B,), jnp.int32), cache["lengths"],
            jnp.full((B,), 1 << 30, jnp.int32), jnp.arange(B),
            jax.random.PRNGKey(0), interpret=True, max_seq_len=Smax)
        s, n_emit = s[slot], n_emit[slot]
    else:
        chunk, _ = kv_cache.paged_prefill(
            model, params, kv1, run, jnp.asarray(table), jnp.asarray(wp[0]),
            jnp.asarray(wo[0]), jnp.asarray(P), jnp.asarray(C),
            interpret=True)
        _, pad_wo = targets(range(P + C, P + C + 2))

        def rows(a, fill):              # a dummy row after row 0
            return np.concatenate([a, np.full_like(a, fill)])
        packed, _ = kv_cache.paged_prefill(
            model, params, kv1, jnp.pad(run, ((0, 1), (0, 2))),
            jnp.asarray(rows(table, scratch)),
            jnp.asarray(rows(np.concatenate(
                [wp, np.full_like(pad_wo, scratch)], 1), scratch)),
            jnp.asarray(rows(np.concatenate([wo, pad_wo], 1), 0)),
            jnp.asarray([P, 0]), jnp.asarray([C, 0]), interpret=True)
        s, n_emit, _, _ = kv_cache.paged_verify(
            model, params, kv1, run, jnp.asarray(table), jnp.asarray([P]),
            jnp.asarray(wp), jnp.asarray(wo), jnp.asarray([C - 1]),
            jnp.asarray([True]), jnp.asarray([0]), jnp.asarray([P]),
            jnp.asarray([1 << 30]), jnp.asarray([0]), jax.random.PRNGKey(0),
            interpret=True, max_seq_len=Smax)
        s, n_emit = s[0], n_emit[0]
    np.testing.assert_allclose(chunk[0], wide[-1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(packed[0], wide[-1], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(s, np.argmax(wide, -1))
    assert 1 <= int(n_emit) <= C
