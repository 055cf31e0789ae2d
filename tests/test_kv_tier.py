"""Cluster-wide KV tier + multi-device replica placement.

Pins the PR's acceptance invariants:
  * ``HostKVTier`` is a refcounted shared pool: in-flight imports pin
    their pages against byte-capacity LRU eviction, re-publishing a
    known prefix copies nothing, quantized payloads are marked lossy;
  * a session re-routed to a peer replica imports the peer's published
    prefix pages through the tier and produces bit-identical greedy
    outputs tier-on vs tier-off, on both KV backends (the quantized
    tier gives up bit-identity like INT8 swap — its importers are
    marked lossy and never publish back);
  * swap-in re-links still-indexed prefix pages instead of forking
    private duplicates (regression: forced offload/upload round-trip);
  * multi-replica drain with a live shared tier leaves zero refcount
    leaks on every replica and zero pinned tier pages;
  * routing/admission prices a tier import as DMA, not prefill;
  * ``tier_import``/``tier_evict`` land in the Perfetto export and the
    Prometheus text, tier occupancy in ``gauges()``;
  * replica placement resolves ``--devices`` specs and commits params
    per device (multi-device paths run under CI's
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` job).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.engine import EngineConfig, ServingEngine
from repro.core.predictor import OraclePredictor
from repro.core.request import Request, reset_request_counter
from repro.distributed.placement import (assign_devices, available_devices,
                                         device_label, place_params)
from repro.models.model import Model
from repro.serving.kv_tier import HostKVTier, SimKVTier
from repro.serving.observability import (EventBus, render_prometheus,
                                         to_chrome_trace)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("granite-3-8b")
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ------------------------------------------------------------ tier units

def _page(seed: int, pg: int = 4):
    rng = np.random.default_rng(seed)
    shape = (1, pg, 1, 2)                      # (layers, page, heads, dim)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def test_tier_publish_probe_acquire_release():
    tier = HostKVTier(1e6, page_size=4)
    toks = list(range(100, 112))               # 3 full pages
    fetched = []

    def fetch(i):
        fetched.append(i)
        return _page(i)

    assert tier.publish(toks, 12, fetch) == 3
    assert fetched == [0, 1, 2]
    assert tier.probe(toks) == 12
    assert tier.probe(toks, cap=7) == 4        # full-page floor under cap
    hit, nbytes = tier.probe_bytes(toks)
    assert hit == 12 and nbytes == tier.bytes > 0

    # re-publishing a cluster-known prefix copies nothing
    assert tier.publish(toks, 12, fetch) == 0
    assert fetched == [0, 1, 2]

    h = tier.acquire(toks, 8)
    assert h is not None and h.tokens == 8 and not h.lossy
    assert tier.pinned_pages() == 2
    mats = h.materialize(np.float32)
    np.testing.assert_array_equal(mats[0][0], _page(0)[0])
    np.testing.assert_array_equal(mats[1][1], _page(1)[1])
    h.release()
    h.release()                                # idempotent
    assert tier.pinned_pages() == 0
    assert tier.stats.imports == 1 and tier.stats.imported_pages == 2
    assert tier.stats.hit_bytes > 0
    g = tier.gauges()
    assert g["tier_pages"] == 3.0 and g["tier_imports_total"] == 1.0
    assert 0 < g["tier_utilization"] <= 1.0
    assert tier.drop_all() == 3 and tier.bytes == 0


def test_tier_byte_cap_lru_skips_pinned():
    page_bytes = sum(a.nbytes for a in _page(0))
    tier = HostKVTier(2 * page_bytes, page_size=4)
    a = list(range(0, 8))
    tier.publish(a, 8, lambda i: _page(i))
    h = tier.acquire(a, 8)                     # pin both pages of A
    b = list(range(50, 58))
    tier.publish(b, 8, lambda i: _page(10 + i))
    # over capacity, but A's pinned pages must survive the eviction sweep
    assert tier.probe(a) == 8
    assert tier.pinned_pages() == 2
    assert tier.stats.evicted_pages >= 1
    assert tier.bytes <= tier.capacity_bytes
    h.release()
    c = list(range(70, 78))
    tier.publish(c, 8, lambda i: _page(20 + i))
    assert tier.bytes <= tier.capacity_bytes
    assert len(tier.entries) <= 2


def test_tier_quantized_payloads_are_lossy_and_smaller():
    import jax.numpy as jnp

    def big(i):                        # realistic page: quant overhead
        rng = np.random.default_rng(i)  # (scales) amortizes over channels
        shape = (2, 4, 2, 32)
        return (rng.standard_normal(shape).astype(np.float32),
                rng.standard_normal(shape).astype(np.float32))

    raw = HostKVTier(1e6, page_size=4)
    q8 = HostKVTier(1e6, page_size=4, quantize=True, interpret=True)
    toks = list(range(200, 208))
    for t in (raw, q8):
        t.publish(toks, 8, big)
    assert q8.bytes < raw.bytes
    h = q8.acquire(toks, 8)
    assert h.lossy
    k0 = np.asarray(h.materialize(jnp.float32)[0][0])
    np.testing.assert_allclose(k0, big(0)[0], atol=0.1)
    h.release()


def test_sim_tier_hit_floor_and_import_time():
    st = SimKVTier(page_size=2, capacity_pages=4, swap_bw=100.0)
    toks = list(range(8))
    st.insert(toks, 8)
    # probe caps at len-1 (the final token is always computed), then
    # floors to full pages: 7 -> 6
    assert st.probe(toks) == 6 and st.probe(toks, cap=5) == 4
    assert st.hit(toks, cap=7) == 6            # full-page floor
    assert st.imports == 1 and st.imported_tokens == 6
    assert st.import_time(4, bytes_per_token=50.0) == pytest.approx(2.0)


# ------------------------------------------------------------- placement

def test_placement_spec_resolution():
    devs = jax.devices()
    assert available_devices(None) == devs
    assert available_devices("auto") == devs
    assert available_devices(devs[0].platform) == devs
    lbl = device_label(devs[0])
    assert lbl == f"{devs[0].platform}:{devs[0].id}"
    assert available_devices(lbl) == [devs[0]]
    assert available_devices("0") == [devs[0]]
    with pytest.raises(ValueError):
        available_devices("nonsense")
    with pytest.raises(ValueError):
        available_devices(str(len(devs)))      # index out of range
    # round-robin assignment covers every device before repeating
    assigned = assign_devices(2 * len(devs))
    assert assigned[:len(devs)] == devs and assigned[len(devs):] == devs


def test_place_params_commits_to_device():
    import jax.numpy as jnp
    dev = jax.devices()[0]
    tree = {"w": jnp.ones((2, 2)), "b": jnp.zeros((2,))}
    assert place_params(tree, None) is tree
    placed = place_params(tree, dev)
    assert placed["w"].devices() == {dev}
    assert placed["w"].committed and not tree["w"].committed


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 XLA devices (CI: "
                           "--xla_force_host_platform_device_count=4)")
def test_multi_device_replica_placement(model_and_params):
    """Real multi-device path: params committed per replica device, one
    engine per device, identical greedy outputs from each replica."""
    cfg, model, params = model_and_params
    devs = assign_devices(2, "auto")
    assert devs[0] != devs[1]
    rng = np.random.default_rng(0)
    toks = rng.integers(2, cfg.vocab_size, 12).tolist()
    outs = []
    for dev in devs:
        eng = ServingEngine(model, place_params(params, dev), EngineConfig(
            max_slots=2, max_seq_len=64, max_new_tokens=8, strategy="alise",
            quantize_offload=False), predictor=OraclePredictor())
        assert eng.device == device_label(dev)
        assert eng.kv.cache["k"].devices() == {dev}   # KV follows params
        assert eng.gauges()["device_index"] == float(dev.id)
        reset_request_counter()
        r = Request(prompt_len=12, arrival_time=0.0, true_out_len=4,
                    prompt_tokens=list(toks))
        eng.serve([r])
        outs.append(list(r.output_tokens))
    assert outs[0] == outs[1], "replicas on different devices diverged"


# --------------------------------------------- cross-replica engine paths

_ENG = dict(max_slots=2, max_seq_len=160, max_new_tokens=8,
            strategy="alise", quantize_offload=False, prefill_chunk=6,
            page_size=8, prefix_cache=True)


def _mk_engine(model, params, tier=None, **kw):
    eng = ServingEngine(model, params, EngineConfig(**{**_ENG, **kw}),
                        predictor=OraclePredictor())
    if tier is not None:
        eng.attach_tier(tier)
    return eng


def test_tier_takes_kernel_mode_from_its_replicas(model_and_params):
    """The INT8 tier runs its kernels in the Pallas mode of the replicas
    attached to it, and refuses a replica whose mode differs."""
    _, model, params = model_and_params
    tier = HostKVTier(1e6, page_size=8, quantize=True)
    eng = _mk_engine(model, params, tier)
    assert tier.interpret is eng.kv.interpret is True
    with pytest.raises(ValueError, match="cannot share a tier"):
        _mk_engine(model, params,
                   HostKVTier(1e6, page_size=8, quantize=True,
                              interpret=False))


def _session(e0, e1, p1, follow, out_len=6):
    """Turn 1 on e0; turn 2 (whole conversation) re-routed to e1."""
    r1 = Request(prompt_len=len(p1), arrival_time=0.0, true_out_len=out_len,
                 prompt_tokens=list(p1))
    e0.serve([r1])
    conv = list(p1) + list(r1.output_tokens) + list(follow)
    r2 = Request(prompt_len=len(conv), arrival_time=0.0,
                 true_out_len=out_len, prompt_tokens=list(conv))
    e1.serve([r2])
    return [list(r1.output_tokens), list(r2.output_tokens)], conv


@pytest.mark.parametrize("backend_kw", [dict(), dict(kv_backend="paged")],
                         ids=["dense", "paged"])
def test_cross_replica_import_bit_identity(model_and_params, backend_kw):
    """Acceptance: a re-routed turn imports the peer's prefix through the
    shared tier and the greedy outputs are bit-identical tier-on/off."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(1)
    p1 = rng.integers(2, cfg.vocab_size, 37).tolist()
    follow = rng.integers(2, cfg.vocab_size, 5).tolist()

    def run(tier_on):
        reset_request_counter()
        tier = HostKVTier(64e6, page_size=8) if tier_on else None
        e0 = _mk_engine(model, params, tier, **backend_kw)
        e1 = _mk_engine(model, params, tier, **backend_kw)
        outs, _ = _session(e0, e1, p1, follow)
        return outs, e1, tier

    ref, e1_off, _ = run(False)
    out, e1_on, tier = run(True)
    assert out == ref, "shared KV tier changed greedy outputs"
    # structural proof of cross-replica reuse: tier-off replica 1 is cold,
    # tier-on it served the prefix from the peer's published pages
    assert e1_off.kv.prefix_stats().hit_tokens == 0
    assert e1_on.kv.prefix_stats().hit_tokens > 0
    assert tier.stats.imports >= 1 and tier.stats.published_pages >= 4
    assert tier.pinned_pages() == 0, "import handles leaked pins"


def test_upload_relinks_indexed_pages(model_and_params):
    """Regression (satellite): a swap round-trip used to fork private
    duplicates of radix-indexed prefix pages.  After upload the request's
    prefix pages must be the *same physical pages* the index holds
    (refcount 2: index + request), with only the non-indexed tail
    allocated fresh."""
    cfg, model, params = model_and_params
    e = _mk_engine(model, params, kv_backend="paged")
    rng = np.random.default_rng(2)
    prompt = rng.integers(2, cfg.vocab_size, 16).tolist()
    reset_request_counter()
    warm = Request(prompt_len=16, arrival_time=0.0, true_out_len=4,
                   prompt_tokens=list(prompt))
    e.serve([warm])                    # publishes the 2 prompt pages
    idx_pages = {n.page for n in e.kv.prefix.index.nodes}
    assert len(idx_pages) >= 2

    r = Request(prompt_len=16, arrival_time=0.0, true_out_len=16,
                prompt_tokens=list(prompt))
    t = 0.0
    e.submit(r, t)
    while len(r.output_tokens) < 4:    # resident, decoding past the prefix
        e.step(t)
        t += 0.1
    pool = e.kv.pool
    shared_before = [p for p in pool.page_table[r.req_id] if p in idx_pages]
    assert len(shared_before) == 2, "hit did not map the indexed pages"

    free_before = len(pool.free_pages)
    e._offload(r)                      # forced evict: KV to host, pages freed
    assert r.req_id not in pool.page_table
    e._upload(r)                       # swap-in: must re-match and re-link
    table = pool.page_table[r.req_id]
    relinked = [p for p in table if p in idx_pages]
    assert relinked == shared_before, \
        f"upload forked duplicates: {table} vs index {sorted(idx_pages)}"
    for p in relinked:
        assert pool.refs[p] == 2, (p, pool.refs[p])   # index + request
    # only the non-indexed tail was allocated fresh — net-zero page cost
    assert len(pool.free_pages) == free_before
    # and the round-trip is invisible to the output stream
    while not r.done:
        e.step(t)
        t += 0.1
    ref = Request(prompt_len=16, arrival_time=0.0, true_out_len=16,
                  prompt_tokens=list(prompt))
    e2 = _mk_engine(model, params, kv_backend="paged", prefix_cache=False)
    e2.serve([ref])
    assert list(r.output_tokens) == list(ref.output_tokens)


def test_multi_replica_drain_zero_refcount_leaks(model_and_params):
    """Three replicas share one tier; sessions hop across them; a
    mid-flight drain() on every replica must leave no page references
    beyond index-held pages and no pinned tier entries."""
    cfg, model, params = model_and_params
    tier = HostKVTier(64e6, page_size=8)
    engs = [_mk_engine(model, params, tier, kv_backend="paged")
            for _ in range(3)]
    rng = np.random.default_rng(3)
    reset_request_counter()
    prompts = [rng.integers(2, cfg.vocab_size, 21).tolist()
               for _ in range(3)]
    for i, p in enumerate(prompts):    # publish from each replica
        r = Request(prompt_len=len(p), arrival_time=0.0, true_out_len=4,
                    prompt_tokens=list(p))
        engs[i].serve([r])
    # re-route each conversation to the next replica, stop mid-flight
    live = []
    t = 0.0
    for i, p in enumerate(prompts):
        r = Request(prompt_len=len(p), arrival_time=0.0, true_out_len=16,
                    prompt_tokens=list(p))
        e = engs[(i + 1) % 3]
        e.submit(r, t)
        for _ in range(3):             # tier import + partial prefill/decode
            e.step(t)
            t += 0.1
        live.append(r)
    assert tier.stats.imports >= 1, "no cross-replica import happened"
    moved = [r for e in engs for r in e.drain()]
    assert len(moved) == len(live)
    for e in engs:
        pool = e.kv.pool
        assert not pool.page_table, pool.page_table
        index_pages = {n.page for n in e.kv.prefix.index.nodes}
        for page, refs in pool.refs.items():
            if page == e.kv.scratch_page:
                assert refs == 1
            else:
                assert page in index_pages and refs == 1, (page, refs)
        e.kv.prefix.drop_all()
        assert sorted(pool.free_pages + [e.kv.scratch_page]) \
            == list(range(pool.cfg.num_pages))
    assert tier.pinned_pages() == 0, "drain leaked tier pins"


def test_quantized_tier_import_is_lossy_never_republished(model_and_params):
    """INT8 tier (capacity over bit-identity, like INT8 swap): the
    importer is marked lossy, so its finish-time publish is suppressed —
    inexact KV never flows back into the exact index/tier."""
    cfg, model, params = model_and_params
    tier = HostKVTier(64e6, page_size=8, quantize=True)
    rng = np.random.default_rng(4)
    p1 = rng.integers(2, cfg.vocab_size, 37).tolist()
    follow = rng.integers(2, cfg.vocab_size, 5).tolist()
    reset_request_counter()
    e0 = _mk_engine(model, params, tier, kv_backend="paged")
    e1 = _mk_engine(model, params, tier, kv_backend="paged")
    outs, conv2 = _session(e0, e1, p1, follow)
    assert tier.stats.imports >= 1
    assert len(outs[1]) > 0
    # e1's index may hold the imported prompt pages, but nothing derived
    # from the lossy import (the generated continuation) was published
    full = conv2 + outs[1][:-1]
    assert e1.kv.prefix_probe(full) <= (len(conv2) // 8) * 8, \
        "lossy tier-imported KV leaked into the exact prefix index"
    assert tier.pinned_pages() == 0


def test_prefill_estimate_prices_tier_import_as_dma(model_and_params):
    """Router/admission pricing: a prompt the tier holds costs DMA + the
    uncached suffix, not prefill over the whole prompt — so a cold
    replica with tier access outbids its own cold estimate.  Monolithic
    pricing (``prefill_chunk=0``) exposes the difference; with chunked
    prefill only the first chunk gates either way."""
    cfg, model, params = model_and_params
    tier = HostKVTier(64e6, page_size=8)
    rng = np.random.default_rng(5)
    p1 = rng.integers(2, cfg.vocab_size, 64).tolist()
    reset_request_counter()
    e0 = _mk_engine(model, params, tier, kv_backend="paged")
    warm = Request(prompt_len=64, arrival_time=0.0, true_out_len=4,
                   prompt_tokens=list(p1))
    e0.serve([warm])
    e1 = _mk_engine(model, params, tier, kv_backend="paged",
                    prefill_chunk=0)
    assert e1.prefix_probe(p1) == 0            # locally cold
    assert tier.probe(p1, len(p1) - 1) > 0     # cluster-hot
    cold = rng.integers(2, cfg.vocab_size, 64).tolist()
    assert e1.prefill_estimate(64, p1) < e1.prefill_estimate(64, cold)
    assert e1.prefill_estimate(64, cold) == \
        pytest.approx(e1.prefill_estimate(64))


def test_tier_events_and_gauges_export(model_and_params):
    """Satellite: tier_import rides the bus into the Perfetto export and
    the Prometheus text; tier occupancy lands in replica gauges."""
    cfg, model, params = model_and_params
    tier = HostKVTier(64e6, page_size=8)
    bus = EventBus(clock="virtual")
    rng = np.random.default_rng(6)
    p1 = rng.integers(2, cfg.vocab_size, 37).tolist()
    follow = rng.integers(2, cfg.vocab_size, 5).tolist()
    reset_request_counter()
    e0 = _mk_engine(model, params, tier, kv_backend="paged")
    e1 = _mk_engine(model, params, tier, kv_backend="paged")
    e0.attach_bus(bus, "engine0")
    e1.attach_bus(bus, "engine1")
    assert tier.bus is bus                     # first replica wires it
    _session(e0, e1, p1, follow)
    kinds = {ev.kind for ev in bus.snapshot()}
    assert "tier_import" in kinds
    imports = [ev for ev in bus.snapshot() if ev.kind == "tier_import"]
    assert imports[0].replica == "engine1"
    assert imports[0].data["tokens"] > 0 and imports[0].data["bytes"] > 0

    g = e1.gauges()
    assert g["tier_imports_total"] >= 1.0
    assert g["tier_pages"] > 0 and g["tier_bytes"] > 0
    assert "tier_dma_imports_total" in g       # DMA-queue accounting
    bus.gauge(g, replica="engine1", t=1.0)

    # Perfetto: tier_import exports as an instant on the replica lane
    obj = to_chrome_trace(bus)
    names = {ev["name"] for ev in obj["traceEvents"]}
    assert "tier_import" in names
    # Prometheus: gauge lines + per-kind counters
    text = render_prometheus(bus)
    assert "alise_tier_bytes" in text
    assert 'kind="tier_import"' in text

    # eviction events: shrink a tiny tier to force tier_evict onto the bus
    synth_bytes = sum(a.nbytes for a in _page(0, pg=8))
    small = HostKVTier(2 * synth_bytes, page_size=8)
    small.bus = bus
    toks = rng.integers(2, cfg.vocab_size, 48).tolist()
    small.publish(toks, 48, lambda i: _page(i, pg=8))
    assert small.stats.evicted_pages >= 1
    assert "tier_evict" in {ev.kind for ev in bus.snapshot()}


# --------------------------------------------------------- simulator twin

def test_cluster_sim_shared_tier_imports():
    """core.cluster: one SimKVTier instance shared by every replica; a
    repeat prompt routed to a different replica imports instead of
    re-prefilling, and the run completes."""
    from repro.core.cluster import ClusterConfig, ClusterRouter
    from repro.core.predictor import OraclePredictor as OP
    from repro.core.request import reset_request_counter as rrc
    from repro.core.trace import SyntheticTrace, TraceConfig
    rrc()
    cfg = ClusterConfig(n_replicas=2, router="round_robin", kv_tier=True,
                        prefix_cache=True, tier_bytes=1e9)
    cr = ClusterRouter(cfg, OP())
    assert cr.tier is not None
    assert cr.replicas[0].sim.tier is cr.replicas[1].sim.tier is cr.tier
    cr.scale_up(1)
    assert cr.replicas[2].sim.tier is cr.tier

    rng = np.random.default_rng(0)
    prompt = rng.integers(2, 1000, 64).tolist()
    reqs = []
    for i in range(4):                 # same prompt, staggered arrivals:
        reqs.append(Request(            # round-robin spreads the repeats
            prompt_len=64, arrival_time=2.0 * i, true_out_len=8,
            prompt_tokens=list(prompt)))
    trace = SyntheticTrace(requests=reqs, cfg=TraceConfig(rate=1.0))
    res = cr.run(trace, tick=0.5)
    assert res.completed == 4
    assert cr.tier.imports >= 1, "no replica imported the shared prefix"


def test_sim_tier_off_matches_legacy_exactly():
    """kv_tier=False must leave the simulator's schedule untouched."""
    from repro.core.simulator import run_sim
    a = run_sim(duration=8.0, rate=4.0, prefix_cache=True, seed=3)
    b = run_sim(duration=8.0, rate=4.0, prefix_cache=True, seed=3,
                kv_tier=False)
    assert a.completed == b.completed
    assert a.normalized_latency == pytest.approx(b.normalized_latency)


def test_sim_tier_on_runs_and_counts():
    from repro.core.simulator import run_sim
    res = run_sim(duration=10.0, rate=4.0, prefix_cache=True, kv_tier=True,
                  seed=3)
    assert res.completed > 0
