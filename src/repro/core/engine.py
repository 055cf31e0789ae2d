"""Real-execution serving engine: continuous batching + ALISE scheduling over
an actual JAX model (paper §3.3).

The engine drives the same Scheduler / TieredKVManager as the simulator and
executes the scheduler's :class:`~repro.core.scheduler.IterationPlan` — a
token-budgeted list of typed work items — over a pluggable
:class:`~repro.serving.kv_cache.KVBackend`:

  * **chunked, resumable prefill**: each :class:`PrefillChunk` item runs
    ``prefill_chunk``-sized pieces of a prompt through the one paged forward
    ``Model.forward_paged`` (KV written device-side into the page pool,
    mid-page chunk boundaries included, or into the dense slot stripe as a
    one-page table), resuming from the partially-filled cache — so one long
    prompt no longer stalls every resident decode lane for a whole-prompt
    dispatch.  Families without chunk support (SSM / hybrid / enc-dec)
    fall back to the monolithic ``Model.prefill`` path;
  * decode lanes ("slots") give the batch a fixed shape => one compiled step;
    storage is either the dense slotted cache or the paged KV pool
    (``EngineConfig.kv_backend``);
  * the decode hot path is **one fused jitted dispatch per iteration**:
    embedding, layer stack, KV writes, attention, sampling (greedy or
    temperature/top-k) and EOS/length termination all run on device — the
    host syncs a single ``(tokens, reasons)`` pair instead of one
    ``int(jnp.argmax(...))`` per slot; prefill first tokens sample on the
    host through the same ``sampler.sample_and_reason``;
  * request-level KV swapping between the device cache ("HBM") and a host
    pool ("DRAM"), quantized INT8 *on device* via the Pallas kv_quant
    kernels per the paper's Eq. 8 — the host link carries the INT8 payload;
  * recompute strategy re-runs prefill over prompt+generated tokens;
  * per-iteration wall-time profiling (bounded ring buffers) used to fit
    the Eq. 3-5 latency model;
  * host spans on the profiler's clock (``serving/observability/span.py``):
    ``alise.engine.step`` holds ``alise.sched.plan``, the swaps
    (``alise.kv.offload`` / ``alise.kv.upload``), ``alise.engine.prefill``
    (or ``alise.engine.prefill_pack``), ``alise.engine.decode`` and
    ``alise.predictor.feedback``; each swap, prefill and decode span also
    times the bus event and the ring entry of its site.

Correctness invariant (tested): with greedy sampling and quantization off,
generated tokens are bit-identical no matter how jobs are preempted/swapped,
identical across the dense and paged backends, and identical chunked vs
monolithic at any chunk size / token budget.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.latency_model import LatencyModel
from repro.core.memory_manager import MemoryConfig, TieredKVManager
from repro.core.predictor import LengthPredictor, RetrievalPredictor
from repro.core.quantization import kv_bytes_per_token
from repro.core.request import KVLocation, Request, RequestState
from repro.core.scheduler import (DecodeLane, PrefillChunk, PrefillPack,
                                  Scheduler, SchedulerConfig)
from repro.distributed.placement import device_label
from repro.models.model import Model
from repro.serving.kv_cache import (DenseKVBackend, KVBackendConfig,
                                    PagedKVBackend, named_program)
from repro.serving.observability.span import span, trace_gc
from repro.serving.sampler import REASONS, sample_and_reason, token_keys


def default_bucket_menu(prefill_chunk: int) -> Tuple[int, ...]:
    """Pow2 bucket menu covering every chunk shape a ``prefill_chunk``-capped
    scheduler can emit — exactly the shapes the backend's lazy pow2
    bucketing would discover one compile at a time."""
    top = max(8, 1 << (max(int(prefill_chunk), 1) - 1).bit_length())
    menu, b = [], 8
    while b <= top:
        menu.append(b)
        b *= 2
    return tuple(menu)


@dataclass
class EngineEvent:
    """Streaming event drained via ``ServingEngine.poll_events()``.

    kinds: ``token`` (one decoded token; ``index`` is its 0-based position
    in ``output_tokens``), ``finish`` (request completed; ``reason`` one of
    eos/length/true_len/ctx), ``cancel`` (client abort).
    """
    kind: str
    req_id: int
    t: float
    token: Optional[int] = None
    index: Optional[int] = None
    reason: str = ""


@dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq_len: int = 256
    max_new_tokens: int = 128
    eos_token: int = 1
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0
    quantize_offload: bool = True
    hbm_bytes: Optional[float] = None      # default: fits ~max_slots*max_seq
    swap_bw: float = 32e9
    realtime_swap: bool = False            # wall-clock mode: enforce the
                                           # modeled swap transfer time (the
                                           # host memcpy is faster than a real
                                           # device<->host DMA, so without
                                           # this, swap stalls are under-
                                           # modeled); sleeps release the GIL,
                                           # so other replicas' pumps overlap
    kv_backend: str = "dense"              # dense | paged
    page_size: int = 16                    # paged backend page granularity
    paged_attn_impl: str = "gather"        # gather (bit-exact vs dense) |
                                           # kernel (Pallas paged attention)
    prefill_chunk: Optional[int] = None    # max prompt tokens per prefill
                                           # chunk (None = monolithic);
                                           # ignored for families without
                                           # chunked-prefill support
    iter_token_budget: Optional[int] = None  # scheduler token budget per
                                             # iteration (None = unbounded)
    prefill_buckets: Optional[Tuple[int, ...]] = None
    # fixed menu of chunk-shape buckets (sorted ascending): the scheduler
    # rounds every PrefillChunk span up to the nearest entry and warmup()
    # pre-compiles one dispatch per bucket, so serve time never sees a
    # novel prefill shape.  None = legacy lazy pow2 bucketing (warmup()
    # then derives the pow2 menu the lazy path would discover).
    prefill_pack: bool = False             # fuse equal-bucket chunks from
                                           # distinct short requests into one
                                           # PrefillPack dispatch (dense-FFN
                                           # attention models only; greedy
                                           # outputs stay bit-identical
                                           # packed-vs-unpacked)
    prefill_pack_width: int = 4            # segment rows per pack dispatch
    warmup_compile: bool = False           # run warmup() at construction
    prefix_cache: bool = False             # cross-request shared-prefix KV
                                           # cache: admit/resume matches the
                                           # longest cached prefix and starts
                                           # chunked prefill at the hit
                                           # watermark (needs chunked-prefill
                                           # support; greedy outputs stay
                                           # bit-identical on vs off)
    prefix_cache_pages: int = 0            # dense backend: private store
                                           # capacity (0 = one batch's worth)
    spec_decode: bool = False              # verify-k speculative decoding:
                                           # each decode lane carries up to
                                           # spec_k model-free draft tokens
                                           # (n-gram / radix lookup), scored
                                           # in one fused dispatch; greedy
                                           # AND temperature outputs stay
                                           # bit-identical spec on vs off
                                           # (needs a chunk-capable model
                                           # family)
    spec_k: int = 3                        # draft tokens per lane (paged
                                           # backend: must be < page_size)
    profile_window: int = 4096             # iter/prefill ring-buffer size
    strategy: str = "alise"
    n_queues: int = 4
    base_quantum: float = 0.25
    quantum_growth: float = 4.0
    age_threshold: float = 2.0
    respect_true_len: bool = True          # stop at trace's true_out_len
    device: Optional[str] = None           # placement label ("cpu:1") this
                                           # replica reports in gauges and
                                           # router attribution (default:
                                           # its params' device); the
                                           # replica runs where its params
                                           # live (placement.place_params)
    seed: int = 0


class ServingEngine:
    def __init__(self, model: Model, params, cfg: EngineConfig,
                 predictor: Optional[LengthPredictor] = None,
                 latency: Optional[LatencyModel] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        # the replica runs where its parameters live: its KV storage is
        # allocated there, every dispatch runs with it as JAX's default
        # device (see _on_device), and the KV backend takes its Pallas mode
        # from its platform (refusing one it has no kernels for)
        device = next(iter(jax.tree.leaves(params)[0].devices()))
        self.jax_device = device
        acfg = model.cfg
        bpt = kv_bytes_per_token(acfg.num_layers, acfg.num_kv_heads, acfg.hd)
        # the dense backend's prefix cache owns a private page store — a
        # real device allocation outside per-request accounting.  Charge
        # it against the budget (and grow the auto-sized default by it,
        # so enabling the cache doesn't silently shrink the slot cache).
        dense_store_bytes = 0.0
        if (cfg.prefix_cache and cfg.kv_backend == "dense"
                and model.supports_paged()):
            capacity = cfg.prefix_cache_pages or (
                cfg.max_slots * cfg.max_seq_len // cfg.page_size)
            dense_store_bytes = capacity * cfg.page_size * bpt
        hbm = cfg.hbm_bytes or (cfg.max_slots * cfg.max_seq_len * bpt
                                + dense_store_bytes)
        mem_cfg = MemoryConfig(
            hbm_bytes=hbm, dram_bytes=1e12, bytes_per_token_fp=bpt,
            swap_bw=cfg.swap_bw, quantize_offload=cfg.quantize_offload,
            reserve_policy="reserve_max" if cfg.strategy == "orca" else "ondemand",
            reserve_max_tokens=cfg.max_new_tokens,
            page_size=(cfg.page_size if cfg.kv_backend == "paged" else None))
        self.mem = TieredKVManager(mem_cfg)
        self.predictor = predictor or RetrievalPredictor(seed=cfg.seed)
        self.latency = latency or LatencyModel(t0=1e-4, alpha=1e-6, beta=1e-2)
        # chunked prefill needs backend support (attention-family
        # decoder-only); other families keep monolithic whole-prompt spans
        self._chunked_ok = model.supports_paged()
        # fixed chunk-shape menu: explicit flag wins; packing without a
        # menu derives the pow2 menu (packs group by bucket, so every
        # packable chunk needs one)
        buckets: Optional[Tuple[int, ...]] = None
        if self._chunked_ok and cfg.prefill_chunk:
            if cfg.prefill_buckets:
                buckets = tuple(sorted({int(b) for b in cfg.prefill_buckets}))
                if buckets[0] <= 0:
                    raise ValueError("prefill buckets must be positive")
            elif cfg.prefill_pack:
                buckets = default_bucket_menu(cfg.prefill_chunk)
        self._buckets = buckets
        self._pack_ok = bool(cfg.prefill_pack and buckets
                             and cfg.prefill_pack_width >= 2
                             and model.supports_prefill_pack())
        sched_cfg = SchedulerConfig(
            max_batch=cfg.max_slots, n_queues=cfg.n_queues,
            base_quantum=cfg.base_quantum, quantum_growth=cfg.quantum_growth,
            age_threshold=cfg.age_threshold, strategy=cfg.strategy,
            max_new_tokens=cfg.max_new_tokens,
            prefill_chunk=(cfg.prefill_chunk if self._chunked_ok else None),
            iter_token_budget=cfg.iter_token_budget,
            prefill_buckets=buckets, prefill_pack=self._pack_ok,
            prefill_pack_width=cfg.prefill_pack_width,
            decode_width=(cfg.spec_k + 1
                          if cfg.spec_decode and model.supports_paged()
                          else 1))
        self.sched = Scheduler(sched_cfg, self.predictor, self.latency, self.mem)

        # --- device state: the pluggable KV backend owns slots + storage
        bcfg = KVBackendConfig(
            max_slots=cfg.max_slots, max_seq_len=cfg.max_seq_len,
            eos_token=cfg.eos_token, max_new_tokens=cfg.max_new_tokens,
            greedy=cfg.greedy, temperature=cfg.temperature, top_k=cfg.top_k,
            quantize_offload=cfg.quantize_offload, page_size=cfg.page_size,
            attn_impl=cfg.paged_attn_impl,
            prefix_cache=(cfg.prefix_cache and self._chunked_ok),
            prefix_cache_pages=cfg.prefix_cache_pages, seed=cfg.seed,
            prefill_buckets=buckets,
            prefill_pack_width=cfg.prefill_pack_width,
            spec_k=cfg.spec_k if cfg.spec_decode else 0)
        with self._on_device():
            if cfg.kv_backend == "paged":
                num_pages = max(1, int(hbm // (cfg.page_size * bpt)))
                self.kv = PagedKVBackend(model, bcfg, num_pages, device)
            elif cfg.kv_backend == "dense":
                self.kv = DenseKVBackend(model, bcfg, device)
            else:
                raise ValueError(f"unknown kv_backend: {cfg.kv_backend!r}")
        # shared-prefix cache active?  (needs chunked-prefill support: a hit
        # resumes mid-prompt through the PR-4 resumable-chunk machinery)
        self._prefix_ok = self.kv.prefix is not None
        # speculative verify-k decode active?  (the backend gates on model
        # support + spec_k > 0; correctness is draft-agnostic, so the draft
        # source needs no warmup or persistence)
        self._spec_ok = self.kv.supports_spec_decode()
        self._draft = None
        if self._spec_ok:
            from repro.serving.draft import make_draft_source
            self._draft = make_draft_source(
                self.kv.prefix.index if self._prefix_ok else None)
        if self._prefix_ok:
            # cached-but-unreferenced pages are the lowest KV tier: every
            # page-shortfall path reclaims them before spilling a resident
            self.mem.register_prefix_cache(self.kv.prefix_reclaim,
                                           self.kv.prefix_pages)
            if dense_store_bytes:
                self.mem.charge_static(dense_store_bytes)
        self.host_pool: Dict[int, dict] = {}       # req_id -> offloaded KV
        # requests whose device KV went through a lossy (INT8) offload/
        # upload round-trip: their pages must never be published into the
        # prefix index — a later hit would hand other requests lossy KV
        # where cache-off recompute is exact, breaking on/off bit-identity.
        # Cleared on drop (recompute rebuilds exact KV).
        self._lossy_kv: set = set()
        self._prefill = jax.jit(named_program("alise_prefill", model.prefill))
        # bounded profiling rings: week-long gateway serves must not leak.
        # Entries lead with a time.perf_counter() timestamp so fits and
        # exported traces can be aligned post-hoc:
        #   iter_times:    (t_mono, ctx_tokens, batch, dt)
        #   prefill_times: (t_mono, n_tokens, dt)
        self.iter_times: Deque[tuple] = deque(maxlen=cfg.profile_window)
        self.prefill_times: Deque[tuple] = deque(maxlen=cfg.profile_window)
        self._generated_of: Dict[int, List[int]] = {}
        # host-side sampling uses the same per-(rid, token-index) key
        # derivation as the fused device dispatch, so every code path —
        # prefill first token, fused decode, verify-k — draws the
        # identical key stream for a given token
        self._sample_base_key = jax.random.PRNGKey(cfg.seed)
        # streaming events: recorded only when a front-end opts in (the
        # gateway sets this), so plain step() drivers that never poll don't
        # accumulate an unbounded buffer
        self.stream_events = False
        self._events: List[EngineEvent] = []       # drained by poll_events()
        # concurrency: the gateway's per-engine pump runs step() in a thread
        # executor while submit/cancel/drain/poll arrive from the event-loop
        # thread.  step_lock serializes every state mutation; the event
        # buffer gets its own lock so poll_events() never blocks on a step.
        self.step_lock = threading.RLock()
        self._events_lock = threading.Lock()
        self._backlog_cache = 0.0                  # refreshed under step_lock
        self._backlog_q90 = 0.0                    # p90 surface (admission)
        self._stall_debt = 0.0                     # modeled swap DMA seconds
        # submit mailbox: lock-free-for-the-loop intake drained at the next
        # step(), so the gateway never blocks on step_lock behind an
        # in-flight JAX iteration (symmetric to the event buffer going the
        # other way)
        self._submit_box: List = []                # [(Request, now), ...]
        self._submit_lock = threading.Lock()
        # observability: a bus is attached by the gateway (or a test/bench
        # harness) via attach_bus(); None keeps every emit site to a single
        # attribute-load + branch on the hot path
        self.bus = None
        self.name = ""                             # replica lane name
        # cluster-wide host-RAM KV tier (serving/kv_tier.py): attached by
        # the launcher/bench via attach_tier(); None keeps the tier paths
        # to one attribute-load + branch
        self.tier = None
        # placement label for attribution (gauges, router WARN rows)
        self.device = cfg.device or device_label(device)
        self._step_wall0 = 0.0                     # perf_counter at step start
        trace_gc()                                 # alise.gc spans
        if cfg.warmup_compile:
            self.warmup()

    def _on_device(self):
        """Scope for this replica's device work: arrays created inside land
        on its device, and warmup and serving trace under the same default
        device — which is part of jit's cache key, so a program warmed
        under one scope would compile again under another."""
        return jax.default_device(self.jax_device)

    # -------------------------------------------------------- observability
    def attach_bus(self, bus, name: str = "") -> None:
        """Wire an observability EventBus through every layer of this
        engine — scheduler (queue/promote/demote), prefix cache
        (hit/publish/evict/CoW) and the engine's own execution spans —
        under one replica lane ``name``."""
        self.bus = bus
        self.name = name
        self.sched.bus = bus
        self.sched.replica = name
        if self._prefix_ok:
            self.kv.prefix.bus = bus
            self.kv.prefix.replica = name
        if self.tier is not None and self.tier.bus is None:
            self.tier.bus = bus        # shared tier: first replica wires it

    def attach_tier(self, tier) -> None:
        """Join this replica to a cluster-wide host-RAM KV tier
        (serving/kv_tier.py): local prefix publishes export their pages to
        the tier, and fresh prefills import a peer replica's pages at
        admit time instead of re-prefilling.  The tier runs its INT8
        kernels in this replica's Pallas mode; the replicas of one tier
        must agree on it."""
        if tier.interpret not in (None, self.kv.interpret):
            raise ValueError("replicas that interpret Pallas kernels and "
                             "replicas that compile them cannot share a tier")
        tier.interpret = self.kv.interpret
        self.tier = tier
        if self._prefix_ok:
            self.kv.prefix.tier = tier
        if self.bus is not None and tier.bus is None:
            tier.bus = self.bus

    def _offset(self, t: float) -> float:
        """The step's clock (``t``, read when the step was asked for, in
        the gateway's or the bus's domain) minus ``time.perf_counter``, as
        of step entry: an in-step instant ``p`` on perf_counter lies at
        ``_offset(t) + p`` on that clock.  Exact in wall mode; in virtual
        mode it yields monotone within-iteration placement (a span's
        ``dur`` stays informational wall seconds)."""
        return t - self._step_wall0

    def gauges(self) -> Dict[str, float]:
        """Replica-level occupancy snapshot for periodic gauge sampling."""
        g = self.mem.gauges()
        g["queue_depth"] = float(self.queue_depth())
        g["backlog_s"] = float(self._backlog_cache)
        g["live_requests"] = float(len(self.sched.live))
        for i, d in enumerate(self.sched.queue_depths()):
            g[f"mlfq_q{i}_depth"] = float(d)
        for w, n in self.kv.decode_width_steps.items():
            g[f"decode_width_{w}_steps"] = float(n)
        g["decode_h2d_transfers"] = float(self.kv.decode_h2d_transfers)
        pool = getattr(self.kv, "pool", None)
        if pool is not None:
            g["pool_free_pages"] = float(len(pool.free_pages))
            g["pool_total_pages"] = float(pool.cfg.num_pages)
            g["pool_utilization"] = float(pool.utilization())
        if self._prefix_ok:
            st = self.kv.prefix_stats().as_dict()
            probes = st.get("hits", 0) + st.get("partial_hits", 0) \
                + st.get("misses", 0)
            g["prefix_hit_ratio"] = (
                (st.get("hits", 0) + st.get("partial_hits", 0)) / probes
                if probes else 0.0)
            for k, v in st.items():
                g[f"prefix_{k}"] = float(v)
        if self.tier is not None:
            g.update(self.tier.gauges())
        g.update(self.predictor.gauges())
        dev_id = self.device.rsplit(":", 1)[-1]
        g["device_index"] = float(dev_id) if dev_id.isdigit() else -1.0
        return g

    # -------------------------------------------------------------- prefill
    def _run_prefill(self, req: Request, tokens: List[int]):
        """Monolithic prefill fallback for families without chunked-prefill
        support (SSM / hybrid / enc-dec): one ``Model.prefill`` dispatch,
        KV placed into a free lane.  Returns the last-token logits row."""
        assert self.kv.free_slot() is not None, \
            "caller must check slot availability"
        S = len(tokens)
        fam = self.model.cfg.family
        if fam in ("ssm", "hybrid"):
            # SSM state depends on every step: no padding allowed
            toks = jnp.asarray(tokens, jnp.int32)[None, :]
            batch = {"tokens": toks}
        else:
            bucket = max(32, 1 << (S - 1).bit_length())   # pow2 buckets
            padded = tokens + [0] * (bucket - S)
            batch = {"tokens": jnp.asarray(padded, jnp.int32)[None, :],
                     "last_index": jnp.asarray([S - 1], jnp.int32)}
        logits, pcache = self._prefill(self.params, batch)
        self.kv.write_prefill(req.req_id, pcache, S)
        return logits

    def _sample_host(self, logits_row, rid: int, new_gen: int, new_ctx: int,
                     true_len: int):
        """One-row host-side sampling + termination for prefill first
        tokens — the same
        ``sample_and_reason`` chain the fused decode step runs on device,
        with the same per-(request, token-index) key derivation, so every
        code path draws the identical stream for a given token.  Returns
        ``(token, reason_str)``."""
        keys = token_keys(self._sample_base_key, [rid], [new_gen - 1])
        tok, reason = sample_and_reason(
            logits_row[None], keys, greedy_sampling=self.cfg.greedy,
            temp=self.cfg.temperature, top_k=self.cfg.top_k,
            eos_token=self.cfg.eos_token,
            max_new_tokens=self.cfg.max_new_tokens,
            max_seq_len=self.cfg.max_seq_len,
            new_gen=jnp.asarray([new_gen], jnp.int32),
            new_ctx=jnp.asarray([new_ctx], jnp.int32),
            true_len=jnp.asarray([true_len], jnp.int32))
        return int(tok[0]), REASONS[int(reason[0])]

    def _true_len_of(self, req: Request) -> int:
        return (req.true_out_len if self.cfg.respect_true_len
                else np.iinfo(np.int32).max)

    def _prefill_target_tokens(self, req: Request) -> List[int]:
        """Tokens a (re-)prefill must materialize.  Cache invariant: the
        most recent sampled token's KV is not yet written (the next decode
        step feeds it), so a recompute covers prompt + generated[:-1]."""
        gen = self._generated_of.get(req.req_id)
        if gen is None:
            gen = list(req.output_tokens)
        return list(req.prompt_tokens) + (gen[:-1] if gen else [])

    def _chunk_prework(self, chunk: PrefillChunk, t: float):
        """Everything a chunk needs *before* its dispatch: residency and
        lane checks, shared-prefix matching, page reservation, memory
        admission.  Returns ``(status, start, target_toks)`` with status
        ``"blocked"`` (cannot run this iteration), ``"covered"`` (prefix
        cache already holds the span — no compute), or ``"ready"``.
        Idempotent, so a pack may prework members it later executes
        through the single-chunk path."""
        r = chunk.req
        rid = r.req_id
        if self.mem.location_of(r) == KVLocation.DRAM:
            # spilled by an earlier item *this* iteration (page shortfall /
            # mid-iteration grow): its prefix KV now lives in the host
            # pool, so the chunk cannot resume until swap-in restores it
            return "blocked", 0, None
        if chunk.start > 0 and not self.kv.has(rid):
            # prefix KV vanished since planning (drop path): the scheduler
            # re-plans from Request.prefilled (reset to 0) next iteration
            return "blocked", 0, None
        if not self.kv.has(rid) and self.kv.free_slot() is None:
            return "blocked", 0, None   # lanes exhausted; retry next iter
        target_toks = self._prefill_target_tokens(r)
        if (self._prefix_ok and chunk.start == 0 and r.prefilled == 0
                and not self.kv.has(rid)):
            # fresh prefill (or recompute): re-match the index *now* — the
            # submit-time hint may be stale in either direction (pages
            # published or evicted since).  A hit maps/copies the cached
            # prefix in and moves the resume watermark forward.  When the
            # cluster tier holds more of this prompt than the local index
            # (a peer replica computed it), import the difference first —
            # upload-DMA cost instead of prefill compute — so the local
            # acquire below sees the extended index.
            if self.tier is not None:
                self._tier_import(rid, target_toks, t)
            hit = self.kv.prefix_acquire(rid, target_toks)
            if hit:
                r.prefilled = hit
                r.cached_prefix_hint = hit
                if self.bus is not None:
                    self.bus.emit("prefix_hit", t=t, req_id=rid,
                                  replica=self.name, tokens=hit)
        start = max(chunk.start, r.prefilled)
        # paged backend: the chunk's coverage may need fresh physical pages;
        # cached-but-unreferenced prefix pages yield first (priority-aware
        # LRU), then spill the largest-context other resident (same victim
        # rule as the decode-path page shortfall).  Prefer fully-prefilled
        # victims — evicting a mid-prefill request whose own chunk is still
        # queued this iteration would just bounce it back.
        while (short := self.kv.chunk_pages_shortfall(rid, chunk.end)) > 0:
            if self.mem.reclaim_cache(short) > 0:
                continue
            others = [x for x in self.sched.live.values()
                      if x.req_id != rid and self.kv.has(x.req_id)
                      and self.mem.resident_hbm(x)]
            if not others:
                return "blocked", 0, None   # cannot make room this iteration
            done = [x for x in others if x.prefill_pending == 0]
            victim = max(done or others, key=lambda x: x.context_len)
            self._spill(victim, t, "page_shortfall")
        if self.mem.location_of(r) == KVLocation.NONE:
            self.mem.admit(r)
        r.state = RequestState.RUNNING
        if r.first_scheduled_time is None:
            r.first_scheduled_time = t
        if start >= chunk.end and not chunk.last:
            # chunk entirely covered by the cached prefix: no compute this
            # item; the scheduler re-plans from the new watermark (a *last*
            # chunk always runs — hits are capped at target-1, the first-
            # token logits must come from a real dispatch)
            return "covered", start, target_toks
        return "ready", start, target_toks

    def _chunk_event(self, sp: span, chunk: PrefillChunk, start: int,
                     n_toks: int, pack_size: int = 1) -> None:
        """Queue a chunk's ``prefill_chunk`` bus event on the span of its
        dispatch (one per member of a packed dispatch)."""
        sp.emit("prefill_chunk", req_id=chunk.req.req_id, replica=self.name,
                start=start, end=chunk.end, tokens=n_toks, last=chunk.last,
                fresh=chunk.fresh, bucket=chunk.bucket, pack_size=pack_size)

    def _chunk_postwork(self, chunk: PrefillChunk, logits_row, target_toks,
                        generated_of, t: float) -> None:
        """Everything after a chunk's dispatch: prefix publication on the
        final chunk, and first-token sampling when a fresh prefill just
        completed.  Shared between the single-chunk path and each member
        of a packed dispatch."""
        r = chunk.req
        rid = r.req_id
        if chunk.last and self._prefix_ok and rid not in self._lossy_kv:
            # prefill complete: publish the full pages covering the target
            # back to the index so the *next* request sharing this prefix
            # hits (the partial tail page stays private — decode writes it)
            pages = self.kv.prefix_publish(rid, target_toks, r.prefilled)
            if pages and self.bus is not None:
                self.bus.emit("prefix_publish", t=t, req_id=rid,
                              replica=self.name, pages=pages)
        if chunk.last and r.generated == 0:   # fresh prefill emits a token
            tok, reason = self._sample_host(
                logits_row, rid, 1, r.context_len + 1, self._true_len_of(r))
            self._accept_token(r, tok, generated_of, t, reason=reason)

    def _exec_prefill_chunk(self, chunk: PrefillChunk, generated_of,
                            t: float) -> bool:
        """Execute one PrefillChunk item: (first chunk) match the shared-
        prefix cache, claim a lane and admit memory, run the uncached part
        of the chunk through the backend's resumable prefill (or the
        monolithic fallback), and — when the final chunk of a fresh
        prefill completes — sample the request's first token.  Returns
        whether the chunk made progress."""
        r = chunk.req
        status, start, target_toks = self._chunk_prework(chunk, t)
        if status != "ready":
            return status == "covered"
        if self._chunked_ok:
            n_chunk_toks = chunk.end - start
        else:
            assert chunk.start == 0 and chunk.last, \
                "monolithic fallback cannot resume a partial chunk"
            n_chunk_toks = len(target_toks)
        with span("engine.prefill", self.bus, self._offset(t),
                  tokens=n_chunk_toks, start=start) as sp:
            if self._chunked_ok:
                logits = self.kv.prefill_chunk(
                    self.params, r.req_id, target_toks[start:chunk.end],
                    start)
                r.prefilled = chunk.end
            else:
                logits = self._run_prefill(r, target_toks)
                r.prefilled = len(target_toks)
            self._chunk_event(sp, chunk, start, n_chunk_toks)
        self.prefill_times.append((sp.t0, n_chunk_toks, sp.dur))
        self._chunk_postwork(chunk, logits[0], target_toks, generated_of, t)
        return True

    def _exec_prefill_pack(self, pack: PrefillPack, generated_of,
                           t: float) -> bool:
        """Execute one PrefillPack: run every member's admission prework,
        then push all *ready* members through the backend's packed prefill
        as a single compiled dispatch (segment rows padded to the pack's
        bucket).  Members whose prework blocks are simply skipped — the
        scheduler re-plans them next iteration, exactly as a blocked
        single chunk.  A pack degraded to one ready member (or a backend
        without pack support) falls back to the ordinary single-chunk
        dispatch, which warmup() has also compiled."""
        ready: List[tuple] = []
        ran_any = False
        for chunk in pack.chunks:
            status, start, target_toks = self._chunk_prework(chunk, t)
            if status == "covered":
                ran_any = True
            elif status == "ready":
                ready.append((chunk, start, target_toks))
        # cumulative resource gate: prework admits each member in
        # isolation, but the fused dispatch claims lanes/pages for *all*
        # of them at once — trim members the shared free supply cannot
        # cover (the scheduler re-plans them next iteration)
        free_lanes = sum(1 for x in self.kv.slot_req if x is None)
        pool = getattr(self.kv, "pool", None)
        free_pages = len(pool.free_pages) if pool is not None else 0
        fit = []
        for c, s, toks in ready:
            rid = c.req.req_id
            need_lane = 0 if self.kv.has(rid) else 1
            need_pages = 0
            if pool is not None:
                need_pages = max(0, pool.pages_needed(c.end)
                                 - len(pool.page_table.get(rid, [])))
            if need_lane > free_lanes or (pool is not None
                                          and need_pages > free_pages):
                continue
            free_lanes -= need_lane
            free_pages -= need_pages
            fit.append((c, s, toks))
        ready = fit
        if not ready:
            return ran_any
        if len(ready) == 1 or not self.kv.supports_pack():
            for chunk, _, _ in ready:
                ran_any |= self._exec_prefill_chunk(chunk, generated_of, t)
            return ran_any
        items = [(c.req.req_id, toks[s:c.end], s) for c, s, toks in ready]
        total = sum(c.end - s for c, s, _ in ready)
        with span("engine.prefill_pack", self.bus, self._offset(t),
                  tokens=total, members=len(ready)) as sp:
            logits = self.kv.prefill_pack(self.params, items,
                                          bucket=pack.bucket)
            for chunk, start, _ in ready:
                self._chunk_event(sp, chunk, start, chunk.end - start,
                                  len(ready))
        self.prefill_times.append((sp.t0, total, sp.dur))
        for i, (chunk, start, target_toks) in enumerate(ready):
            chunk.req.prefilled = chunk.end
            self._chunk_postwork(chunk, logits[i], target_toks, generated_of,
                                 t)
        return True

    # -------------------------------------------------------------- warmup
    def warmup(self) -> Dict[int, float]:
        """Pre-compile every dispatch shape serve time can hit, on an idle
        engine: one chunk dispatch per prefill bucket, one packed dispatch
        per bucket (when packing is on; a single member already compiles
        the full ``(width, bucket)`` shape — dummy rows pad the rest), the
        fused decode step at every page-table width the paged backend
        dispatches, and the host-side
        first-token sampling chain.  Each bucket runs twice — the first
        rep compiles, the second measures — and the measured seconds land
        in ``self.latency.bucket_costs`` so EWT prices a bucketed chunk at
        its true padded dispatch cost.  Returns the per-bucket seconds
        table ({} for families without chunked-prefill support, whose
        monolithic prompt-length buckets are unbounded).

        Warm dispatches only touch state they immediately release: the
        chunk/pack KV lands in a lane (dense: lengths reset by ``clear``,
        so the garbage rows are never attended; paged: pages freed), and
        the all-inactive decode writes position 0 of free stripes / the
        scratch page — so a warmed engine is bit-identical to a cold one
        under any sampling (keys derive from (request, token-index), so
        warm draws never perturb a real request's stream).
        """
        assert not self.sched.live, "warmup() requires an idle engine"
        with self._on_device():
            return self._warmup()

    def _warmup(self) -> Dict[int, float]:
        costs: Dict[int, float] = {}
        menu = self._buckets
        if menu is None and self._chunked_ok and self.cfg.prefill_chunk:
            menu = default_bucket_menu(self.cfg.prefill_chunk)
        warm_rid = -(1 << 30)       # never collides with real request ids
        for b in (menu or ()):
            for rep in range(2):
                t0 = time.perf_counter()
                logits = self.kv.prefill_chunk(self.params, warm_rid,
                                               [1] * b, 0)
                jax.block_until_ready(logits)
                costs[b] = time.perf_counter() - t0
                if rep == 0:
                    # keys derive from (rid, index): warm draws touch only
                    # the warm_rid stream, so no counter to save/restore
                    self._sample_host(logits[0], warm_rid, 1, 1, 1)
                self.kv.clear(warm_rid)
            if self._pack_ok and self.kv.supports_pack():
                for _ in range(2):
                    out = self.kv.prefill_pack(
                        self.params, [(warm_rid, [1] * b, 0)], bucket=b)
                    jax.block_until_ready(out)
                    self.kv.clear(warm_rid)
        if menu:
            # swap staging: one offload/upload round-trip per pow2 context
            # bucket.  Payloads are pow2-bucketed (see KVBackend.offload),
            # so this finite sweep means ALISE's speculative offloads
            # never compile at serve time either.  Fill the warm lane
            # through already-warmed chunk shapes only.
            span = 8
            while span <= self.cfg.max_seq_len:
                try:
                    filled = 0
                    while filled < span:
                        c = max((b for b in menu if b <= span - filled),
                                default=span - filled)
                        self.kv.prefill_chunk(self.params, warm_rid,
                                              [1] * c, filled)
                        filled += c
                    blob = self.kv.offload(warm_rid)
                    self.kv.upload(warm_rid, blob)
                except RuntimeError:    # page pool too small for this span
                    pass
                self.kv.clear(warm_rid)
                span *= 2
        B = self.cfg.max_slots
        tokens = np.zeros((B, 1), np.int32)
        active = np.zeros((B,), bool)
        zeros = np.zeros((B,), np.int32)
        tl = np.full((B,), np.iinfo(np.int32).max, np.int32)
        # every page-table width of the paged ladder
        self.kv.warm_decode(self.params, tokens, active, zeros, zeros, tl,
                            zeros)
        if self._spec_ok:
            # warm the verify-k shape (the serve path's only other decode
            # dispatch: one fixed (B, spec_k+1) shape, variable draft
            # counts ride the n_drafts mask) and record its measured
            # dispatch seconds so EWT prices speculative iterations right
            vtok = np.zeros((B, self.cfg.spec_k + 1), np.int32)
            nd = np.zeros((B,), np.int32)
            for _ in range(2):
                t0v = time.perf_counter()
                self.kv.decode_verify(self.params, vtok, nd, active,
                                      zeros, zeros, tl, zeros)
                verify_dt = time.perf_counter() - t0v
            self.latency.verify_cost = verify_dt
        if costs:
            merged = dict(self.latency.bucket_costs or {})
            merged.update(costs)
            self.latency.bucket_costs = merged
        return costs

    # ------------------------------------------------------------ swapping
    def _swap_stall(self, n_tokens: int, t0: float) -> None:
        """Record the modeled transfer time of an offload/upload (residual
        beyond the wall time the host copy already took).  Only active with
        ``realtime_swap``: the stall stands in for device<->host DMA the
        host thread would wait on.  It is *accumulated* here and slept off
        at the end of step() after ``step_lock`` is released, so the
        replica's wall timing is preserved without blocking loop-thread
        submit/cancel/poll on the lock for the DMA duration — the sleep
        releases the GIL, which is what the gateway's concurrent pump
        overlaps across replicas."""
        if not self.cfg.realtime_swap:
            return
        bpt = self.mem.cfg.bytes_per_token_fp
        if self.cfg.quantize_offload:
            bpt *= self.mem.cfg.quant_ratio   # INT8 payload (Eq. 8), same
                                              # ratio the simulator charges
        need = n_tokens * bpt / self.cfg.swap_bw - (time.perf_counter() - t0)
        if need > 0:
            self._stall_debt += need

    def _tier_import(self, rid: int, toks: List[int], t: float) -> int:
        """Pull a cluster-tier prefix into the *local* prefix cache when
        the tier holds more of ``toks`` than the local index.  The pages
        land in the index under the same refcount discipline as a local
        publish, so the caller's ``prefix_acquire`` then maps (paged) or
        copies (dense) them like any local hit.  Returns the imported
        token watermark (0 = tier adds nothing over the local cache)."""
        cap = len(toks) - 1
        if cap <= 0 or not self._prefix_ok:
            return 0
        local = self.kv.prefix_probe(toks)
        want = self.tier.probe(toks, cap)
        if want <= local:
            return 0
        handle = self.tier.acquire(toks, want)
        if handle is None:
            return 0
        t0 = time.perf_counter()
        try:
            if handle.lossy:
                # quantized tier: the imported prefix is INT8 round-
                # tripped (divergent, like INT8 swap) — never publish
                # pages derived from it back to the exact index/tier
                self._lossy_kv.add(rid)
            got = self.kv.tier_fill(toks, handle)
        finally:
            handle.release()
        if got > local:
            self.mem.note_tier_import(t, handle.nbytes)
            if self.cfg.realtime_swap:
                # the host copy stands in for a device<->host DMA; sleep
                # off the modeled residual like any other swap transfer
                need = (handle.nbytes / self.cfg.swap_bw
                        - (time.perf_counter() - t0))
                if need > 0:
                    self._stall_debt += need
            if self.bus is not None:
                self.bus.emit("tier_import", t=t, req_id=rid,
                              replica=self.name, tokens=got,
                              bytes=handle.nbytes,
                              pages=len(handle.payloads))
        return got

    def _offload(self, req: Request) -> None:
        t0 = time.perf_counter()
        blob = self.kv.offload(req.req_id)
        if not self.cfg.quantize_offload:
            # exact payload: remember the tokens the blob covers so the
            # backend's upload can re-match the radix index and re-link
            # still-shared pages instead of forking private duplicates
            blob["tokens"] = self._prefill_target_tokens(req)[:blob["lengths"]]
        self.host_pool[req.req_id] = blob
        if self.cfg.quantize_offload:
            self._lossy_kv.add(req.req_id)
        self._swap_stall(blob["lengths"], t0)

    def _upload(self, req: Request) -> None:
        t0 = time.perf_counter()
        blob = self.host_pool.pop(req.req_id)
        self.kv.upload(req.req_id, blob)
        self._swap_stall(blob["lengths"], t0)

    def _drop_kv(self, req_id: int) -> None:
        """Delete all engine-side KV for a request (slot/pages + host pool)."""
        self.kv.clear(req_id)
        self.host_pool.pop(req_id, None)
        self._lossy_kv.discard(req_id)
        if self._draft is not None:
            self._draft.release(req_id)

    def _spill(self, victim: Request, t: float, reason: str) -> None:
        """Preempt a resident victim to host DRAM — the single offload
        path shared by the planned swap-out, page-shortfall, and
        mid-iteration-grow sites (engine KV move + memory accounting +
        request state + observability events)."""
        if self.bus is not None:
            self.bus.emit("preempt", t=t, req_id=victim.req_id,
                          replica=self.name, reason=reason)
        with span("kv.offload", self.bus, self._offset(t)) as sp:
            self._offload(victim)
            op = self.mem.offload(victim, t)
            sp.emit("swap_out", req_id=victim.req_id, replica=self.name,
                    bytes=op.bytes, quantized=self.cfg.quantize_offload)
        victim.state = RequestState.PREEMPTED
        victim.preempt_count += 1

    # ------------------------------------------------------------ main loop
    def submit(self, req: Request, now: float = 0.0) -> None:
        """Enqueue a request.  Re-entrant: a request released from another
        engine (drain / re-route) resumes from its existing ``output_tokens``
        via the recompute path, so no generated token is lost or re-emitted."""
        with self.step_lock:
            self._generated_of[req.req_id] = list(req.output_tokens)
            if self._prefix_ok and req.prompt_tokens:
                # speculative pricing: the scheduler/EWT charge only the
                # uncached suffix (re-matched for real at prefill time).
                # Probed *before* sched.submit so the hit-aware predictor
                # sees the cache watermark at predict time.
                req.cached_prefix_hint = self.kv.prefix_probe(
                    self._prefill_target_tokens(req))
            self.sched.submit(req, now)
            self._backlog_cache, self._backlog_q90 = \
                self.sched.backlog_quantiles()

    def submit_nowait(self, req: Request, now: float = 0.0) -> None:
        """Non-blocking intake for the concurrent pump: park the request in
        the submit mailbox (drained at the start of the next step) instead
        of waiting on ``step_lock`` behind an in-flight iteration.  Depth
        and backlog signals account for parked requests immediately — with
        the cached-prefix hint set here, *before* parking, so the mailbox
        term of ``predicted_backlog`` prices only the uncached suffix
        (the probe is lock-free: it reads the index without step_lock and
        degrades to 0 on a racing mutation)."""
        if self._prefix_ok and req.prompt_tokens:
            req.cached_prefix_hint = self.kv.prefix_probe(
                self._prefill_target_tokens(req))
        with self._submit_lock:
            self._submit_box.append((req, now))

    def _drain_submit_box(self) -> None:
        """Move mailbox arrivals into the scheduler (under step_lock)."""
        with self._submit_lock:
            box, self._submit_box = self._submit_box, []
        for req, t in box:
            self.submit(req, t)

    def poll_events(self) -> List[EngineEvent]:
        """Drain streaming events produced since the last poll (recorded
        only while ``stream_events`` is set).  Thread-safe against a step()
        running concurrently in an executor thread."""
        with self._events_lock:
            evs, self._events = self._events, []
        return evs

    def _emit_event(self, ev: EngineEvent) -> None:
        with self._events_lock:
            self._events.append(ev)

    def release(self, req_id: int) -> Optional[Request]:
        """Detach a live request without finishing it (drain / cancel):
        frees its lane/pages, host-pool KV, and memory accounting.  The
        returned request can be re-submitted to any engine and will continue
        deterministically from its current ``output_tokens``."""
        with self.step_lock, self._on_device():
            req = self.sched.live.get(req_id)
            if req is None:
                return None
            self._drop_kv(req_id)
            self.sched.release(req)
            self._generated_of.pop(req_id, None)
            req.state = RequestState.QUEUED
            self._backlog_cache, self._backlog_q90 = \
                self.sched.backlog_quantiles()
            return req

    def drain(self) -> List[Request]:
        """Release every live request (and any mailbox arrival not yet
        scheduled) for re-enqueue elsewhere (replica removal / elastic
        scale-down)."""
        with self._submit_lock:
            box, self._submit_box = self._submit_box, []
        with self.step_lock:
            out = [self.release(rid) for rid in list(self.sched.live.keys())]
        return out + [req for req, _ in box]

    def cancel(self, req_id: int, t: float = 0.0) -> bool:
        """Client abort: free all engine state and emit a cancel event."""
        # parked in the submit mailbox: cancellable without the step lock
        with self._submit_lock:
            for i, (req, _) in enumerate(self._submit_box):
                if req.req_id == req_id:
                    del self._submit_box[i]
                    req.state = RequestState.CANCELLED
                    req.finish_time = t
                    if self.stream_events:
                        self._emit_event(EngineEvent("cancel", req_id, t))
                    return True
        with self.step_lock:
            req = self.release(req_id)
            if req is None:
                return False
            req.state = RequestState.CANCELLED
            req.finish_time = t
        if self.stream_events:
            self._emit_event(EngineEvent("cancel", req_id, t))
        return True

    def queue_depth(self) -> int:
        return len(self.sched.live) + len(self._submit_box)

    def predicted_backlog(self, quantile: Optional[float] = None) -> float:
        """Predicted remaining seconds of live work (routing/admission).

        Returns the snapshot refreshed under ``step_lock`` at the end of
        every step/submit/release, so event-loop callers (router, admission)
        never race a step mutating scheduler state in an executor thread.
        Between engine-state changes the cache is exact, which keeps
        virtual-clock routing decisions bit-identical to a fresh compute.
        ``quantile >= 0.9`` reads the p90 remaining-length surface — the
        admission gate's conservative backlog — while routing/EWT keep the
        p50 default.  Mailbox arrivals not yet scheduled contribute their
        remaining prefill estimate (the chunked-prefill cost model over the
        actual prefill target — prompt plus recompute tokens for a
        re-routed request, minus anything already materialized) so
        back-to-back dispatches don't all see a stale zero and wall-mode
        routing doesn't mis-estimate parked work."""
        chunk = self.sched.cfg.prefill_chunk
        with self._submit_lock:
            pending = sum(self.latency.prefill_time_remaining(
                              req.prefill_target,
                              max(req.prefilled, req.cached_prefix_hint),
                              chunk)
                          for req, _ in self._submit_box)
        base = self._backlog_q90 if (quantile is not None
                                     and quantile >= 0.9) \
            else self._backlog_cache
        return base + pending

    def prefix_probe(self, prompt_tokens) -> int:
        """Expected shared-prefix cache hit for a prompt on *this* replica
        (router affinity + admission pricing; 0 when the cache is off).
        Lock-free: reads race a step thread at worst into a 0 hint."""
        if not self._prefix_ok or not prompt_tokens:
            return 0
        return self.kv.prefix_probe(prompt_tokens)

    def prefill_estimate(self, prompt_len: int,
                         prompt_tokens=None) -> float:
        """Prefill latency term for the gateway's expected-TTFT admission
        gate: with chunked prefill enabled, only the *first chunk* gates
        (later chunks interleave with resident decode instead of
        serializing behind the backlog); monolithic prefill charges the
        whole prompt.  With the shared-prefix cache, only the *uncached
        suffix* is charged — a cache-hit long prompt gates like the short
        job it really is."""
        chunk = self.sched.cfg.prefill_chunk
        cap = max(prompt_len - 1, 0)
        hit = min(self.prefix_probe(prompt_tokens), cap)
        if hit <= 0:
            est = self.latency.first_chunk_time(prompt_len, chunk)
        else:
            rem = prompt_len - hit
            est = self.latency.prefill_chunk_time(
                hit, min(rem, chunk) if chunk else rem)
        if self.tier is not None and prompt_tokens:
            # tier-aware pricing: a cluster-tier import is upload-DMA
            # cost plus the first uncached chunk from the imported
            # watermark — not prefill compute over the whole prompt
            t_hit, t_bytes = self.tier.probe_bytes(prompt_tokens, cap)
            if t_hit > hit:
                rem = prompt_len - t_hit
                est = min(est, t_bytes / self.cfg.swap_bw
                          + self.latency.prefill_chunk_time(
                              t_hit, min(rem, chunk) if chunk else rem))
        return est

    def serve(self, requests: List[Request], realtime: bool = False,
              max_wall_s: float = 600.0) -> List[Request]:
        """Batch driver: serve all requests to completion (thin wrapper over
        the re-entrant submit()/step()/poll_events() API)."""
        t_start = time.perf_counter()
        pending = sorted(requests, key=lambda r: r.arrival_time)
        i_arr = 0

        def now() -> float:
            return time.perf_counter() - t_start

        while (i_arr < len(pending) or self.sched.live) \
                and now() < max_wall_s:
            t = now()
            while i_arr < len(pending) and (
                    not realtime or pending[i_arr].arrival_time <= t):
                self.submit(pending[i_arr], t)
                i_arr += 1
            ran_any = self.step(now())
            self.poll_events()          # batch mode: nobody streams; discard
            if not ran_any:
                if i_arr >= len(pending) and not self.sched.live:
                    break
                time.sleep(0.0005)
        return requests

    def _reserve_pages(self, runnable: List[Request], t: float
                       ) -> List[Request]:
        """Paged backend: decoding one token may cross a page boundary for
        some requests; when the pool can't supply the fresh pages, spill the
        largest-context runnable requests (the same victim rule as the
        mid-iteration HBM spill) until the rest fit.  The dense backend
        never has a shortfall (every slot owns a full stripe)."""
        runnable = list(runnable)
        while runnable:
            short = self.kv.pages_shortfall([r.req_id for r in runnable])
            if short <= 0:
                break
            if self.mem.reclaim_cache(short) > 0:
                continue       # cached-but-unreferenced pages yielded first
            victim = max(runnable, key=lambda r: r.context_len)
            runnable.remove(victim)
            self._spill(victim, t, "page_shortfall")
        return runnable

    def step(self, t: float) -> bool:
        """One scheduling + execution iteration; returns whether work ran."""
        with span("engine.step"):
            with self.step_lock, self._on_device():
                ran_any = self._step(t)
                stall, self._stall_debt = self._stall_debt, 0.0
            # learning happens here — outside step_lock, after the
            # iteration's dispatch work is done — so a slow (or
            # pathological) predictor update can never stall token emission
            # or a concurrent submit
            with span("predictor.feedback"):
                self.predictor.drain_feedback()
            if stall > 0:
                time.sleep(stall)          # modeled swap DMA, lock released
        return ran_any

    def _step(self, t: float) -> bool:
        """The iteration under ``step_lock``: plan, drops and swaps, the
        prefill items in priority order, then one fused decode batch."""
        generated_of = self._generated_of
        self._step_wall0 = time.perf_counter()
        self._drain_submit_box()
        with span("sched.plan"):
            plan = self.sched.plan(t)

        for r in plan.drop:                # recompute-strategy eviction
            # under very tight HBM the planned victim's KV may already
            # live in the host pool (offloaded earlier) rather than a slot
            dropped_ctx = r.context_len
            self._drop_kv(r.req_id)
            self.mem.drop(r)
            r.state = RequestState.QUEUED
            r.preempt_count += 1
            if self.bus is not None:
                self.bus.emit("drop", t=t, req_id=r.req_id,
                              replica=self.name, tokens=dropped_ctx)
        for r in plan.swap_out:
            if not self.kv.has(r.req_id):
                continue                   # already off-slot; nothing to move
            self._spill(r, t, "planned")
        for r in plan.swap_in:
            if self.kv.free_slot() is None:
                continue                   # retry next iteration
            with span("kv.upload", self.bus, self._offset(t)) as sp:
                self._upload(r)
                op = self.mem.upload(r, t)
                sp.emit("swap_in", req_id=r.req_id, replica=self.name,
                        bytes=op.bytes)
            r.state = RequestState.PREEMPTED
            self.sched._swap_ready_at[r.req_id] = 0.0

        ran_any = False
        # compute items in priority order: prefill chunks execute as
        # encountered; decode lanes collect into one fused batch
        decode_lanes: List[Request] = []
        for item in plan.items:
            if isinstance(item, DecodeLane):
                decode_lanes.append(item.req)
            elif isinstance(item, PrefillPack):
                ran_any |= self._exec_prefill_pack(item, generated_of, t)
            else:
                ran_any |= self._exec_prefill_chunk(item, generated_of, t)

        runnable = [r for r in decode_lanes if self.kv.has(r.req_id)]
        if runnable and self.cfg.kv_backend == "paged":
            runnable = self._reserve_pages(runnable, t)
        if runnable:
            self._exec_decode(runnable, generated_of, t)
            ran_any = True

        if self.bus is not None and plan.hol_blocked:
            # charge each blocked higher-priority request the wall time of
            # the iteration that ran lower-priority work ahead of it (the
            # direct HoL-blocking measurement)
            iter_dt = time.perf_counter() - self._step_wall0
            for r in plan.hol_blocked:
                self.bus.emit("hol_blocked", t=t, dur=iter_dt,
                              req_id=r.req_id, replica=self.name,
                              level=r.priority_level)
        self._backlog_cache, self._backlog_q90 = \
            self.sched.backlog_quantiles()
        return ran_any

    def _exec_decode(self, runnable: List[Request], generated_of,
                     t: float) -> None:
        """One fused decode dispatch over ``runnable``'s lanes, then each
        lane's token(s) accepted, in the span ``alise.engine.decode``,
        which also times the ``decode_iter`` event.  The iteration ring
        times the lane arrays, the dispatch and its sync: from the span's
        start to the sync's end."""
        ctx_tokens = int(sum(r.context_len for r in runnable))
        width = self.kv.decode_width([r.req_id for r in runnable])
        with span("engine.decode", self.bus, self._offset(t),
                  lanes=len(runnable), ctx=ctx_tokens, width=width) as sp:
            B = self.cfg.max_slots
            k1 = (self.cfg.spec_k + 1) if self._spec_ok else 1
            with span("decode.stage"):
                tokens = np.zeros((B, k1), np.int32)
                n_drafts = np.zeros((B,), np.int32)
                active = np.zeros((B,), bool)
                base_gen = np.zeros((B,), np.int32)
                base_ctx = np.zeros((B,), np.int32)
                true_len = np.full((B,), np.iinfo(np.int32).max, np.int32)
                rids = np.zeros((B,), np.int32)
                slot_of = {}           # pinned: a mid-loop spill may evict
                for r in runnable:
                    slot = self.kv.slot_of(r.req_id)
                    slot_of[r.req_id] = slot
                    gen = generated_of[r.req_id]
                    prev = gen[-1] if gen else r.prompt_tokens[-1]
                    tokens[slot, 0] = prev
                    if self._spec_ok:
                        drafts = self._draft.propose(
                            r.req_id, list(r.prompt_tokens) + gen,
                            self.cfg.spec_k)
                        for i, d in enumerate(drafts):
                            tokens[slot, 1 + i] = d
                        n_drafts[slot] = len(drafts)
                    active[slot] = True
                    base_gen[slot] = r.generated
                    base_ctx[slot] = r.context_len
                    rids[slot] = r.req_id
                    if self.cfg.respect_true_len:
                        true_len[slot] = r.true_out_len
                    r.state = RequestState.RUNNING
            if self._spec_ok:
                # one verify-k dispatch: score the fed token plus all
                # drafts, accept the longest exact-match run, sample the
                # bonus token, terminate — one host sync for up to
                # spec_k+1 emitted tokens per lane
                s, n_emit, reasons = self.kv.decode_verify(
                    self.params, tokens, n_drafts, active, base_gen,
                    base_ctx, true_len, rids)
            else:
                # one dispatch: decode + sample + terminate on device
                toks, reasons = self.kv.decode(
                    self.params, tokens, active, base_gen + 1,
                    base_ctx + 1, true_len, rids)
            self.iter_times.append((sp.t0, ctx_tokens, len(runnable),
                                    time.perf_counter() - sp.t0))
            if self.bus is not None:
                extra = {}
                if self._spec_ok:
                    extra = dict(
                        drafted=int(n_drafts.sum()),
                        accepted=int(sum(max(int(n_emit[sl]) - 1, 0)
                                         for sl in slot_of.values())))
                sp.emit("decode_iter", replica=self.name,
                        batch=len(runnable), ctx_tokens=ctx_tokens, **extra)
            with span("decode.accept"):
                for r in runnable:
                    # the token must be accepted even if a neighbor's
                    # mem.grow() spill offloaded r mid-loop: this decode
                    # already wrote r's fed token's KV (and advanced any
                    # SSM state), so skipping would re-feed the same token
                    # after swap-in and duplicate its KV row — accepting
                    # keeps the "last sampled token's KV not yet written"
                    # invariant intact for the host-pool copy
                    slot = slot_of[r.req_id]
                    if self._spec_ok:
                        m = int(n_emit[slot])
                        r.spec_iters += 1
                        r.spec_drafted += int(n_drafts[slot])
                        r.spec_accepted += max(m - 1, 0)
                        for i in range(m):
                            last = i == m - 1
                            self._accept_token(
                                r, int(s[slot, i]), generated_of, t,
                                reason=(REASONS[int(reasons[slot])]
                                        if last else ""))
                    else:
                        self._accept_token(
                            r, int(toks[slot]), generated_of, t,
                            reason=REASONS[int(reasons[slot])])

    def step_and_poll(self, t: float) -> Tuple[bool, List[EngineEvent]]:
        """One iteration plus its events, as a single executor-friendly call
        (the gateway pump runs this off the event loop; events produced by
        the step are returned atomically so the caller can dispatch them in
        loop-thread order)."""
        ran = self.step(t)
        return ran, self.poll_events()

    def _accept_token(self, req: Request, tok: int, generated_of, t: float,
                      reason: str = ""):
        """Record a sampled token.  ``reason`` carries the termination
        verdict from ``sample_and_reason`` — computed on device by the
        fused step, host-side (same function) for prefill first tokens."""
        req.generated += 1
        # the fed/just-sampled token's predecessors are all materialized:
        # context minus the one token whose KV the next decode step writes
        req.prefilled = req.prompt_len + max(req.generated - 1, 0)
        generated_of[req.req_id].append(tok)
        req.output_tokens.append(tok)
        if self.stream_events:
            self._emit_event(EngineEvent(
                "token", req.req_id, t, token=tok,
                index=len(req.output_tokens) - 1))
        if req.first_token_time is None:
            req.first_token_time = t
            req.first_token_emit_time = self._offset(t) + time.perf_counter()
        # a request spilled mid-iteration by an earlier neighbor's grow()
        # lives in DRAM now; its byte growth is settled at upload time
        if self.mem.resident_hbm(req) and not self.mem.grow(req):
            # engine HBM exhausted mid-iteration: offload highest-EWT resident
            others = [r for r in self.sched.live.values()
                      if self.mem.resident_hbm(r) and r.req_id != req.req_id]
            if others:
                victim = max(others, key=lambda r: r.context_len)
                self._spill(victim, t, "hbm_grow")
                self.mem.grow(req)
        if reason:
            if self._prefix_ok and req.prompt_tokens \
                    and req.req_id not in self._lossy_kv:
                # finish-time publish: a multi-turn follow-up resends this
                # whole conversation, so the generated tokens' full pages
                # are worth caching too (everything up to the prefilled
                # watermark is materialized; the fed token's KV is not)
                pages = self.kv.prefix_publish(
                    req.req_id, self._prefill_target_tokens(req),
                    req.prefilled)
                if pages and self.bus is not None:
                    self.bus.emit("prefix_publish", t=t, req_id=req.req_id,
                                  replica=self.name, pages=pages)
            self._drop_kv(req.req_id)      # lane/pages or host-pool copy
            self.sched.note_finished(req, t)
            if self.bus is not None:
                # self-contained: arrival/first-token/prediction ride along
                # so an engine-only trace (no gateway) still yields length
                # and TTFT error distributions
                self.bus.emit("finish", t=t, req_id=req.req_id,
                              replica=self.name, reason=reason,
                              generated=req.generated,
                              predicted=req.predicted_len,
                              cached_prefix=req.cached_prefix_hint,
                              arrival_t=req.arrival_time,
                              first_token_t=req.first_token_time,
                              preempts=req.preempt_count,
                              demotions=req.demotions)
            # the token mirror is per-live-request state: dropping it here
            # (as release() already does) keeps week-long serves from
            # accumulating one token list per request ever served
            self._generated_of.pop(req.req_id, None)
            if self.stream_events:
                self._emit_event(EngineEvent(
                    "finish", req.req_id, t, reason=reason))
        else:
            self.sched.note_generated(req, t)

    # ----------------------------------------------------------- profiling
    def fit_latency_model(self) -> LatencyModel:
        """Fit Eq. 3-5 coefficients from this engine's measured step times.
        Ring entries carry a leading ``time.perf_counter`` timestamp (for
        post-hoc alignment with exported traces); the fit strips it."""
        decode = [(ctx / max(b, 1), dt / 1.0)
                  for _, ctx, b, dt in self.iter_times]
        prefill = [(n, dt) for _, n, dt in self.prefill_times]
        return LatencyModel.fit(prefill, decode)

    def autotune_token_budget(self, target_tpot: float) -> Optional[int]:
        """Set ``iter_token_budget`` from the fitted latency model: the
        budget whose predicted mixed-iteration time (full decode batch +
        prefill-chunk fill) matches ``target_tpot``.  Needs profiled
        iterations (run a warmup batch first); returns the chosen budget
        (None leaves the budget unbounded)."""
        lm = self.fit_latency_model()
        if self.iter_times:
            ctx = float(np.mean([c / max(b, 1)
                                 for _, c, b, _ in self.iter_times]))
        else:
            ctx = self.cfg.max_seq_len / 2
        budget = lm.budget_for_tpot(target_tpot, self.cfg.max_slots, ctx)
        with self.step_lock:
            self.sched.cfg.iter_token_budget = budget
        return budget
