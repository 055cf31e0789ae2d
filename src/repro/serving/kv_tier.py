"""Cluster-wide host-RAM KV tier: a shared cold store of prefix pages.

Every replica owns its HBM-resident prefix cache, but a session re-routed
to a peer replica used to re-prefill a prefix the cluster already
computed.  :class:`HostKVTier` turns the prefix cache into a cluster
asset: replicas *publish* their exact prefix pages into one host-RAM pool
(one copy per unique page cluster-wide, radix-indexed by token prefix)
and any replica *imports* a peer's pages at admit time — an upload-DMA-
shaped transfer instead of prefill compute (FastServe's proactive
multi-tier KV movement, arxiv 2305.05920).

Discipline mirrors the on-device prefix cache:

  * entries are **refcounted handles**: an in-flight import pins its
    pages, so byte-capacity LRU eviction can never free a payload a
    replica is copying;
  * only **exact** KV is published (the engine's ``_lossy_kv`` guard runs
    upstream), so with the default fp tier a cross-replica import is
    bit-indistinguishable from recompute;
  * ``quantize=True`` stores INT8 payloads via the Pallas ``kv_quant``
    path (~half the bytes, so the tier holds ~2x the prefixes) — like
    INT8 swap this is lossy, importers are marked lossy and never
    re-publish, and greedy tier-on/off bit-identity is documented as NOT
    holding in this mode.

:class:`SimKVTier` is the analytical twin for the simulator / cluster
replicas: shared hit lengths + page-capacity LRU, imports priced at
``bytes / swap_bw`` DMA time instead of prefill compute.
"""
from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serving.prefix_cache import RadixPageIndex, SimPrefixIndex


@dataclass
class TierStats:
    publishes: int = 0            # publish calls that stored >= 1 page
    published_pages: int = 0
    imports: int = 0              # acquire calls that pinned >= 1 page
    imported_pages: int = 0
    hit_bytes: int = 0            # payload bytes served to importers
    evicted_pages: int = 0
    evicted_bytes: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dict(self.__dict__)


class _Entry:
    """One page payload: ``("raw", k, v)`` host arrays, or
    ``("q8", k_blob, v_blob)`` kv_quant tuples."""

    __slots__ = ("payload", "nbytes", "refs")

    def __init__(self, payload, nbytes: int):
        self.payload = payload
        self.nbytes = nbytes
        self.refs = 0               # pinned by in-flight imports


class TierHandle:
    """Pinned view of a matched prefix: ``payloads[i]`` covers token page
    ``i`` from the root.  Call :meth:`release` once the pages are copied
    on-device (a ``finally`` block — an unreleased handle pins its pages
    against eviction forever)."""

    def __init__(self, tier: "HostKVTier", ids: List[int],
                 payloads: List[tuple], nbytes: int, lossy: bool):
        self._tier = tier
        self._ids = ids
        self.payloads = payloads
        self.nbytes = nbytes
        self.lossy = lossy
        self.tokens = len(ids) * tier.page_size

    def materialize(self, dtype) -> List[Tuple]:
        """Decode every payload to ``(k, v)`` page arrays of ``dtype``
        (host numpy for raw entries; dequantized device arrays for q8)."""
        out = []
        mode = self._tier.interpret
        for payload in self.payloads:
            if payload[0] == "raw":
                out.append((payload[1], payload[2]))
            else:
                from repro.serving.kv_cache import dequantize_kv_device
                out.append((dequantize_kv_device(payload[1], dtype,
                                                 interpret=mode),
                            dequantize_kv_device(payload[2], dtype,
                                                 interpret=mode)))
        return out

    def release(self) -> None:
        if self._ids:
            self._tier._unpin(self._ids)
            self._ids = []


class HostKVTier:
    """Shared host-RAM cold tier of prefix KV pages (cluster asset).

    Thread-safe: replicas' pump threads publish/import concurrently.
    ``capacity_bytes`` bounds payload bytes; overflow evicts unpinned
    pages leaf-first in least-recently-imported order.
    """

    def __init__(self, capacity_bytes: float, page_size: int,
                 quantize: bool = False, interpret: Optional[bool] = None):
        self.capacity_bytes = float(capacity_bytes)
        self.page_size = int(page_size)
        self.quantize = bool(quantize)
        # Pallas mode of the kv_quant kernels that (de)quantize INT8
        # payloads: the replicas' own, handed over by
        # ServingEngine.attach_tier
        self.interpret = interpret
        self.index = RadixPageIndex(self.page_size)
        self.entries: Dict[int, _Entry] = {}
        self.bytes = 0
        self.stats = TierStats()
        self.lock = threading.Lock()
        self._ids = itertools.count()
        self.bus = None                # observability EventBus (None = off)
        self.replica = "tier"

    # ------------------------------------------------------------- probe
    def probe(self, tokens, cap: Optional[int] = None) -> int:
        """Full-page matched token length (touch-free: pricing/routing
        probes must not skew the LRU)."""
        if not tokens:
            return 0
        limit = len(tokens) if cap is None else min(cap, len(tokens))
        with self.lock:
            n = self.index.probe_len(tokens, limit)
        return (n // self.page_size) * self.page_size

    def probe_bytes(self, tokens, cap: Optional[int] = None
                    ) -> Tuple[int, int]:
        """(hit_tokens, payload_bytes) for the matchable full pages —
        the DMA-cost input for tier-aware TTFT pricing."""
        if not tokens:
            return 0, 0
        limit = len(tokens) if cap is None else min(cap, len(tokens))
        with self.lock:
            full, _ = self.index.match(tokens, limit, touch=False)
            nbytes = sum(self.entries[n.page].nbytes for n in full)
        return len(full) * self.page_size, nbytes

    # ----------------------------------------------------------- acquire
    def acquire(self, tokens, upto: int) -> Optional[TierHandle]:
        """Pin and return the payloads covering ``tokens[:upto]`` (full
        pages, from the root); ``None`` when nothing matches.  The match
        LRU-touches entries (this is a served hit)."""
        pg = self.page_size
        n_pages = min(upto, len(tokens)) // pg
        if n_pages <= 0:
            return None
        with self.lock:
            full, _ = self.index.match(tokens, n_pages * pg)
            ids = [n.page for n in full][:n_pages]
            if not ids:
                return None
            payloads, nbytes = [], 0
            for pid in ids:
                e = self.entries[pid]
                e.refs += 1
                payloads.append(e.payload)
                nbytes += e.nbytes
            self.stats.imports += 1
            self.stats.imported_pages += len(ids)
            self.stats.hit_bytes += nbytes
        return TierHandle(self, ids, payloads, nbytes, self.quantize)

    def _unpin(self, ids: List[int]) -> None:
        with self.lock:
            for pid in ids:
                e = self.entries.get(pid)
                if e is not None:
                    e.refs -= 1

    # ----------------------------------------------------------- publish
    def publish(self, tokens, upto: int,
                fetch_page: Callable[[int], tuple]) -> int:
        """Index ``tokens[:upto]`` (clipped to full pages).

        ``fetch_page(i)`` returns the ``(k, v)`` page arrays for token
        page ``i`` — consulted only for pages the tier does not already
        hold, so re-publishing a cluster-wide-known prefix copies
        nothing.  Returns the number of newly-stored pages."""
        pg = self.page_size
        upto = (min(upto, len(tokens)) // pg) * pg
        if upto <= 0:
            return 0
        with self.lock:
            created = self.index.insert(tokens, upto,
                                        self._store_page(fetch_page))
            if created:
                self.stats.publishes += 1
                self.stats.published_pages += len(created)
                self._evict_to_capacity()
        return len(created)

    def _store_page(self, fetch_page):
        def page_of(i: int) -> int:
            k, v = fetch_page(i)
            payload, nbytes = self._pack(k, v)
            pid = next(self._ids)
            self.entries[pid] = _Entry(payload, nbytes)
            self.bytes += nbytes
            return pid
        return page_of

    def _pack(self, k, v) -> Tuple[tuple, int]:
        if not self.quantize:
            k = np.asarray(k)
            v = np.asarray(v)
            return ("raw", k, v), k.nbytes + v.nbytes
        import jax.numpy as jnp
        from repro.serving.kv_cache import quantize_kv_device
        if self.interpret is None:
            raise RuntimeError("a quantizing tier needs its replicas' "
                               "kernel mode: attach it to an engine first")
        kb = jax.device_get(quantize_kv_device(jnp.asarray(k),
                                               interpret=self.interpret))
        vb = jax.device_get(quantize_kv_device(jnp.asarray(v),
                                               interpret=self.interpret))
        nbytes = sum(getattr(x, "nbytes", 0) for x in (*kb, *vb))
        return ("q8", kb, vb), nbytes

    # ------------------------------------------------------------- evict
    def _evict_to_capacity(self) -> None:
        """Drop unpinned pages (LRU leaf-first) until payload bytes fit.
        Caller holds the lock."""
        while self.bytes > self.capacity_bytes:
            freed = self.index.evict_lru(
                8, can_evict=lambda p: self.entries[p].refs == 0)
            if not freed:
                break                  # everything left is pinned
            for pid in freed:
                e = self.entries.pop(pid)
                self.bytes -= e.nbytes
                self.stats.evicted_pages += 1
                self.stats.evicted_bytes += e.nbytes
                if self.bus is not None:
                    self.bus.emit("tier_evict", replica=self.replica,
                                  bytes=e.nbytes)

    def drop_all(self) -> int:
        """Release every entry (shutdown / tests); pinned pages too, so
        only call once importers are drained."""
        with self.lock:
            pages = self.index.clear()
            self.entries.clear()
            self.bytes = 0
        return len(pages)

    # ------------------------------------------------------------- stats
    def gauges(self) -> Dict[str, float]:
        with self.lock:
            s = self.stats
            return {
                "tier_bytes": float(self.bytes),
                "tier_capacity_bytes": float(self.capacity_bytes),
                "tier_pages": float(len(self.entries)),
                "tier_utilization": self.bytes / max(self.capacity_bytes,
                                                     1.0),
                "tier_hit_bytes_total": float(s.hit_bytes),
                "tier_imports_total": float(s.imports),
                "tier_imported_pages_total": float(s.imported_pages),
                "tier_published_pages_total": float(s.published_pages),
                "tier_evicted_pages_total": float(s.evicted_pages),
            }

    def pinned_pages(self) -> int:
        with self.lock:
            return sum(1 for e in self.entries.values() if e.refs > 0)


# ------------------------------------------------------ simulator twin

class SimKVTier:
    """Analytical cluster-tier twin for ``ServingSimulator`` /
    ``core.cluster`` replicas: one shared token-level index; a tier hit
    replaces the prefix's prefill compute with ``bytes / swap_bw`` of
    import DMA."""

    def __init__(self, page_size: int, capacity_pages: int,
                 swap_bw: float):
        self.page_size = page_size
        self.sim = SimPrefixIndex(page_size, capacity_pages)
        self.swap_bw = float(swap_bw)
        self.imports = 0
        self.imported_tokens = 0

    def probe(self, tokens, cap: Optional[int] = None) -> int:
        n = self.sim.probe(tokens)
        if cap is not None:
            n = min(n, cap)
        return (n // self.page_size) * self.page_size

    def hit(self, tokens, cap: int) -> int:
        """Served hit (LRU-touching), floored to full pages."""
        n = (self.sim.hit(tokens, cap) // self.page_size) * self.page_size
        if n > 0:
            self.imports += 1
            self.imported_tokens += n
        return n

    def insert(self, tokens, upto: int) -> int:
        return self.sim.insert(tokens, upto)

    def import_time(self, n_tokens: int, bytes_per_token: float) -> float:
        return (n_tokens * bytes_per_token) / max(self.swap_bw, 1e-9)
