"""Pluggable KV backends for the serving engine + the paged KV page pool.

Two layers live here:

  * :class:`PagedKVPool` — the vLLM-style block allocator (physical pages +
    per-request page tables) the paper's baseline uses and ALISE's
    request-level swapping sits on: pages for a request can be freed,
    offloaded (optionally INT8), and re-materialized without moving other
    requests' pages.
  * :class:`KVBackend` — the engine-facing abstraction over device KV
    residency.  :class:`DenseKVBackend` keeps the original slotted dense
    cache (one ``(B, Smax, ...)`` buffer per layer); :class:`PagedKVBackend`
    stores KV in the page pool and decodes through the paged-attention path.
    Both expose the same interface: decode-lane (slot) assignment, prefill
    KV placement, request-granular offload/upload blobs, and ``decode()`` —
    **one fused jitted dispatch per iteration** that samples tokens and
    computes termination flags on device (no per-slot host sync).
    Both run one forward, ``Model.forward_paged``, for decode, verify-k,
    chunks and packs: the dense cache is a pool of one ``Smax`` page per
    slot (``models.model.stripe_view``), so greedy outputs agree across
    the backends by construction.

Offload/upload runs through the Pallas ``kv_quant`` kernels when
``quantize_offload`` is set: KV is quantized **on device** and the host link
carries the INT8 payload + per-row scales (paper Eq. 8), instead of moving
fp tensors and quantizing in host numpy.

Every program is jitted from a named function (:func:`named_program`), so
the profiler's trace names its executions ``jit_alise_<what>``; a decode
dispatch runs in the host spans ``alise.decode.stage`` (page tables and
lane arrays packed into one int32 buffer, sent to the device in one
transfer: :func:`lane_buffer`), ``alise.decode.dispatch`` and
``alise.decode.sync``; the program unpacks the buffer
(:func:`decode_program`).

The paged decode step gathers only as many pages per lane as its page table
is wide, and that width follows the live context: the smallest rung of
:func:`decode_width_ladder` covering the longest active lane
(:func:`decode_table_width`).  Positions past a lane's end are masked before
the softmax, so a narrower table changes no result, only the copy.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.kv_quant import kv_dequantize_op, kv_quantize_op
from repro.models.model import stripe_view
from repro.serving.observability.span import span
from repro.serving.sampler import decode_epilogue, verify_epilogue

_QBLK = 128                                   # kv_quant row-tile

# Every jitted cache-update dispatch donates the cache/pool it rewrites, so
# XLA updates it in place instead of holding a second whole-cache buffer.
# Donation deletes the caller's input buffer on every backend (CPU
# included): callers rebind the returned arrays and never touch the
# donated ones again.


def named_program(name: str, fn, **static):
    """``fn`` with the keywords ``static`` bound, as a function called
    ``name``: JAX names a jitted program after its function, so its
    executions read ``jit_<name>`` in a profiler trace."""
    def program(*args):
        return fn(*args, **static)
    program.__name__ = program.__qualname__ = name
    return program


# A decode dispatch's host inputs travel as ONE (B, W) int32 buffer: one
# host-to-device copy a step instead of one per array.  Its layout gives,
# per column group in argument order, the array's shape past the lane axis
# (() or (n,)) and whether it is bool (stored as 0/1).
LaneLayout = Tuple[Tuple[Tuple[int, ...], bool], ...]


def lane_buffer(B: int, *cols) -> Tuple[np.ndarray, LaneLayout, list]:
    """One decode step's host inputs packed column by column, in order,
    into one ``(B, W)`` int32 buffer.  A column is a host array, ``(B,)``
    or ``(B, n)``, int32 or bool, copied in; or a shape tail, ``()`` or
    ``(n,)``, for int32 columns the caller fills in place.  Returns the
    buffer, its layout and a writable view of each column."""
    layout = tuple((c, False) if isinstance(c, tuple)
                   else (np.shape(c)[1:], np.asarray(c).dtype == bool)
                   for c in cols)
    buf = np.zeros((B, sum(t[0] if t else 1 for t, _ in layout)), np.int32)
    views, at = [], 0
    for (tail, _), c in zip(layout, cols):
        w = tail[0] if tail else 1
        view = buf[:, at:at + w] if tail else buf[:, at]
        if not isinstance(c, tuple):
            view[...] = c
        views.append(view)
        at += w
    return buf, layout, views


def unpack_lanes(packed, layout: LaneLayout) -> list:
    """Inside a program: the arrays :func:`lane_buffer` packed, split back
    by static column slices, bool columns as ``!= 0``."""
    out, at = [], 0
    for tail, is_bool in layout:
        w = tail[0] if tail else 1
        col = packed[:, at:at + w] if tail else packed[:, at]
        out.append(col != 0 if is_bool else col)
        at += w
    return out


def decode_program(name: str, fn, **static):
    """``fn(params, kv, *lane arrays, base_key, **static)`` jitted as the
    program ``name`` that takes ``(params, kv, packed, base_key, layout)``:
    it unpacks the lane arrays from ``packed`` (``layout`` is static, so a
    program compiles once per buffer shape) and donates ``kv``."""
    def unpacked(params, kv, packed, base_key, layout, **kw):
        return fn(params, kv, *unpack_lanes(packed, layout), base_key, **kw)
    return jax.jit(named_program(name, unpacked, **static),
                   static_argnums=(4,), donate_argnums=(1,))


# ------------------------------------------------ programs over one forward
# Each program composes ``Model.forward_paged`` (the dense cache of the
# other families: ``Model.decode_step``) with the lm head, only where its
# logits are needed, and a sampler epilogue.  ``model`` is bound first.

def paged_decode(model, params, kv, tokens, tables, lens, write_page,
                 write_off, active, new_gen, new_ctx, true_len, rids,
                 base_key, *, attn_impl: str = "gather",
                 interpret: bool = False, **sample):
    """The fused paged decode step: ``tokens`` (B, 1) at positions ``lens``
    (B,), each token's KV written at ``(write_page, write_off)`` (B,).
    Returns ``(sampled (B,), reason (B,), new_kv)``."""
    h, kv = model.forward_paged(params, kv, tokens, tables,
                                write_page[:, None], write_off[:, None],
                                lens, attn_impl=attn_impl,
                                interpret=interpret)
    tok, reason = decode_epilogue(model.logits(params, h[:, -1]), active,
                                  new_gen, new_ctx, true_len, rids,
                                  base_key, **sample)
    return tok, reason, kv


def paged_verify(model, params, kv, tokens, tables, lens, write_page,
                 write_off, n_drafts, active, base_gen, base_ctx, true_len,
                 rids, base_key, *, interpret: bool = False, **sample):
    """The fused paged verify-k step: ``tokens`` (B, K1) at ``lens + i``,
    written at ``(write_page, write_off)`` (B, K1).  Returns ``(samples
    (B, K1), n_emit (B,), reason (B,), new_kv)``; the backend commits
    page-table state after the sync."""
    h, kv = model.forward_paged(params, kv, tokens, tables, write_page,
                                write_off, lens, interpret=interpret)
    s, n_emit, reason = verify_epilogue(
        model.logits(params, h), tokens, n_drafts, active, base_gen,
        base_ctx, true_len, rids, base_key, **sample)
    return s, n_emit, reason, kv


def paged_prefill(model, params, kv, tokens, tables, write_page, write_off,
                  start, chunk_len, *, interpret: bool = False):
    """Prefill chunks into the page pool: ``tokens`` (N, C) right-padded,
    ``start``/``chunk_len`` (N,) and write targets (N, C) — or, for one
    chunk, scalars and (C,).  Returns ``(last_logits (N, V), new_kv)``."""
    N, C = tokens.shape
    h, kv = model.forward_paged(params, kv, tokens, tables,
                                write_page.reshape(N, C),
                                write_off.reshape(N, C), start.reshape(N),
                                interpret=interpret)
    return model.chunk_logits(params, h, chunk_len.reshape(N)), kv


def dense_decode(model, params, cache, tokens, active, new_gen, new_ctx,
                 true_len, rids, base_key, **sample):
    """The fused decode step over the dense cache of any family: lanes
    not ``active`` keep their ``lengths``.  Returns ``(sampled, reason,
    new_cache)``."""
    logits, cache = model.decode_step(params, cache, tokens)
    lengths = cache["lengths"]
    cache = {**cache, "lengths": jnp.where(active, lengths, lengths - 1)}
    tok, reason = decode_epilogue(logits, active, new_gen, new_ctx,
                                  true_len, rids, base_key, **sample)
    return tok, reason, cache


def dense_verify(model, params, cache, tokens, n_drafts, active, base_gen,
                 base_ctx, true_len, rids, base_key, *,
                 interpret: bool = False, **sample):
    """The fused verify-k step over the dense stripes: accepted lanes
    advance ``lengths`` by ``n_emit``; rejected positions' KV stays past
    the watermark, never attended and overwritten by the next dispatch.
    Returns ``(samples, n_emit, reason, new_cache)``."""
    B, K1 = tokens.shape
    lengths = cache["lengths"]
    h, kv = model.forward_paged(
        params, {"k": cache["k"], "v": cache["v"]}, tokens,
        *stripe_view(jnp.arange(B), lengths, K1), lengths,
        interpret=interpret)
    s, n_emit, reason = verify_epilogue(
        model.logits(params, h), tokens, n_drafts, active, base_gen,
        base_ctx, true_len, rids, base_key, **sample)
    return s, n_emit, reason, {**cache, **kv, "lengths": lengths + n_emit}


def dense_prefill(model, params, k_cache, v_cache, tokens, slots, start,
                  chunk_len, *, interpret: bool = False):
    """Prefill chunks into the dense stripes of ``slots``: ``tokens``
    (N, C), ``slots``/``start``/``chunk_len`` (N,) (scalars for one
    chunk).  A dummy row's out-of-range slot reads a clamped stripe and
    writes nothing.  Returns ``(last_logits (N, V), new_k, new_v)``."""
    N, C = tokens.shape
    start = start.reshape(N)
    h, kv = model.forward_paged(
        params, {"k": k_cache, "v": v_cache}, tokens,
        *stripe_view(slots.reshape(N), start, C), start, interpret=interpret)
    return (model.chunk_logits(params, h, chunk_len.reshape(N)),
            kv["k"], kv["v"])


def decode_width_ladder(max_seq_len: int, page_size: int) -> Tuple[int, ...]:
    """The page-table widths a paged decode step is compiled at: multiples
    of an eighth of a full table (at least one page), up to the full
    ``max_seq_len // page_size`` pages."""
    full = max_seq_len // page_size
    step = max(1, full // 8)
    return tuple(range(step, full, step)) + (full,)


def decode_table_width(ladder: Sequence[int], pages: Iterable[int]) -> int:
    """The decode page-table width for a batch whose active lanes hold
    ``pages`` pages each, counted after the step's extend: the smallest
    rung of ``ladder`` covering the largest (the smallest rung when no lane
    is active)."""
    need = max(pages, default=0)
    return next(w for w in ladder if w >= need)


# --------------------------------------------------------------- page pool

@dataclass
class PagedKVConfig:
    num_pages: int = 256
    page_size: int = 16
    num_kv_heads: int = 8
    head_dim: int = 64
    num_layers: int = 4
    dtype: str = "float32"


class PagedKVPool:
    """Physical page pool + per-request page tables (one layer set each).

    Pages are **refcounted** so the shared-prefix cache can alias one
    physical page into many page tables (and its own index): every table
    entry and every index entry holds one reference; a page returns to
    the free list only when its count reaches zero.  The classic
    single-owner paths (allocate/extend/free) are the refcount-1 special
    case, so existing callers are unchanged.
    """

    def __init__(self, cfg: PagedKVConfig):
        self.cfg = cfg
        shape = (cfg.num_layers, cfg.num_pages, cfg.page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        self.k = jnp.zeros(shape, cfg.dtype)
        self.v = jnp.zeros(shape, cfg.dtype)
        self.free_pages: List[int] = list(range(cfg.num_pages))
        self.page_table: Dict[int, List[int]] = {}       # req -> pages
        self.lengths: Dict[int, int] = {}
        self.refs: Dict[int, int] = {}                   # page -> refcount
        # CoW page duplication and page-granular writes as jitted, donated
        # dispatches: an eager at[].set would materialize a whole new pool
        def alise_kv_cow(k, v, s, d):
            return (k.at[:, d].set(k[:, s][:, None]),
                    v.at[:, d].set(v[:, s][:, None]))

        def alise_kv_scatter(k, v, idx, ks, vs):
            return (k.at[:, idx].set(ks.astype(k.dtype)),
                    v.at[:, idx].set(vs.astype(v.dtype)))
        self._cow_copy = jax.jit(alise_kv_cow, donate_argnums=(0, 1))
        self._scatter = jax.jit(alise_kv_scatter, donate_argnums=(0, 1))

    # ----------------------------------------------------------- refcounts
    def take_page(self) -> int:
        """Claim one free page (refcount 1)."""
        page = self.free_pages.pop()
        self.refs[page] = 1
        return page

    def incref(self, page: int) -> None:
        self.refs[page] = self.refs.get(page, 0) + 1

    def decref(self, page: int) -> int:
        """Drop one reference; a page at zero returns to the free list."""
        n = self.refs.get(page, 0) - 1
        if n <= 0:
            self.refs.pop(page, None)
            self.free_pages.append(page)
            return 0
        self.refs[page] = n
        return n

    def cow_page(self, src: int) -> int:
        """Copy-on-write: duplicate ``src``'s KV into a fresh page the
        caller owns exclusively — one jitted dispatch, pool buffers
        donated (in-place on TPU), dynamic indices so every (src, dst)
        pair reuses the same compiled program."""
        dst = self.take_page()
        self.k, self.v = self._cow_copy(self.k, self.v, jnp.asarray(src),
                                        jnp.asarray([dst]))
        return dst

    def scatter_pages(self, idx, k, v) -> None:
        """Write whole pages in place: ``k``/``v`` (L, len(idx), page,
        KVH, d) land at physical pages ``idx`` of every layer."""
        self.k, self.v = self._scatter(self.k, self.v, jnp.asarray(idx),
                                       k, v)

    # ------------------------------------------------------------ allocator
    def pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.cfg.page_size)

    def can_allocate(self, tokens: int) -> bool:
        return len(self.free_pages) >= self.pages_needed(tokens)

    def allocate(self, req_id: int, tokens: int) -> List[int]:
        n = self.pages_needed(tokens)
        if len(self.free_pages) < n:
            raise RuntimeError(
                f"page pool exhausted: need {n}, free {len(self.free_pages)}")
        pages = [self.take_page() for _ in range(n)]
        self.page_table[req_id] = pages
        self.lengths[req_id] = tokens
        return pages

    def extend(self, req_id: int, new_tokens: int = 1) -> Optional[int]:
        """Grow a sequence; returns a newly-allocated page id or None."""
        length = self.lengths[req_id] + new_tokens
        need = self.pages_needed(length)
        new_page = None
        if need > len(self.page_table[req_id]):
            if not self.free_pages:
                raise RuntimeError("page pool exhausted on extend")
            new_page = self.take_page()
            self.page_table[req_id].append(new_page)
        self.lengths[req_id] = length
        return new_page

    def extend_to(self, req_id: int, tokens: int) -> None:
        """Grow a sequence to cover ``tokens`` logical positions (chunked
        prefill: each chunk extends coverage, including mid-page boundaries
        where the next chunk continues inside a partially-filled page)."""
        pages = self.page_table[req_id]
        need = self.pages_needed(tokens)
        while len(pages) < need:
            if not self.free_pages:
                raise RuntimeError("page pool exhausted on extend_to")
            pages.append(self.take_page())
        self.lengths[req_id] = max(self.lengths.get(req_id, 0), tokens)

    def reserve_scratch(self) -> int:
        """Permanently remove one physical page from the allocator — the
        sacrificial write target for inactive decode lanes in the fused
        batched step (their token writes must land *somewhere* harmless)."""
        return self.take_page()

    def free(self, req_id: int) -> None:
        for page in self.page_table.pop(req_id, []):
            self.decref(page)
        self.lengths.pop(req_id, None)

    def utilization(self) -> float:
        return 1.0 - len(self.free_pages) / self.cfg.num_pages

    # ------------------------------------------------------------- KV write
    def write_tokens(self, req_id: int, layer: int, pos: int, k_new, v_new):
        """Write one token's KV at logical position pos.  k_new: (KVH, d)."""
        pages = self.page_table[req_id]
        page = pages[pos // self.cfg.page_size]
        off = pos % self.cfg.page_size
        self.k = self.k.at[layer, page, off].set(k_new.astype(self.k.dtype))
        self.v = self.v.at[layer, page, off].set(v_new.astype(self.v.dtype))

    def write_prefill(self, req_id: int, k, v) -> List[int]:
        """Allocate pages for a fresh sequence and scatter its prefill KV in
        one device op per tensor.  k/v: (L, S, KVH, d) device arrays."""
        S = k.shape[1]
        pages = self.allocate(req_id, S)
        pg = self.cfg.page_size
        pad = len(pages) * pg - S
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        self.scatter_pages(
            pages, k.reshape(k.shape[0], len(pages), pg, *k.shape[2:]),
            v.reshape(v.shape[0], len(pages), pg, *v.shape[2:]))
        return pages

    def block_table_array(self, req_ids: List[int]) -> tuple:
        """(tables (B, max_pages) int32, lengths (B,) int32) padded."""
        max_pages = max((len(self.page_table[r]) for r in req_ids), default=1)
        tables = np.zeros((len(req_ids), max_pages), np.int32)
        lens = np.zeros((len(req_ids),), np.int32)
        for i, r in enumerate(req_ids):
            pages = self.page_table[r]
            tables[i, :len(pages)] = pages
            lens[i] = self.lengths[r]
        return jnp.asarray(tables), jnp.asarray(lens)

    # ----------------------------------------------------------- swap paths
    def snapshot(self, req_id: int) -> dict:
        """Copy a request's pages to host (offload unit)."""
        pages = self.page_table[req_id]
        idx = jnp.asarray(pages)
        return {"k": np.asarray(self.k[:, idx]),
                "v": np.asarray(self.v[:, idx]),
                "tokens": self.lengths[req_id]}

    def restore(self, req_id: int, snap: dict) -> None:
        pages = self.allocate(req_id, snap["tokens"])
        self.scatter_pages(pages, jnp.asarray(snap["k"]),
                           jnp.asarray(snap["v"]))


# ------------------------------------------------- device-side quant blobs

def quantize_kv_device(x, *, interpret: bool) -> tuple:
    """INT8-quantize an arbitrary-rank KV tensor on device via the Pallas
    ``kv_quantize`` kernel (per (token, head) row over the last axis) and
    pull the *INT8* payload to host — the host link carries half the bytes
    of the fp tensor (Eq. 8), unlike the old host-numpy ``quantize_np``
    path which shipped fp32 first.  ``interpret`` is the Pallas mode of
    the device ``x`` lives on.  Returns ``(q, lam, z, shape)``."""
    shape = tuple(x.shape)
    d = shape[-1]
    rows = int(np.prod(shape[:-1]))
    flat = jnp.reshape(x, (rows, d)).astype(jnp.float32)
    pad = (-rows) % _QBLK
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    q, lam, z = kv_quantize_op(flat, blk=min(_QBLK, flat.shape[0]),
                               interpret=interpret)
    q, lam, z = jax.device_get((q[:rows], lam[:rows], z[:rows]))
    return np.asarray(q), np.asarray(lam), np.asarray(z), shape


def dequantize_kv_device(blob: tuple, dtype=jnp.float32, *,
                         interpret: bool):
    """Inverse of :func:`quantize_kv_device`: upload INT8 + scales, run the
    Pallas ``kv_dequantize`` kernel on device, reshape to the saved shape."""
    q, lam, z, shape = blob
    rows = q.shape[0]
    pad = (-rows) % _QBLK
    qj, lj, zj = jnp.asarray(q), jnp.asarray(lam), jnp.asarray(z)
    if pad:
        qj = jnp.pad(qj, ((0, pad), (0, 0)))
        lj = jnp.pad(lj, ((0, pad), (0, 0)))
        zj = jnp.pad(zj, ((0, pad), (0, 0)))
    x = kv_dequantize_op(qj, lj, zj, dtype=dtype,
                         blk=min(_QBLK, qj.shape[0]), interpret=interpret)
    return x[:rows].reshape(shape)


# ---------------------------------------------------------------- backends

@dataclass
class KVBackendConfig:
    """Static knobs a backend needs to build its fused decode dispatch."""
    max_slots: int
    max_seq_len: int
    eos_token: int = 1
    max_new_tokens: int = 128
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0
    quantize_offload: bool = True
    page_size: int = 16            # paged backend only
    attn_impl: str = "gather"      # paged attention: gather | kernel
    prefix_cache: bool = False     # cross-request shared-prefix KV cache
    prefix_cache_pages: int = 0    # dense backend: private store capacity
                                   # (0 = one full batch of stripes)
    seed: int = 0
    prefill_buckets: Optional[Tuple[int, ...]] = None
    # fixed, sorted menu of chunk-shape buckets: chunk dispatch shapes are
    # rounded up to the nearest entry (instead of lazy pow2 bucketing), so
    # an explicit warmup pass can pre-compile every serve-time shape
    prefill_pack_width: int = 4    # segment rows per packed-prefill dispatch
    spec_k: int = 0                # speculative verify-k draft width
                                   # (0 = plain one-token fused decode)


class KVBackend:
    """Engine-facing device KV residency + the fused in-JIT decode step.

    Decode lanes ("slots") give the decode batch its fixed shape; the
    backing storage is implementation-defined (dense per-slot buffers or a
    shared page pool).  ``decode()`` is the hot path: one jitted dispatch
    covering token embedding, the layer stack, KV writes, attention,
    sampling, and termination — the engine syncs a single small
    ``(tokens, reasons)`` pair per iteration.
    """

    def __init__(self, model, cfg: KVBackendConfig, device):
        self.model = model
        self.cfg = cfg
        # the one place the Pallas mode is decided: from the platform of
        # the device this backend's storage and dispatches live on
        self.device = device
        self.interpret = interpret_mode(device.platform)
        self.slot_req: List[Optional[int]] = [None] * cfg.max_slots
        self.prefix = None                 # shared-prefix cache (optional)
        # sampling keys are derived per (request id, token index) inside the
        # jitted dispatch (sampler.token_keys): the stream is independent of
        # batch composition, warmup, preemption, and spec-on/off
        self._base_key = jax.random.PRNGKey(cfg.seed)
        # decode steps served per page-table width, keyed by the positions
        # gathered per lane (the paged backend's ladder; empty on dense)
        self.decode_width_steps: Dict[int, int] = {}
        # host-to-device copies made staging decode steps: one a step
        self.decode_h2d_transfers = 0

    # --------------------------------------------------------------- lanes
    def slot_of(self, rid: int) -> Optional[int]:
        try:
            return self.slot_req.index(rid)
        except ValueError:
            return None

    def has(self, rid: int) -> bool:
        return rid in self.slot_req

    def free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _sample_kwargs(self) -> dict:
        c = self.cfg
        return dict(greedy_sampling=c.greedy, temp=c.temperature,
                    top_k=c.top_k, eos_token=c.eos_token,
                    max_new_tokens=c.max_new_tokens,
                    max_seq_len=c.max_seq_len)

    @staticmethod
    def _pow2_bucket(n: int) -> int:
        """Pow2 chunk-length buckets (min 8) bound jit recompiles."""
        return max(8, 1 << (n - 1).bit_length())

    def _chunk_bucket(self, n: int) -> int:
        """Dispatch-shape bucket for an ``n``-token chunk: the smallest
        entry of the fixed ``prefill_buckets`` menu covering it (so warmup
        can pre-compile every shape), else the legacy lazy pow2 bucket."""
        menu = self.cfg.prefill_buckets
        if menu:
            for b in menu:
                if b >= n:
                    return b
            raise ValueError(
                f"{n}-token chunk exceeds the largest prefill bucket "
                f"{menu[-1]}; the scheduler must clamp chunk spans")
        return self._pow2_bucket(n)

    def _stage(self, buf: np.ndarray):
        """A :func:`lane_buffer` on the device, in one copy.  Uncommitted,
        as every array this backend stages: the engine runs its backend
        under its device as the default (``ServingEngine._on_device``),
        and a committed input would key the programs apart from warm-up."""
        self.decode_h2d_transfers += 1
        return jax.device_put(buf)

    def _dispatch_sync(self, program, params, kv, packed, layout):
        """One decode dispatch and the wait for its sampled outputs: returns
        (the outputs before the KV, as numpy arrays; the new KV)."""
        with span("decode.dispatch"):
            *out, kv = program(params, kv, packed, self._base_key, layout)
        with span("decode.sync"):
            out = jax.device_get(out)
        return [np.asarray(o) for o in out], kv

    # ----------------------------------------------------------- interface
    def write_prefill(self, rid: int, pcache, length: int) -> None:
        """Place batch-index-0 of a prefill cache into a free lane."""
        raise NotImplementedError

    def prefill_chunk(self, params, rid: int, tokens: List[int],
                      start: int):
        """Run one resumable prefill chunk for ``rid``: write KV for
        absolute positions ``[start, start+len(tokens))`` device-side
        (assigning a lane on the first chunk) and return the chunk's
        last-position logits (jnp (1, V)) — the prompt's next-token logits
        when this chunk completes the prefill target."""
        raise NotImplementedError

    def supports_pack(self) -> bool:
        """Whether :meth:`prefill_pack` is available (packed multi-request
        chunk dispatch)."""
        return False

    def prefill_pack(self, params, items: Sequence[Tuple[int, List[int],
                                                         int]],
                     bucket: int = 0):
        """Run several requests' prefill chunks as ONE dispatch.

        ``items``: up to ``prefill_pack_width`` tuples ``(rid, tokens,
        start)`` — distinct requests, every chunk's dispatch shape rounded
        to the same ``bucket`` (0 = derive from the longest member).
        Returns per-segment last-position logits, jnp (len(items), V), in
        item order."""
        raise NotImplementedError

    def chunk_pages_shortfall(self, rid: int, end: int) -> int:
        """Physical pages missing to extend ``rid``'s KV coverage to
        ``end`` tokens (always 0 for the dense backend)."""
        return 0

    def clear(self, rid: int) -> None:
        raise NotImplementedError

    def offload(self, rid: int) -> dict:
        """Detach a request's KV into a host blob (INT8 via the Pallas
        kv_quant kernel when quantize_offload); frees its lane/pages."""
        raise NotImplementedError

    def upload(self, rid: int, blob: dict) -> None:
        raise NotImplementedError

    def decode(self, params, tokens, active, new_gen, new_ctx, true_len,
               rids):
        """One fused iteration -> (sampled (B,), reason (B,)) numpy."""
        raise NotImplementedError

    def decode_width(self, rids: Sequence[int]) -> int:
        """Positions per lane the next decode step over lanes ``rids``
        attends over: the full stripe here."""
        return self.cfg.max_seq_len

    def warm_decode(self, params, *args) -> None:
        """Run :meth:`decode` (``args`` as it takes them, every lane
        inactive) at every shape serving dispatches, so that none compiles
        later."""
        self.decode(params, *args)

    def supports_spec_decode(self) -> bool:
        """Whether :meth:`decode_verify` is available (``spec_k > 0`` and
        the model family supports the verify-k dispatch)."""
        return False

    def decode_verify(self, params, tokens, n_drafts, active, base_gen,
                      base_ctx, true_len, rids):
        """One fused verify-k iteration.

        ``tokens``: (B, spec_k+1) int — column 0 each lane's fed previous
        token, columns 1..k its draft tokens (zero-padded past
        ``n_drafts[b]``; padding is never matched).  ``base_gen``/
        ``base_ctx``: per-lane generated count / context length *before*
        the dispatch.  Returns ``(samples (B, spec_k+1), n_emit (B,),
        reason (B,))`` numpy — the caller accepts ``samples[b, :n_emit[b]]``
        and applies ``reason[b]`` to the last accepted token; KV/page state
        for each lane advances by exactly ``n_emit[b]`` positions
        (speculative writes past that are rolled back)."""
        raise NotImplementedError

    def pages_shortfall(self, rids: List[int]) -> int:
        """Physical pages missing to decode one token for each of ``rids``
        (always 0 for the dense backend)."""
        return 0

    # --------------------------------------------- shared-prefix cache
    def prefix_probe(self, tokens) -> int:
        """Expected cached-prefix hit length for ``tokens`` (pricing /
        routing hint; touch-free, so probes cannot skew the LRU)."""
        if self.prefix is None or not tokens:
            return 0
        return self.prefix.probe(list(tokens))

    def prefix_acquire(self, rid: int, tokens) -> int:
        """Materialize the longest cached prefix of ``tokens`` for ``rid``
        (claiming its decode lane) and return the hit length — the
        request's starting ``prefilled`` watermark.  0 = miss / disabled."""
        return 0

    def prefix_publish(self, rid: int, tokens, upto: int) -> int:
        """Share ``rid``'s materialized KV for ``tokens[:upto]`` (full
        pages only) back into the index; returns pages newly shared."""
        return 0

    def prefix_reclaim(self, n_pages: int) -> int:
        """Evict up to ``n_pages`` cached-but-unreferenced pages (LRU) —
        the first spill victims, ahead of any resident job's pages."""
        if self.prefix is None:
            return 0
        return self.prefix.reclaim(n_pages)

    def prefix_pages(self):
        """(pages held by the cache, pages reclaimable right now)."""
        if self.prefix is None:
            return (0, 0)
        return self.prefix.held_pages()

    def prefix_stats(self):
        return None if self.prefix is None else self.prefix.stats


class DenseKVBackend(KVBackend):
    """The original slotted dense cache behind the KVBackend interface.

    Storage is ``model.init_cache(max_slots, max_seq_len)``; every slot owns
    a full ``max_seq_len`` stripe.  Supports every model family (attention,
    SSM, hybrid, enc-dec) — per-key batch axes come from the cache spec.
    """

    def __init__(self, model, cfg: KVBackendConfig, device):
        super().__init__(model, cfg, device)
        self.cache = model.init_cache(cfg.max_slots, cfg.max_seq_len)
        self._axes = self._cache_batch_axes()
        # the cache pytree (arg 1) is consumed and fully re-emitted by the
        # fused step: donate it so it updates in place
        self._fused = decode_program(
            "alise_decode", functools.partial(dense_decode, model),
            **self._sample_kwargs())
        self._fused_verify = None
        if cfg.spec_k > 0 and model.supports_paged():
            self._fused_verify = decode_program(
                "alise_decode_verify", functools.partial(dense_verify, model),
                interpret=self.interpret, **self._sample_kwargs())
        # chunk and pack programs run the one forward over the whole cache
        # (donated, written in place), each row's stripe a one-page table
        self._chunk = self._pack = None
        if model.supports_paged():
            self._chunk = self._prefill_program("alise_prefill_chunk")
        if model.supports_prefill_pack():
            self._pack = self._prefill_program("alise_prefill_pack")
        if cfg.prefix_cache and model.supports_paged():
            from repro.serving.prefix_cache import DensePrefixCache
            acfg = model.cfg
            capacity = cfg.prefix_cache_pages or (
                cfg.max_slots * cfg.max_seq_len // cfg.page_size)
            self.prefix = DensePrefixCache(
                acfg.num_layers, acfg.num_kv_heads, acfg.hd,
                cfg.page_size, capacity, self.cache["k"].dtype)

            # hit placement as one jitted, cache-donated dispatch (the
            # eager per-tensor at[].set would copy the whole cache twice)
            def alise_kv_place(kc, vc, lengths, k, v, slot, hit):
                span = k.shape[1]
                kc = kc.at[:, slot, :span].set(k.astype(kc.dtype))
                vc = vc.at[:, slot, :span].set(v.astype(vc.dtype))
                return kc, vc, lengths.at[slot].set(hit)
            self._place = jax.jit(alise_kv_place, donate_argnums=(0, 1, 2))

    def _prefill_program(self, name: str):
        return jax.jit(named_program(
            name, functools.partial(dense_prefill, self.model),
            interpret=self.interpret), donate_argnums=(1, 2))

    def _cache_batch_axes(self) -> Dict[str, int]:
        fam = self.model.cfg.family
        axes = {"lengths": 0}
        if fam == "ssm":
            axes.update(conv=1, ssm=1)
        elif fam == "hybrid":
            axes.update(k=1, v=1, conv=2, ssm=2)
        else:
            axes.update(k=1, v=1)
            if self.model.cfg.is_encoder_decoder:
                axes.update(xk=1, xv=1)
        return axes

    # ------------------------------------------------------------ helpers
    def _slot_view(self, slot: int) -> Dict[str, jnp.ndarray]:
        return {key: jnp.take(arr, slot, axis=self._axes[key])
                for key, arr in self.cache.items()}

    def _write_slot(self, slot: int, data: Dict) -> None:
        new = {}
        for key, arr in self.cache.items():
            ax = self._axes[key]
            idx = [slice(None)] * arr.ndim
            idx[ax] = slot
            new[key] = arr.at[tuple(idx)].set(
                jnp.asarray(data[key]).astype(arr.dtype))
        self.cache = new

    def _slot_shape(self, key: str) -> list:
        arr = self.cache[key]
        shape = list(arr.shape)
        del shape[self._axes[key]]
        return shape

    # ---------------------------------------------------------- interface
    def write_prefill(self, rid: int, pcache, length: int) -> None:
        slot = self.free_slot()
        assert slot is not None, "caller must check slot availability"
        data = {}
        for key, arr in self.cache.items():
            ax = self._axes[key]
            if key == "lengths":
                data[key] = jnp.asarray(length, jnp.int32)
                continue
            src = jnp.take(pcache[key], 0, axis=ax)
            if key in ("k", "v"):   # seq axis: trim bucket pad, pad to Smax
                buf = jnp.zeros(self._slot_shape(key), arr.dtype)
                buf = buf.at[:, :length].set(
                    src[:, :length].astype(arr.dtype))
                data[key] = buf
            else:
                data[key] = src
        self._write_slot(slot, data)
        self.slot_req[slot] = rid

    def prefill_chunk(self, params, rid: int, tokens: List[int],
                      start: int):
        slot = self.slot_of(rid)
        if slot is None:                    # first chunk: claim a lane
            slot = self.free_slot()
            assert slot is not None, "caller must check slot availability"
            self.slot_req[slot] = rid
        C = len(tokens)
        Cb = self._chunk_bucket(C)
        toks = jnp.asarray(list(tokens) + [0] * (Cb - C), jnp.int32)[None, :]
        logits, k_new, v_new = self._chunk(
            params, self.cache["k"], self.cache["v"], toks,
            jnp.asarray(slot, jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.asarray(C, jnp.int32))
        self.cache = {**self.cache, "k": k_new, "v": v_new,
                      "lengths": self.cache["lengths"].at[slot].set(start + C)}
        return logits

    def supports_pack(self) -> bool:
        return self._pack is not None

    def prefill_pack(self, params, items, bucket: int = 0):
        N = self.cfg.prefill_pack_width
        assert self._pack is not None, "model cannot pack prefills"
        assert 0 < len(items) <= N, f"pack of {len(items)} > width {N}"
        Cb = bucket or self._chunk_bucket(max(len(t) for _, t, _ in items))
        toks = np.zeros((N, Cb), np.int32)
        starts = np.zeros((N,), np.int32)
        lens = np.zeros((N,), np.int32)
        # dummy rows target slot == max_slots: out of range by one, so the
        # forward's gather clamps and its KV writes are dropped
        slots = np.full((N,), self.cfg.max_slots, np.int32)
        for i, (rid, tokens, start) in enumerate(items):
            slot = self.slot_of(rid)
            if slot is None:                # first chunk: claim a lane
                slot = self.free_slot()
                assert slot is not None, "caller must check slot availability"
                self.slot_req[slot] = rid
            C = len(tokens)
            assert C <= Cb, f"{C}-token member exceeds pack bucket {Cb}"
            toks[i, :C] = tokens
            starts[i], lens[i], slots[i] = start, C, slot
        logits, k_new, v_new = self._pack(
            params, self.cache["k"], self.cache["v"], jnp.asarray(toks),
            jnp.asarray(slots), jnp.asarray(starts), jnp.asarray(lens))
        lengths = np.array(self.cache["lengths"])
        lengths[slots[:len(items)]] = starts[:len(items)] + lens[:len(items)]
        self.cache = {**self.cache, "k": k_new, "v": v_new,
                      "lengths": jnp.asarray(lengths)}
        # numpy (device_get, not a compile): an eager jnp slice here would
        # recompile for every distinct pack occupancy
        return np.asarray(logits)[:len(items)]

    # --------------------------------------------- shared-prefix cache
    def prefix_acquire(self, rid: int, tokens) -> int:
        """Copy-based hit: claim a lane and copy the cached prefix's KV
        from the private page store into the slot stripe, so chunked
        prefill resumes at the hit watermark (the prefix's prefill
        compute — the TTFT-dominant cost — is skipped)."""
        if self.prefix is None or self.has(rid):
            return 0
        slot = self.free_slot()
        if slot is None:
            return 0
        hit, k, v = self.prefix.fetch(list(tokens))
        if hit == 0:
            return 0
        self.slot_req[slot] = rid
        # the fetched span is page-bucketed (pow2): positions past `hit`
        # carry pad garbage that chunked prefill overwrites before any
        # query attends there, and the placement compiles O(log) programs
        span = min(k.shape[1], self.cfg.max_seq_len)
        kc, vc, lengths = self._place(
            self.cache["k"], self.cache["v"], self.cache["lengths"],
            k[:, :span], v[:, :span], jnp.asarray(slot),
            jnp.asarray(hit, jnp.int32))
        self.cache = {**self.cache, "k": kc, "v": vc, "lengths": lengths}
        return hit

    def prefix_publish(self, rid: int, tokens, upto: int) -> int:
        if self.prefix is None:
            return 0
        slot = self.slot_of(rid)
        if slot is None:
            return 0
        return self.prefix.publish(list(tokens), upto,
                                   self.cache["k"][:, slot],
                                   self.cache["v"][:, slot])

    def tier_fill(self, tokens, handle) -> int:
        """Land a cluster-tier prefix import in the private dense prefix
        store.  Dense slots can't alias host pages, so the import is a
        normal publish whose source is the tier payload instead of a
        slot stripe; the caller's ``prefix_acquire`` then copy-fetches
        it like any local hit.  Returns the cached token watermark."""
        if self.prefix is None or handle is None:
            return 0
        pg = self.prefix.page_size
        toks = list(tokens)[:handle.tokens]
        n = len(toks) // pg
        if n <= 0:
            return 0
        mats = handle.materialize(self.cache["k"].dtype)[:n]
        k_np = np.concatenate([np.asarray(m[0]) for m in mats], axis=1)
        v_np = np.concatenate([np.asarray(m[1]) for m in mats], axis=1)
        # pow2 token-span bucket (zero pad) so the publish scatter keeps
        # a bounded compile family across import sizes
        nb = 1 << (n - 1).bit_length()
        if nb > n:
            pad = ((0, 0), (0, (nb - n) * pg), (0, 0), (0, 0))
            k_np = np.pad(k_np, pad)
            v_np = np.pad(v_np, pad)
        self.prefix.publish(toks, n * pg, jnp.asarray(k_np),
                            jnp.asarray(v_np))
        return n * pg

    def clear(self, rid: int) -> None:
        slot = self.slot_of(rid)
        if slot is None:
            return
        self.cache = {**self.cache,
                      "lengths": self.cache["lengths"].at[slot].set(0)}
        self.slot_req[slot] = None

    def offload(self, rid: int) -> dict:
        slot = self.slot_of(rid)
        data = self._slot_view(slot)
        length = int(data["lengths"])
        # pow2-bucketed payload span: the eager gather/quantize/scatter
        # chain then compiles O(log max_seq) programs instead of one per
        # distinct context length (ALISE offloads speculatively, so swap
        # staging is on the serve path); rows in [length, span) carry
        # garbage the restored ``lengths`` masks
        span = min(self._pow2_bucket(max(length, 1)), self.cfg.max_seq_len)
        stored: dict = {"lengths": length}
        for key, arr in data.items():
            if key == "lengths":
                continue
            if key in ("k", "v"):
                # zero the pad rows: channel-wise quant statistics span the
                # token axis, so stale-slot garbage past ``length`` would
                # otherwise perturb the real rows' scales
                mask = jnp.arange(span)[None, :, None, None] < length
                trimmed = jnp.where(mask, arr[:, :span], 0)
            else:
                trimmed = arr
            if self.cfg.quantize_offload and key in ("k", "v"):
                stored[key] = ("q8", quantize_kv_device(
                    trimmed, interpret=self.interpret))
            else:
                stored[key] = ("raw", np.asarray(jax.device_get(trimmed)))
        self.clear(rid)
        return stored

    def upload(self, rid: int, blob: dict) -> None:
        slot = self.free_slot()
        assert slot is not None
        length = blob["lengths"]
        data: dict = {"lengths": jnp.asarray(length, jnp.int32)}
        for key in self.cache:
            if key == "lengths":
                continue
            item = blob[key]
            if item[0] == "q8":
                src = dequantize_kv_device(item[1], dtype=jnp.float32,
                                           interpret=self.interpret)
            else:
                src = jnp.asarray(item[1])
            if key in ("k", "v"):
                # the blob carries the pow2-bucketed span (>= length);
                # writing it whole keeps the scatter shape-stable, and the
                # pad rows past ``length`` are masked by ``lengths``
                buf = jnp.zeros(self._slot_shape(key),
                                self.cache[key].dtype)
                buf = buf.at[:, :src.shape[1]].set(
                    src.astype(self.cache[key].dtype))
                data[key] = buf
            else:
                data[key] = src
        self._write_slot(slot, data)
        self.slot_req[slot] = rid

    def decode(self, params, tokens, active, new_gen, new_ctx, true_len,
               rids):
        with span("decode.stage"):
            buf, layout, _ = lane_buffer(len(tokens), tokens, active,
                                         new_gen, new_ctx, true_len, rids)
            packed = self._stage(buf)
        (tok, reason), self.cache = self._dispatch_sync(
            self._fused, params, self.cache, packed, layout)
        return tok, reason

    def supports_spec_decode(self) -> bool:
        return self._fused_verify is not None

    def decode_verify(self, params, tokens, n_drafts, active, base_gen,
                      base_ctx, true_len, rids):
        with span("decode.stage"):
            buf, layout, _ = lane_buffer(len(tokens), tokens, n_drafts,
                                         active, base_gen, base_ctx,
                                         true_len, rids)
            packed = self._stage(buf)
        (s, n_emit, reason), self.cache = self._dispatch_sync(
            self._fused_verify, params, self.cache, packed, layout)
        return s, n_emit, reason


class PagedKVBackend(KVBackend):
    """Paged KV storage: decode lanes share one physical page pool.

    Attention-family decoder-only stacks only (see
    :meth:`Model.supports_paged`).  Offload/upload move whole pages —
    request-granular, page-aligned — and the fused step writes the new
    token's KV directly into its page at ``(write_page, write_off)``;
    inactive lanes write to a reserved scratch page.
    """

    def __init__(self, model, cfg: KVBackendConfig, num_pages: int,
                 device):
        super().__init__(model, cfg, device)
        if not model.supports_paged():
            raise ValueError(
                "paged KV backend requires an attention-family decoder-only "
                f"model (family={model.cfg.family}, "
                f"enc_dec={model.cfg.is_encoder_decoder})")
        if cfg.max_seq_len % cfg.page_size:
            raise ValueError("max_seq_len must be a page_size multiple")
        if cfg.spec_k >= cfg.page_size:
            raise ValueError(
                f"spec_k ({cfg.spec_k}) must be < page_size "
                f"({cfg.page_size}) so a lane's speculative span never "
                "needs more than its one scratch page")
        acfg = model.cfg
        spec_on = cfg.spec_k > 0 and model.supports_paged()
        self.max_pages_per_seq = cfg.max_seq_len // cfg.page_size
        self.decode_widths = decode_width_ladder(cfg.max_seq_len,
                                                 cfg.page_size)
        self.decode_width_steps = {w * cfg.page_size: 0
                                   for w in self.decode_widths}
        self.pool = PagedKVPool(PagedKVConfig(
            # +1 sacrificial scratch page, plus one private scratch page
            # per decode lane when verify-k is on (speculative KV lands
            # there until accepted)
            num_pages=num_pages + 1 + (cfg.max_slots if spec_on else 0),
            page_size=cfg.page_size, num_kv_heads=acfg.num_kv_heads,
            head_dim=acfg.hd, num_layers=acfg.num_layers,
            dtype=model.kv_dtype))
        self.scratch_page = self.pool.reserve_scratch()
        # per-lane speculative scratch pages: commit swaps one into the
        # request's page table and takes a fresh replacement from the pool
        self.lane_scratch: List[int] = (
            [self.pool.reserve_scratch() for _ in range(cfg.max_slots)]
            if spec_on else [])
        # kv (arg 1) is the whole page pool, consumed and re-emitted: donate
        # so pages are written in place
        self._fused = decode_program(
            "alise_decode", functools.partial(paged_decode, model),
            attn_impl=cfg.attn_impl, interpret=self.interpret,
            **self._sample_kwargs())
        self._fused_verify = None
        if spec_on:
            self._fused_verify = decode_program(
                "alise_decode_verify", functools.partial(paged_verify, model),
                interpret=self.interpret, **self._sample_kwargs())
        # chunks, packs and verify attend through the logical-order page
        # gather, as the dense backend does over its one-page tables;
        # attn_impl only selects the width-1 decode attention
        prefill = functools.partial(paged_prefill, model)
        self._chunk = jax.jit(named_program(
            "alise_prefill_chunk", prefill, interpret=self.interpret),
            donate_argnums=(1,))
        self._pack = (jax.jit(named_program(
            "alise_prefill_pack", prefill, interpret=self.interpret),
            donate_argnums=(1,))
            if model.supports_prefill_pack() else None)
        if cfg.prefix_cache:
            from repro.serving.prefix_cache import PagedPrefixCache
            self.prefix = PagedPrefixCache(self.pool, cfg.page_size)

    # ---------------------------------------------------------- interface
    def write_prefill(self, rid: int, pcache, length: int) -> None:
        slot = self.free_slot()
        assert slot is not None, "caller must check slot availability"
        k = jnp.take(pcache["k"], 0, axis=1)[:, :length]
        v = jnp.take(pcache["v"], 0, axis=1)[:, :length]
        self.pool.write_prefill(rid, k, v)
        self.slot_req[slot] = rid

    def prefill_chunk(self, params, rid: int, tokens: List[int],
                      start: int):
        slot = self.slot_of(rid)
        if slot is None:                    # first chunk: claim a lane
            slot = self.free_slot()
            assert slot is not None, "caller must check slot availability"
            self.slot_req[slot] = rid
            if rid not in self.pool.page_table:
                self.pool.allocate(rid, 0)  # empty table; chunks extend it
        C = len(tokens)
        end = start + C
        pg = self.cfg.page_size
        # grow page coverage to the chunk's end (caller checked
        # chunk_pages_shortfall); a chunk may start/end mid-page
        self.pool.extend_to(rid, end)
        pt = self.pool.page_table[rid]
        Cb = self._chunk_bucket(C)
        toks = jnp.asarray(list(tokens) + [0] * (Cb - C), jnp.int32)[None, :]
        wp = np.full((Cb,), self.scratch_page, np.int32)
        wo = np.arange(Cb, dtype=np.int32) % pg     # harmless scratch offsets
        for i in range(C):
            pos = start + i
            wp[i] = pt[pos // pg]
            wo[i] = pos % pg
        tables = np.full((1, self.max_pages_per_seq), self.scratch_page,
                         np.int32)
        tables[0, :len(pt)] = pt
        logits, kv = self._chunk(
            params, {"k": self.pool.k, "v": self.pool.v}, toks,
            jnp.asarray(tables), jnp.asarray(wp), jnp.asarray(wo),
            jnp.asarray(start, jnp.int32), jnp.asarray(C, jnp.int32))
        self.pool.k, self.pool.v = kv["k"], kv["v"]
        return logits

    def supports_pack(self) -> bool:
        return self._pack is not None

    def prefill_pack(self, params, items, bucket: int = 0):
        N = self.cfg.prefill_pack_width
        assert self._pack is not None, "model cannot pack prefills"
        assert 0 < len(items) <= N, f"pack of {len(items)} > width {N}"
        pg = self.cfg.page_size
        Cb = bucket or self._chunk_bucket(max(len(t) for _, t, _ in items))
        toks = np.zeros((N, Cb), np.int32)
        starts = np.zeros((N,), np.int32)
        lens = np.zeros((N,), np.int32)
        # dummy rows (and pad columns) write the sacrificial scratch page
        wp = np.full((N, Cb), self.scratch_page, np.int32)
        wo = np.broadcast_to(np.arange(Cb, dtype=np.int32) % pg,
                             (N, Cb)).copy()
        tables = np.full((N, self.max_pages_per_seq), self.scratch_page,
                         np.int32)
        for i, (rid, tokens, start) in enumerate(items):
            slot = self.slot_of(rid)
            if slot is None:                # first chunk: claim a lane
                slot = self.free_slot()
                assert slot is not None, "caller must check slot availability"
                self.slot_req[slot] = rid
                if rid not in self.pool.page_table:
                    self.pool.allocate(rid, 0)
            C = len(tokens)
            assert C <= Cb, f"{C}-token member exceeds pack bucket {Cb}"
            end = start + C
            self.pool.extend_to(rid, end)   # caller checked the shortfall
            pt = self.pool.page_table[rid]
            toks[i, :C] = tokens
            starts[i], lens[i] = start, C
            for j in range(C):
                pos = start + j
                wp[i, j] = pt[pos // pg]
                wo[i, j] = pos % pg
            tables[i, :len(pt)] = pt
        logits, kv = self._pack(
            params, {"k": self.pool.k, "v": self.pool.v},
            jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(wp),
            jnp.asarray(wo), jnp.asarray(starts), jnp.asarray(lens))
        self.pool.k, self.pool.v = kv["k"], kv["v"]
        # numpy (device_get, not a compile): an eager jnp slice here would
        # recompile for every distinct pack occupancy
        return np.asarray(logits)[:len(items)]

    def chunk_pages_shortfall(self, rid: int, end: int) -> int:
        have = len(self.pool.page_table.get(rid, []))
        return max(0, self.pool.pages_needed(end) - have
                   - len(self.pool.free_pages))

    # --------------------------------------------- shared-prefix cache
    def prefix_acquire(self, rid: int, tokens) -> int:
        """Zero-copy hit: map the cached prefix's pages into ``rid``'s
        page table (refcount +1 each; partial page served copy-on-write)
        and claim its decode lane, so chunked prefill resumes at the hit
        watermark."""
        if self.prefix is None or self.has(rid) \
                or rid in self.pool.page_table:
            return 0
        slot = self.free_slot()
        if slot is None:
            return 0
        hit = self.prefix.acquire(rid, list(tokens))
        if hit:
            self.slot_req[slot] = rid
        return hit

    def prefix_publish(self, rid: int, tokens, upto: int) -> int:
        if self.prefix is None:
            return 0
        return self.prefix.publish(rid, list(tokens), upto)

    def clear(self, rid: int) -> None:
        slot = self.slot_of(rid)
        if slot is not None:
            self.slot_req[slot] = None
        self.pool.free(rid)

    def offload(self, rid: int) -> dict:
        pages = self.pool.page_table[rid]
        # pow2-bucketed page count, padded with the sacrificial scratch
        # page: the eager gather/quantize/scatter chain compiles O(log)
        # programs instead of one per distinct page count (ALISE offloads
        # speculatively, so swap staging is on the serve path)
        nb = 1 << (max(len(pages), 1) - 1).bit_length()
        nb = min(max(nb, len(pages)), self.max_pages_per_seq)
        idx = jnp.asarray(pages + [self.scratch_page] * (nb - len(pages)))
        length = self.pool.lengths[rid]
        # zero pad pages / tail rows: channel-wise quant statistics span
        # the token axes, so scratch/stale garbage would otherwise perturb
        # the real rows' scales
        pos = jnp.arange(nb * self.cfg.page_size).reshape(
            nb, self.cfg.page_size)[None, :, :, None, None]
        k = jnp.where(pos < length, self.pool.k[:, idx], 0)
        v = jnp.where(pos < length, self.pool.v[:, idx], 0)
        stored: dict = {"lengths": length}
        for key, arr in (("k", k), ("v", v)):
            if self.cfg.quantize_offload:
                stored[key] = ("q8", quantize_kv_device(
                    arr, interpret=self.interpret))
            else:
                stored[key] = ("raw", np.asarray(jax.device_get(arr)))
        self.clear(rid)
        return stored

    def upload(self, rid: int, blob: dict) -> None:
        slot = self.free_slot()
        assert slot is not None
        length = blob["lengths"]
        toks = blob.get("tokens")
        shared: List[int] = []
        if toks is not None and self.prefix is not None:
            # swap round-trips rejoin the shared prefix pool: pages of
            # this sequence's prefix still in the radix index are mapped
            # in place (refcount +1) instead of forked into private
            # duplicates that drift from the index
            full, _ = self.prefix.index.match(
                list(toks), min(length, len(toks)), touch=False)
            shared = [n.page for n in full]
            for p in shared:
                # pin before reclaim: a refcount-1 index page is exactly
                # what prefix_reclaim would evict out from under us
                self.pool.incref(p)
        n_need = self.pool.pages_needed(length)
        short = n_need - len(shared) - len(self.pool.free_pages)
        if short > 0:       # cached-but-unreferenced pages yield first
            self.prefix_reclaim(short)
        fresh = [self.pool.take_page() for _ in range(n_need - len(shared))]
        self.pool.page_table[rid] = shared + fresh
        self.pool.lengths[rid] = length
        src = {}
        for key in ("k", "v"):
            item = blob[key]
            if item[0] == "q8":
                src[key] = dequantize_kv_device(item[1], dtype=jnp.float32,
                                                interpret=self.interpret)
            else:
                src[key] = jnp.asarray(item[1])
        # the blob carries the pow2-padded page bucket; surplus rows
        # scatter into the scratch page (shape-stable, harmless), and so
        # do rows covering re-linked shared pages — their device KV is
        # already exact and may be serving other requests
        idx = ([self.scratch_page] * len(shared) + fresh
               + [self.scratch_page] * (src["k"].shape[1] - n_need))
        self.pool.scatter_pages(idx, src["k"], src["v"])
        self.slot_req[slot] = rid

    def tier_fill(self, tokens, handle) -> int:
        """Land a cluster-tier prefix import in the local prefix cache.

        Only pages past the local radix match transfer; each lands in a
        fresh pool page through the same pow2 scratch-padded scatter
        shape family as ``upload``, so a warmed swap round-trip already
        compiled the program.  The fresh pages enter the index owning
        their single refcount (index-held, like any published page) and
        the caller's ``prefix_acquire`` then maps them zero-copy.
        Returns the token watermark now cached locally."""
        if self.prefix is None or handle is None:
            return 0
        pg = self.cfg.page_size
        toks = list(tokens)[:handle.tokens]
        n = len(toks) // pg
        if n <= 0:
            return 0
        full, _ = self.prefix.index.match(toks, n * pg, touch=False)
        have = len(full)
        if have >= n:
            return have * pg        # local cache already covers the hit
        short = (n - have) - len(self.pool.free_pages)
        if short > 0:
            self.prefix_reclaim(short)
        n = min(n, have + len(self.pool.free_pages))
        if n <= have:
            return have * pg        # pool too tight to land the import
        mats = handle.materialize(self.pool.k.dtype)[have:n]
        fresh = [self.pool.take_page() for _ in range(n - have)]
        nb = 1 << (len(fresh) - 1).bit_length()
        nb = min(max(nb, len(fresh)), self.max_pages_per_seq)
        src = []
        for j in range(2):
            parts = [np.asarray(m[j]) for m in mats]
            buf = np.zeros((parts[0].shape[0], nb) + parts[0].shape[1:],
                           dtype=parts[0].dtype)
            buf[:, :len(parts)] = np.stack(parts, axis=1)
            src.append(jnp.asarray(buf))
        self.pool.scatter_pages(
            fresh + [self.scratch_page] * (nb - len(fresh)), *src)
        created = self.prefix.index.insert(toks, n * pg,
                                           lambda i: fresh[i - have])
        used = {node.page for node in created}
        for p in fresh:             # chain clipped early: hand pages back
            if p not in used:
                self.pool.decref(p)
        self.prefix.stats.inserted_pages += len(created)
        return n * pg

    def pages_shortfall(self, rids: List[int]) -> int:
        pg = self.cfg.page_size
        if self._fused_verify is not None:
            # verify-k: a lane whose worst-case accepted span (k+1 tokens)
            # would cross into its scratch page needs one free page for the
            # post-commit scratch replacement
            k1 = self.cfg.spec_k + 1
            need_new = sum(
                1 for rid in rids
                if self.pool.lengths[rid] + k1
                > len(self.pool.page_table[rid]) * pg)
        else:
            need_new = sum(1 for rid in rids
                           if self.pool.lengths[rid] % pg == 0)
        return max(0, need_new - len(self.pool.free_pages))

    def _decode_pages(self, rids: Sequence[int]) -> int:
        """Page-table width of the next decode step over lanes ``rids``:
        their page counts once the step's extend has grown them."""
        pool = self.pool
        return decode_table_width(self.decode_widths, (
            max(len(pool.page_table[r]),
                pool.pages_needed(pool.lengths[r] + 1)) for r in rids))

    def decode_width(self, rids: Sequence[int]) -> int:
        if self._fused_verify is not None:      # verify-k keeps full tables
            return (self.max_pages_per_seq + 1) * self.cfg.page_size
        return self._decode_pages(rids) * self.cfg.page_size

    def warm_decode(self, params, *args) -> None:
        for pages in self.decode_widths:
            self.decode(params, *args, pages=pages)

    def decode(self, params, tokens, active, new_gen, new_ctx, true_len,
               rids, *, pages: Optional[int] = None):
        """The fused step over page tables ``pages`` wide: by default the
        smallest rung covering the active lanes, counted in
        ``decode_width_steps``; warm-up fixes each rung in turn."""
        B, pg = self.cfg.max_slots, self.cfg.page_size
        with span("decode.stage"):
            lanes = [(slot, rid) for slot, rid in enumerate(self.slot_req)
                     if rid is not None and active[slot]]
            if pages is None:
                pages = self._decode_pages([rid for _, rid in lanes])
                self.decode_width_steps[pages * pg] += 1
            # the page table and write targets are built in their columns
            # of the packed buffer: inactive lanes read and write scratch
            buf, layout, (_, tables, lens, wp, wo, *_) = lane_buffer(
                B, tokens, (pages,), (), (), (), active, new_gen, new_ctx,
                true_len, rids)
            tables[...] = self.scratch_page
            wp[...] = self.scratch_page
            for slot, rid in lanes:
                # the fed token's KV lands at logical position `pos`: grow
                # the page table first (caller guarantees a free page via
                # pages_shortfall), then point the write at its page slot
                self.pool.extend(rid, 1)
                pos = self.pool.lengths[rid] - 1
                pt = self.pool.page_table[rid]
                tables[slot, :len(pt)] = pt
                lens[slot] = pos
                wp[slot] = pt[pos // pg]
                wo[slot] = pos % pg
            packed = self._stage(buf)
        (tok, reason), kv = self._dispatch_sync(
            self._fused, params, {"k": self.pool.k, "v": self.pool.v},
            packed, layout)
        self.pool.k, self.pool.v = kv["k"], kv["v"]
        return tok, reason

    def supports_spec_decode(self) -> bool:
        return self._fused_verify is not None

    def decode_verify(self, params, tokens, n_drafts, active, base_gen,
                      base_ctx, true_len, rids):
        B, pg = self.cfg.max_slots, self.cfg.page_size
        K1 = self.cfg.spec_k + 1
        maxp = self.max_pages_per_seq
        with span("decode.stage"):
            # one extra table column holds the lane's scratch page right
            # after its real pages: a scratch-resident write at logical
            # position p (p // pg == len(table)) gathers back at exactly p
            buf, layout, (_, tables, lens, wp, wo, *_) = lane_buffer(
                B, tokens, (maxp + 1,), (), (K1,), (K1,), n_drafts, active,
                base_gen, base_ctx, true_len, rids)
            tables[...] = self.scratch_page
            wp[...] = self.scratch_page
            wo[...] = np.arange(K1, dtype=np.int32) % pg
            for slot, rid in enumerate(self.slot_req):
                if rid is None or not active[slot]:
                    continue
                # NO pre-extend: speculative positions past the last real
                # page land on the lane's scratch page, promoted on accept
                pos = self.pool.lengths[rid]
                pt = self.pool.page_table[rid]
                tables[slot, :len(pt)] = pt
                tables[slot, len(pt)] = self.lane_scratch[slot]
                lens[slot] = pos
                for i in range(K1):
                    p = pos + i
                    wp[slot, i] = (pt[p // pg] if p // pg < len(pt)
                                   else self.lane_scratch[slot])
                    wo[slot, i] = p % pg
            packed = self._stage(buf)
        (s, n_emit, reason), kv = self._dispatch_sync(
            self._fused_verify, params, {"k": self.pool.k, "v": self.pool.v},
            packed, layout)
        self.pool.k, self.pool.v = kv["k"], kv["v"]
        # commit after the sync: lanes whose accepted span crossed into
        # scratch promote it into the page table (pointer move, no copy)
        # and take a fresh scratch page; rejected speculative writes are
        # rolled back by simply not advancing the pool length
        for slot, rid in enumerate(self.slot_req):
            if rid is None or not active[slot] or n_emit[slot] == 0:
                continue
            pt = self.pool.page_table[rid]
            new_len = self.pool.lengths[rid] + int(n_emit[slot])
            if new_len > len(pt) * pg:
                pt.append(self.lane_scratch[slot])
                # caller guaranteed a free page via pages_shortfall
                self.lane_scratch[slot] = self.pool.take_page()
            self.pool.lengths[rid] = new_len
        return s, n_emit, reason
