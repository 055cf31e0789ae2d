"""Paged decode attention (Pallas TPU) — vLLM's PagedAttention adapted to TPU.

GPU PagedAttention gathers KV pages with per-thread loads; TPUs have no
per-lane gather, so the indirection is lifted into *scalar prefetch*: the
block table lives in SMEM and drives the BlockSpec index_map, letting the
DMA engine stream exactly the pages each sequence needs, double-buffered
across the page grid axis.  This is the hardware adaptation of the paper's
executor kernel noted in DESIGN.md §3.

Grid: (B, n_pages); the page axis is innermost/sequential, carrying the
online-softmax state in VMEM scratch.  One grid step moves a whole page —
all KV heads — as one ``(page, KVH, d)`` block: the block's last two dims
equal the pool's, which is what the TPU tiling rule accepts (a one-head
``(page, 1, d)`` block is refused).  Inside, a static loop over the KV
heads scores each head's ``G`` query rows against that head's ``(page, d)``
slice, with per-head softmax state.  Pages past `lengths[b]` are skipped
entirely (pl.when) — unused pages cost no MXU cycles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, lengths_ref, layer_ref,      # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref, o_ref,              # VMEM tiles
            m_ref, l_ref, acc_ref, *,                # scratch
            page: int, kvh: int, n_pages: int, scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    length = lengths_ref[b]

    @pl.when((pi * page) < length)        # whole page past length: skip
    def _body():
        for h in range(kvh):
            attend_head(h, q_ref[0, h].astype(jnp.float32),
                        k_ref[0, 0, :, h, :].astype(jnp.float32),
                        v_ref[0, 0, :, h, :].astype(jnp.float32),
                        m_ref, l_ref, acc_ref, pi=pi, page=page,
                        length=length, scale=scale)

    @pl.when(pi == n_pages - 1)
    def _finish():
        finish(o_ref, l_ref, acc_ref)


def init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def attend_head(h: int, q, k, v, m_ref, l_ref, acc_ref, *, pi, page: int,
                length, scale: float):
    """One online-softmax step of KV head ``h``: its ``G`` query rows ``q``
    (G, d) against the page's ``k``/``v`` rows of that head (page, d);
    tokens at or past ``length`` are masked."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG_INF)
    m_prev = m_ref[h]                                           # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def finish(o_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def head_state(kvh: int, G: int, d: int):
    """VMEM scratch of the online softmax, one ``(G, ·)`` block per KV
    head: running max, running sum, output accumulator."""
    return [pltpu.VMEM((kvh, G, 1), jnp.float32),
            pltpu.VMEM((kvh, G, 1), jnp.float32),
            pltpu.VMEM((kvh, G, d), jnp.float32)]


def paged_attention(q, k_cache, v_cache, block_tables, lengths, *,
                    layer=None, interpret: bool = False):
    """q: (B, H, d); caches: (num_pages, page, KVH, d), or the whole
    layer-stacked pool (L, num_pages, page, KVH, d) with ``layer`` the
    (traced) layer to read — the kernel then DMAs that layer's pages
    straight out of the pool, with no per-layer copy.
    block_tables: (B, max_pages) int32; lengths: (B,) -> (B, H, d)."""
    if layer is None:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    B, H, d = q.shape
    _, num_pages, page, KVH, _ = k_cache.shape
    G = H // KVH
    max_pages = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_kernel, page=page, kvh=KVH,
                               n_pages=max_pages, scale=scale)

    def page_map(b, pi, tables, lens, lyr):
        return (lyr[0], tables[b, pi], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, KVH, G, d), lambda b, pi, t, l, y: (b, 0, 0, 0)),
            # k/v page, all heads: the block table picks the physical page
            pl.BlockSpec((1, 1, page, KVH, d), page_map),
            pl.BlockSpec((1, 1, page, KVH, d), page_map),
        ],
        out_specs=pl.BlockSpec((1, KVH, G, d),
                               lambda b, pi, t, l, y: (b, 0, 0, 0)),
        scratch_shapes=head_state(KVH, G, d),
    )
    # query head r belongs to KV group r // G: (B, H, d) -> (B, KVH, G, d)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q.reshape(B, KVH, G, d), k_cache, v_cache)
    return out.reshape(B, H, d)
