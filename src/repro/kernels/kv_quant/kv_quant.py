"""INT8 KV quantization kernels + fused dequant paged attention (Pallas TPU).

Three kernels:
  * ``kv_quantize``   — per-(token, head) asymmetric INT8 (paper Eq. 8),
                        tiled over pages so quantize-on-offload streams;
  * ``kv_dequantize`` — the inverse;
  * ``paged_attention_q8`` — decode attention reading INT8 pages and
    dequantizing *inside* the kernel: HBM traffic for the KV stream halves,
    which attacks the memory roofline term that dominates decode (§Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.paged_attention import (attend_head, finish,
                                                           head_state,
                                                           init_state)


# ------------------------------------------------------------ quant/dequant

def _quant_kernel(x_ref, q_ref, lam_ref, z_ref):
    x = x_ref[...].astype(jnp.float32)
    mx = x.max(axis=-1, keepdims=True)
    mn = x.min(axis=-1, keepdims=True)
    lam = jnp.maximum((mx - mn) / 255.0, 1e-8)
    z = jnp.round(-mn / lam)
    q = jnp.clip(jnp.round(x / lam + z), 0.0, 255.0) - 128.0
    q_ref[...] = q.astype(jnp.int8)
    lam_ref[...] = lam
    z_ref[...] = z


def kv_quantize(x, *, blk: int = 128, interpret: bool = False):
    """x: (T, d) -> (q int8 (T,d), lam (T,1), z (T,1)); rows are tokens
    (flatten any leading dims first)."""
    T, d = x.shape
    blk = min(blk, T)
    assert T % blk == 0
    return pl.pallas_call(
        _quant_kernel,
        grid=(T // blk,),
        in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, d), jnp.int8),
                   jax.ShapeDtypeStruct((T, 1), jnp.float32),
                   jax.ShapeDtypeStruct((T, 1), jnp.float32)],
        interpret=interpret,
    )(x)


def _dequant_kernel(q_ref, lam_ref, z_ref, x_ref, *, dtype):
    q = q_ref[...].astype(jnp.float32) + 128.0
    x_ref[...] = (lam_ref[...] * (q - z_ref[...])).astype(dtype)


def kv_dequantize(q, lam, z, *, dtype=jnp.bfloat16, blk: int = 128,
                  interpret: bool = False):
    T, d = q.shape
    blk = min(blk, T)
    assert T % blk == 0
    return pl.pallas_call(
        functools.partial(_dequant_kernel, dtype=dtype),
        grid=(T // blk,),
        in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), dtype),
        interpret=interpret,
    )(q, lam, z)


# -------------------------------------------- fused dequant paged attention

def _paged_q8_kernel(tables_ref, lengths_ref,
                     q_ref, kq_ref, klam_ref, kz_ref,
                     vq_ref, vlam_ref, vz_ref, o_ref,
                     m_ref, l_ref, acc_ref, *,
                     page: int, kvh: int, n_pages: int, scale: float):
    """Same page-per-step layout and per-head loop as ``paged_attention``:
    a whole INT8 page (all KV heads) per grid step; each head's rows get
    their scale/zero applied before its two matmuls."""
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    length = lengths_ref[b]

    def rows(q_ref, lam_ref, z_ref, h):
        q = q_ref[0, :, h, :].astype(jnp.float32)                 # (page, d)
        return lam_ref[0, :, h, :] * (q + 128.0 - z_ref[0, :, h, :])

    @pl.when((pi * page) < length)
    def _body():
        for h in range(kvh):
            attend_head(h, q_ref[0, h].astype(jnp.float32),
                        rows(kq_ref, klam_ref, kz_ref, h),
                        rows(vq_ref, vlam_ref, vz_ref, h),
                        m_ref, l_ref, acc_ref, pi=pi, page=page,
                        length=length, scale=scale)

    @pl.when(pi == n_pages - 1)
    def _finish():
        finish(o_ref, l_ref, acc_ref)


def paged_attention_q8(q, kq, k_lam, k_z, vq, v_lam, v_z, block_tables,
                       lengths, *, interpret: bool = False):
    """q: (B,H,d); kq/vq: (num_pages, page, KVH, d) int8 with per-(token,head)
    scale/zero (num_pages, page, KVH, 1); -> (B, H, d)."""
    B, H, d = q.shape
    num_pages, page, KVH, _ = kq.shape
    G = H // KVH
    max_pages = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_paged_q8_kernel, page=page, kvh=KVH,
                               n_pages=max_pages, scale=scale)

    def page_spec(width):
        return pl.BlockSpec((1, page, KVH, width),
                            lambda b, pi, tables, lens: (tables[b, pi], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, KVH, G, d), lambda b, pi, t, l: (b, 0, 0, 0)),
            page_spec(d), page_spec(1), page_spec(1),
            page_spec(d), page_spec(1), page_spec(1),
        ],
        out_specs=pl.BlockSpec((1, KVH, G, d),
                               lambda b, pi, t, l: (b, 0, 0, 0)),
        scratch_shapes=head_state(KVH, G, d),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q.reshape(B, KVH, G, d), kq, k_lam, k_z,
      vq, v_lam, v_z)
    return out.reshape(B, H, d)
