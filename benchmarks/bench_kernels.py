"""Kernel microbenchmarks (paper §3.3 fused kernels).

CPU-container note: Pallas kernels run in interpret mode here, so wall time
measures the *reference semantics*, not TPU speed.  ``derived`` therefore
also reports the roofline-model TPU v5e time from the kernel's exact
FLOP/byte counts — the number used in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, note, pick, time_call
from repro.kernels.flash_prefill import flash_attention, flash_prefill_ref
from repro.kernels.fused_rmsnorm import fused_rmsnorm_op, rmsnorm_ref
from repro.kernels.kv_quant import kv_quantize_op, paged_attention_q8_op, kv_quantize_ref
from repro.kernels.paged_attention import paged_attention_ref, paged_decode_attention

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def _tpu_time_us(flops: float, bytes_: float) -> float:
    return max(flops / PEAK_FLOPS, bytes_ / HBM_BW) * 1e6


def run() -> None:
    key = jax.random.PRNGKey(0)

    # flash prefill: one layer tile of granite-3-8b at 2k
    B, H, KVH, S, d = 1, 8, 2, pick(2048, 256), 128
    blk = pick(256, 128)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, KVH, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, KVH, S, d), jnp.float32)
    us = time_call(lambda: jax.block_until_ready(
        flash_attention(q, k, v, q_blk=blk, kv_blk=blk, interpret=True)))
    flops = 2 * 2 * B * H * S * S * d * 0.5        # causal half
    bts = (q.size + k.size + v.size) * 4 + q.size * 4
    emit(f"kernels/flash_prefill/B1xH8xS{S}", us,
         f"tpu_roofline_us={_tpu_time_us(flops, bts):.1f};flops={flops:.3g}")
    us_ref = time_call(lambda: jax.block_until_ready(
        jax.jit(lambda a, b, c: flash_prefill_ref(a, b, c))(q, k, v)))
    emit(f"kernels/flash_prefill_ref/B1xH8xS{S}", us_ref, "jnp_oracle")

    # paged decode attention: 32k context, 64 pages live
    Bd, Hd, KVHd, dd, page, npg, maxp = \
        pick(8, 2), 8, 8, 128, 64, pick(512, 32), pick(64, 8)
    ks = jax.random.split(key, 5)
    qd = jax.random.normal(ks[0], (Bd, Hd, dd), jnp.float32)
    kc = jax.random.normal(ks[1], (npg, page, KVHd, dd), jnp.float32)
    vc = jax.random.normal(ks[2], (npg, page, KVHd, dd), jnp.float32)
    tables = jax.random.randint(ks[3], (Bd, maxp), 0, npg)
    lengths = jnp.full((Bd,), maxp * page, jnp.int32)
    us = time_call(lambda: jax.block_until_ready(paged_decode_attention(
        qd, kc, vc, tables, lengths, interpret=True)), iters=2)
    kv_bytes = 2 * Bd * maxp * page * KVHd * dd * 4
    flops_d = 2 * 2 * Bd * Hd * maxp * page * dd
    ctx = maxp * page
    emit(f"kernels/paged_attention/B{Bd}_ctx{ctx}", us,
         f"tpu_roofline_us={_tpu_time_us(flops_d, kv_bytes):.1f}")
    us_ref = time_call(lambda: jax.block_until_ready(jax.jit(
        paged_attention_ref)(qd, kc, vc, tables, lengths)))
    emit(f"kernels/paged_attention_ref/B{Bd}_ctx{ctx}", us_ref, "jnp_oracle")

    # fused q8 paged attention: same shape, int8 KV stream (bytes halve)
    kq, klam, kz = kv_quantize_ref(kc)
    vq, vlam, vz = kv_quantize_ref(vc)
    us = time_call(lambda: jax.block_until_ready(paged_attention_q8_op(
        qd, kq, klam, kz, vq, vlam, vz, tables, lengths, interpret=True)),
        iters=2)
    q8_bytes = kv_bytes / 4 + 2 * Bd * maxp * page * KVHd * 8  # int8 + scales
    emit(f"kernels/paged_attention_q8/B{Bd}_ctx{ctx}", us,
         f"tpu_roofline_us={_tpu_time_us(flops_d, q8_bytes):.1f};"
         f"hbm_bytes_ratio={q8_bytes/kv_bytes:.2f}")
    note(f"[kernels] int8 KV stream cuts decode attention HBM bytes to "
         f"{q8_bytes/kv_bytes:.2f}x of bf16/fp32")

    # fused decode+sample step vs per-slot host argmax (engine hot path):
    # same decode compute; the fused step samples and computes termination
    # on device so the host syncs one (tokens, reasons) pair instead of
    # 8 argmax round-trips
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models.model import Model
    from repro.serving.kv_cache import dense_decode

    mcfg = get_smoke_config("granite-3-8b")
    model = Model(mcfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    Bs, Smax = 8, pick(256, 64)
    cache = model.init_cache(Bs, Smax)
    cache = {**cache, "lengths": jnp.full((Bs,), Smax // 2, jnp.int32)}
    toks = jnp.ones((Bs, 1), jnp.int32)
    ones = jnp.ones((Bs,), jnp.int32)
    active = jnp.ones((Bs,), bool)
    step_key = jax.random.PRNGKey(1)
    rids = jnp.arange(Bs, dtype=jnp.int32)
    fused = jax.jit(lambda p, c, t: dense_decode(
        model, p, c, t, active, ones, ones, ones * Smax, rids, step_key,
        max_seq_len=Smax))
    plain = jax.jit(model.decode_step)

    def per_slot():
        logits, c = plain(params, cache, toks)
        return [int(jnp.argmax(logits[i])) for i in range(Bs)]

    def one_dispatch():
        tok, reason, c = fused(params, cache, toks)
        return np.asarray(jax.device_get(tok))

    us_slot = time_call(per_slot)
    us_fused = time_call(one_dispatch)
    emit(f"kernels/decode_per_slot/B{Bs}", us_slot,
         f"host_syncs={Bs};tok_per_s={Bs/us_slot*1e6:.0f}")
    emit(f"kernels/decode_fused_sampled/B{Bs}", us_fused,
         f"host_syncs=1;tok_per_s={Bs/us_fused*1e6:.0f};"
         f"speedup={us_slot/us_fused:.2f}x")
    note(f"[kernels] fused in-jit decode+sample: {us_slot:.0f}us (per-slot "
         f"argmax) -> {us_fused:.0f}us ({us_slot/us_fused:.2f}x at B={Bs})")

    # kv quantize
    T = pick(4096, 512)
    x = jax.random.normal(key, (T, 128), jnp.float32)
    us = time_call(lambda: jax.block_until_ready(
        kv_quantize_op(x, interpret=True)))
    emit(f"kernels/kv_quantize/T{T}xd128", us,
         f"tpu_roofline_us={_tpu_time_us(x.size*3, x.size*5):.1f}")

    # fused rmsnorm
    R = pick(4096, 512)
    xr = jax.random.normal(key, (R, R), jnp.bfloat16)
    s = jnp.ones((R,), jnp.float32)
    us = time_call(lambda: jax.block_until_ready(
        fused_rmsnorm_op(xr, s, interpret=True)))
    emit(f"kernels/fused_rmsnorm/{R}x{R}", us,
         f"tpu_roofline_us={_tpu_time_us(xr.size*4, xr.size*4):.1f}")
    us_ref = time_call(lambda: jax.block_until_ready(
        jax.jit(rmsnorm_ref)(xr, s)))
    emit(f"kernels/fused_rmsnorm_ref/{R}x{R}", us_ref, "jnp_oracle")

    # ssd chunk scan (mamba2-2.7b-like tile: Q=128, P=64, N=128)
    from repro.kernels.ssd_scan import ssd_chunked_fused
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N, Q = 1, pick(512, 256), 4, 64, 128, 128
    ks = jax.random.split(key, 4)
    xs = jax.random.normal(ks[0], (B, S, H, P))
    dts = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(7), (H,)) * 0.2)
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    us = time_call(lambda: jax.block_until_ready(ssd_chunked_fused(
        xs, dts, A, Bm, Cm, chunk=Q, interpret=True)[0]), iters=2)
    fl = 2 * B * S * (Q * N + Q * H * P + 2 * H * P * N)
    by = (xs.size + Bm.size + Cm.size) * 4 * 2
    emit(f"kernels/ssd_chunk/B1xS{S}xH4", us,
         f"tpu_roofline_us={_tpu_time_us(fl, by):.1f}")
    us_ref = time_call(lambda: jax.block_until_ready(jax.jit(
        lambda *a: ssd_chunked(*a, chunk=Q)[0])(xs, dts, A, Bm, Cm)))
    emit(f"kernels/ssd_chunk_ref/B1xS{S}xH4", us_ref, "jnp_oracle")


if __name__ == "__main__":
    run()
