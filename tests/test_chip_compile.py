"""The serve path's Pallas kernels compile for a TPU v5e at granite-3-8b's
published widths.

Interpret mode (every other kernel test) cannot see the TPU's tiling rules
or its fast-memory limits; the TPU compiler installed with JAX can, for a
chip that is described rather than attached.  Nothing here runs: each test
lowers a kernel (or one decode layer) against shapes placed on a described
``v5e:2x2`` device and compiles it.  The topology is described inside a
fixture, so importing this file never loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_chip_config
from repro.core.engine import default_bucket_menu
from repro.kernels.flash_prefill.flash_prefill import flash_prefill_prefix
from repro.kernels.kv_quant.kv_quant import (kv_dequantize, kv_quantize,
                                             paged_attention_q8)
from repro.kernels.paged_attention.paged_attention import paged_attention
from repro.launch.serve import SIZES
from repro.models.model import Model
from repro.serving.kv_cache import paged_decode, paged_prefill

ARCH = "granite-3-8b"
SIZE = SIZES["chip"]
LANES = 16                     # decode lanes chip_smoke.py serves with
PAGE = 16                      # EngineConfig.page_size
CHUNK = 256                    # prefill chunk chip_smoke.py serves with


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def cfg():
    return get_chip_config(ARCH)


def _compile(fn, *args, one_chip, kernel=True, donate=(), **kw):
    """Lower ``fn`` on shapes placed on the described chip and compile;
    ``kernel``: the program must hold a compiled Pallas kernel;
    ``donate``: argument numbers donated, as the engine donates them."""
    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    args = jax.tree.map(place, args)
    compiled = jax.jit(functools.partial(fn, **kw),
                       donate_argnums=donate).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return compiled


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _pool(cfg, layers):
    """Page pool of ``LANES`` lanes x ``max_seq_len`` tokens (+ scratch)."""
    pages = LANES * SIZE.max_seq_len // PAGE + 1
    shape = (pages, PAGE, cfg.num_kv_heads, cfg.hd)
    return _s(shape if layers is None else (layers, *shape), jnp.bfloat16)


def test_kv_quant_roundtrip_compiles(cfg, one_chip):
    """The INT8 swap path: one lane's KV of one layer as (token, head)
    rows of ``head_dim``."""
    rows = SIZE.max_seq_len * cfg.num_kv_heads
    _compile(kv_quantize, _s((rows, cfg.hd), jnp.bfloat16), one_chip=one_chip,
             blk=128, interpret=False)
    _compile(kv_dequantize, _s((rows, cfg.hd), jnp.int8),
             _s((rows, 1), jnp.float32), _s((rows, 1), jnp.float32),
             one_chip=one_chip, dtype=jnp.float32, blk=128, interpret=False)


@pytest.mark.parametrize("bucket", default_bucket_menu(CHUNK))
def test_flash_prefill_prefix_compiles(cfg, one_chip, bucket):
    """``--chunk-attn flash``: one chunk per prefill bucket over a full
    lane stripe."""
    H, KVH, d = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    _compile(flash_prefill_prefix, _s((1, H, bucket, d), jnp.bfloat16),
             _s((1, KVH, SIZE.max_seq_len, d), jnp.bfloat16),
             _s((1, KVH, SIZE.max_seq_len, d), jnp.bfloat16),
             _s((1,), jnp.int32), one_chip=one_chip, interpret=False)


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one-layer-pool", "layer-stacked-pool"])
def test_paged_attention_compiles(cfg, one_chip, stacked):
    """``paged_attn_impl="kernel"``: the decode batch over the page pool,
    as a one-layer pool and as the layer-stacked pool the fused decode
    step reads one traced layer of."""
    maxp = SIZE.max_seq_len // PAGE
    args = (_s((LANES, cfg.num_heads, cfg.hd), jnp.bfloat16),
            _pool(cfg, cfg.num_layers if stacked else None),
            _pool(cfg, cfg.num_layers if stacked else None),
            _s((LANES, maxp), jnp.int32), _s((LANES,), jnp.int32))
    if stacked:
        _compile(lambda q, k, v, t, n, i: paged_attention(
            q, k, v, t, n, layer=i, interpret=False),
            *args, _s((), jnp.int32), one_chip=one_chip)
    else:
        _compile(paged_attention, *args, one_chip=one_chip, interpret=False)


def test_paged_attention_q8_compiles(cfg, one_chip):
    maxp = SIZE.max_seq_len // PAGE
    page8 = _pool(cfg, None)
    q8 = _s(page8.shape, jnp.int8)
    scale = _s((*page8.shape[:-1], 1), jnp.float32)
    _compile(paged_attention_q8, _s((LANES, cfg.num_heads, cfg.hd),
                                    jnp.bfloat16),
             q8, scale, scale, q8, scale, scale,
             _s((LANES, maxp), jnp.int32), _s((LANES,), jnp.int32),
             one_chip=one_chip, interpret=False)


def _decode_args(cfg, layers):
    """Arguments of the fused paged decode step after ``params``: the
    pool of ``layers`` layers and one decode batch of ``LANES`` lanes."""
    pool = _pool(cfg, layers)
    i32 = functools.partial(_s, dtype=jnp.int32)
    return ({"k": pool, "v": pool}, i32((LANES, 1)),
            i32((LANES, SIZE.max_seq_len // PAGE)), i32((LANES,)),
            i32((LANES,)), i32((LANES,)), _s((LANES,), jnp.bool_),
            i32((LANES,)), i32((LANES,)), i32((LANES,)), i32((LANES,)),
            _s((2,), jnp.uint32))


def _decode_kw(attn_impl):
    return dict(attn_impl=attn_impl, interpret=False,
                max_new_tokens=SIZE.max_new_tokens,
                max_seq_len=SIZE.max_seq_len, kernel=attn_impl == "kernel")


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_fused_paged_decode_layer_compiles(cfg, one_chip, attn_impl):
    """One layer of the engine's fused decode dispatch (embedding, layer,
    KV write, attention, logits, sampling) with kernels compiled."""
    model = Model(cfg.scaled(num_layers=1), attn_chunk=32, remat=False)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    _compile(functools.partial(paged_decode, model), params,
             *_decode_args(model.cfg, 1), one_chip=one_chip,
             **_decode_kw(attn_impl))


# What one chip must hold besides the programs' own arguments, outputs and
# temporaries: the runtime, swap staging buffers and the host transfers of
# the sampler.  chip_smoke.py's peak in use on a v5e sits 0.4-0.7 GB above
# the compiled peaks; 1.5 GiB leaves room for that with a margin.
HBM = 16 * 2**30
RESERVE = int(1.5 * 2**30)


def _peak(compiled) -> int:
    """Device bytes a program holds at once: arguments, plus outputs not
    aliased to a donated argument, plus temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["decode-gather", "decode-kernel",
                                     "prefill-256", "prefill-1024"])
def test_chip_config_fits_one_v5e(cfg, one_chip, program):
    """The full ``chip_config()`` depth (weights of every layer) with the
    engine's page pool of ``LANES`` lanes x ``max_seq_len`` tokens: the
    fused decode step and a prefill chunk (the served chunk of 256, and a
    whole 1024-token prompt in one chunk) compiled for a v5e, with the pool
    donated as the engine donates it, leave ``RESERVE`` of its HBM free.
    This is what sizes ``CHIP_LAYERS``."""
    model = Model(cfg, attn_chunk=32, remat=False)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if program.startswith("decode"):
        compiled = _compile(functools.partial(paged_decode, model), params,
                            *_decode_args(cfg, cfg.num_layers),
                            one_chip=one_chip, donate=(1,),
                            **_decode_kw(program.split("-")[1]))
    else:
        C = int(program.split("-")[1])
        pool = _pool(cfg, cfg.num_layers)
        i32 = functools.partial(_s, dtype=jnp.int32)
        compiled = _compile(functools.partial(paged_prefill, model), params,
                            {"k": pool, "v": pool}, i32((1, C)),
                            i32((1, SIZE.max_seq_len // PAGE)), i32((C,)),
                            i32((C,)), i32(()), i32(()), one_chip=one_chip,
                            donate=(1,), kernel=False, interpret=False)
    peak = _peak(compiled)
    print(f"{program}: {peak / 1e9:.2f} GB at peak of {HBM / 1e9:.2f} GB")
    assert peak < HBM - RESERVE
