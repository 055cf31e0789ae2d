"""Seeded random weights of a dense GQA decoder, made by the benchmark.

The system under test is handed these weights; the reference makes the
same ones again, one layer at a time, from the same seed.  Neither takes
anything from the other.

Every matrix entry is a uniform 8-bit integer times a power of two, so it
is exactly representable in bfloat16 (the served type) and in float32 (the
reference's type): the two start from identical values, and the bits come
from integer counters, so a layer made alone equals the same layer made
inside the stacked set.  The power of two is the one nearest to giving
the entries the usual ``1/sqrt(fan_in)`` spread (0.02 for the embedding).
Norm scales are ones.

Names follow the parameter tree of a dense decoder: ``embed`` (V, D),
``lm_head`` (D, V) when the head is not tied, ``final_norm/scale``, and per
layer ``ln1/scale``, ``attn/{wq,wk,wv,wo}``, ``ln2/scale``,
``ffn/{wi,wg,wo}`` (``wi`` the up projection, ``wg`` the gate).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

INT8_STD = math.sqrt((256 ** 2 - 1) / 12)      # std of uniform ints -128..127


def base_key(seed: int):
    """A key from a seed of any size (seeds go past 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def global_shapes(sizes: dict) -> dict:
    D, V = sizes["d_model"], sizes["vocab_size"]
    shapes = {"embed": (V, D), "final_norm/scale": (D,)}
    if not sizes["tie_embeddings"]:
        shapes["lm_head"] = (D, V)
    return shapes


def layer_shapes(sizes: dict) -> dict:
    D, F = sizes["d_model"], sizes["d_ff"]
    H, KVH = sizes["num_heads"], sizes["num_kv_heads"]
    hd = sizes.get("head_dim") or D // H
    return {"ln1/scale": (D,), "attn/wq": (D, H * hd),
            "attn/wk": (D, KVH * hd), "attn/wv": (D, KVH * hd),
            "attn/wo": (H * hd, D), "ln2/scale": (D,),
            "ffn/wi": (D, F), "ffn/wg": (D, F), "ffn/wo": (F, D)}


def scale_exponent(name: str, shape: tuple) -> int:
    """k such that ints * 2**-k spread like the usual initialisation."""
    target = 0.02 if name == "embed" else 1.0 / math.sqrt(shape[0])
    return round(math.log2(INT8_STD / target))


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _matrix(key, name: str, shape: tuple, dtype):
    rows, cols = shape
    words = -(-cols // 4)
    bits = jax.random.bits(key, (rows, words), jnp.uint32)
    ints = jax.lax.bitcast_convert_type(bits, jnp.int8).reshape(rows, 4 * words)
    scale = 2.0 ** -scale_exponent(name, shape)
    return ints[:, :cols].astype(dtype) * jnp.asarray(scale, dtype)


def make_leaf(key, name: str, shape: tuple, dtype, layer=None):
    """One weight; ``layer`` (an int or a traced int) picks a layer's own."""
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    k = _leaf_key(key, name)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return _matrix(k, name, shape, dtype)


def make_layer(key, sizes: dict, layer, dtype) -> dict:
    return {name: make_leaf(key, name, shape, dtype, layer)
            for name, shape in layer_shapes(sizes).items()}


def make_globals(key, sizes: dict, dtype) -> dict:
    return {name: make_leaf(key, name, shape, dtype)
            for name, shape in global_shapes(sizes).items()}


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_params(template, sizes: dict, seed: int):
    """The whole parameter tree, shaped like ``template`` (a tree of
    ``ShapeDtypeStruct``, as ``jax.eval_shape`` gives for the model's own
    initialiser), built on the default device by one compiled call.  Every
    leaf of the template has to be one this module knows by name and
    shape: anything else means the model is not the one the configuration
    describes."""
    L = sizes["num_layers"]
    gshapes, lshapes = global_shapes(sizes), layer_shapes(sizes)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    names = [_path_name(p) for p, _ in flat]
    for name, (_, leaf) in zip(names, flat):
        if name in gshapes:
            want = gshapes[name]
        elif name.startswith("layers/") and name[7:] in lshapes:
            want = (L,) + lshapes[name[7:]]
        else:
            raise ValueError(f"parameter {name!r} {leaf.shape} is not part of "
                             "a dense GQA decoder")
        if tuple(leaf.shape) != want:
            raise ValueError(f"parameter {name!r} has shape {leaf.shape}, the "
                             f"configuration gives {want}")
    dtypes = [leaf.dtype for _, leaf in flat]

    def build(key):
        stacked = jax.lax.map(
            lambda l: make_layer(key, sizes, l, dtypes[0]), jnp.arange(L))
        glob = make_globals(key, sizes, dtypes[0])
        leaves = []
        for name, dt in zip(names, dtypes):
            arr = stacked[name[7:]] if name.startswith("layers/") else glob[name]
            leaves.append(arr.astype(dt))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    if len(set(np.dtype(d) for d in dtypes)) != 1:
        raise ValueError(f"mixed parameter dtypes {set(dtypes)}")
    return jax.jit(build)(base_key(seed))
