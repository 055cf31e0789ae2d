"""Seeded random weights, made by the benchmark.

The system under test is handed these weights; the reference makes the
same ones again, one layer at a time, from the same seed.  Neither takes
anything from the other.

Which leaves a model has, their shapes and the layer each stacked slice
is made for are the architecture's: its module under ``archs/`` gives
them as a ``Tree``.  This module only makes the leaves.

Every matrix entry is a uniform 8-bit integer times a power of two, so it
is exactly representable in bfloat16 (the served type) and in float32 (the
reference's type): the two start from identical values, and the bits come
from integer counters, so a layer made alone equals the same layer made
inside the stacked set.  The power of two is the one nearest to giving
the entries the usual ``1/sqrt(fan_in)`` spread (0.02 for the embedding),
the fan-in being the second-to-last axis.  Leaves of one axis (norm
scales, biases) are ones.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INT8_STD = math.sqrt((256 ** 2 - 1) / 12)      # std of uniform ints -128..127


@dataclass(frozen=True)
class Stack:
    """Leaves stacked over layers.  ``layers`` holds, at each index of the
    stacked axes, the layer that slice is made for; ``leaves`` maps each
    leaf's name inside one layer to its shape in one layer."""
    layers: Tuple
    leaves: Dict[str, tuple]


@dataclass(frozen=True)
class Tree:
    """A model's parameter tree: leaves outside any stack by their path,
    and stacks by the path they sit under (a stacked leaf's path is the
    stack's, a slash, then its name inside the layer)."""
    globals: Dict[str, tuple]
    stacks: Dict[str, Stack]


def base_key(seed: int):
    """A key from a seed of any size (seeds go past 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def scale_exponent(name: str, shape: tuple) -> int:
    """k such that ints * 2**-k spread like the usual initialisation."""
    target = 0.02 if name == "embed" else 1.0 / math.sqrt(shape[-2])
    return round(math.log2(INT8_STD / target))


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _matrix(key, name: str, shape: tuple, dtype):
    *rows, cols = shape
    words = -(-cols // 4)
    bits = jax.random.bits(key, (*rows, words), jnp.uint32)
    ints = jax.lax.bitcast_convert_type(bits, jnp.int8).reshape(
        *rows, 4 * words)
    scale = 2.0 ** -scale_exponent(name, shape)
    return ints[..., :cols].astype(dtype) * jnp.asarray(scale, dtype)


def make_leaf(key, name: str, shape: tuple, dtype, layer=None):
    """One weight; ``layer`` (an int or a traced int) picks a layer's own."""
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    k = _leaf_key(key, name)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return _matrix(k, name, shape, dtype)


def make_leaves(key, shapes: dict, dtype, layer=None) -> dict:
    """Each leaf of ``shapes`` (name -> shape); ``layer`` as for
    ``make_leaf``.  The reference makes one layer of a stack with it."""
    return {name: make_leaf(key, name, shape, dtype, layer)
            for name, shape in shapes.items()}


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _place(tree: Tree, name: str):
    """(stack path or None, name inside the stack, shape it must have)."""
    if name in tree.globals:
        return None, name, tuple(tree.globals[name])
    for at, stack in tree.stacks.items():
        inner = name[len(at) + 1:]
        if name.startswith(at + "/") and inner in stack.leaves:
            return at, inner, (np.shape(stack.layers)
                               + tuple(stack.leaves[inner]))
    return None, None, None


def make_params(template, module, sizes: dict, seed: int):
    """The whole parameter tree, shaped like ``template`` (a tree of
    ``ShapeDtypeStruct``, as ``jax.eval_shape`` gives for the model's own
    initialiser), built on the default device by one compiled call.
    ``module`` is the architecture's, and ``module.params(sizes)`` its
    ``Tree``.  Every leaf of the template has to be one the tree names,
    at the shape it gives: anything else means the model is not the one
    the configuration describes."""
    tree = module.params(sizes)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    places = []
    for path, leaf in flat:
        name = _path_name(path)
        at, inner, want = _place(tree, name)
        if inner is None:
            raise ValueError(f"parameter {name!r} {leaf.shape} is not part of "
                             f"the architecture {module.__name__}")
        if tuple(leaf.shape) != want:
            raise ValueError(f"parameter {name!r} has shape {leaf.shape}, the "
                             f"configuration gives {want}")
        places.append((at, inner, leaf.dtype))

    def build(key):
        made = {}
        for at, stack in tree.stacks.items():
            wanted = {inner: (tuple(stack.leaves[inner]), dt)
                      for a, inner, dt in places if a == at}
            if not wanted:
                continue
            layers = np.asarray(stack.layers, np.int32)
            flat_made = jax.lax.map(
                lambda l: {n: make_leaf(key, n, s, dt, l)
                           for n, (s, dt) in wanted.items()},
                jnp.asarray(layers.reshape(-1)))
            made[at] = {n: a.reshape(layers.shape + a.shape[1:])
                        for n, a in flat_made.items()}
        leaves = [make_leaf(key, inner, tree.globals[inner], dt) if at is None
                  else made[at][inner] for at, inner, dt in places]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(base_key(seed))
