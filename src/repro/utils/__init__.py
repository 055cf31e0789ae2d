"""Small shared utilities."""
from __future__ import annotations

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
    "int8": jnp.int8,
    "int4": jnp.int4,
    "int32": jnp.int32,
}


def dtype_of(name: str):
    return DTYPES[name]


def tree_size_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def tree_param_count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def assert_no_nans(tree, where: str = ""):
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
            raise AssertionError(f"non-finite values at {jax.tree_util.keystr(path)} {where}")


REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache for this process; returns
    its directory.  Called from entry points, never at import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache is the fixed ``.jax_cache/``
    at the repository root: a fixed path, because a directory that moves
    between runs never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
