"""The dense GQA decoder: its parameter tree, its plain reference and the
work of its steps.  Everything the benchmark knows of this architecture's
mathematics is here; a configuration file selects it with
``"architecture": "dense_gqa"``.

``sizes`` is the ``arch`` block of a configuration file (``d_model``,
``num_layers``, ``num_heads``, ``num_kv_heads``, ``head_dim`` or none,
``d_ff``, ``vocab_size``, ``tie_embeddings``, ``rope_theta``, ``act``,
``norm_type``, ``qkv_bias``).

Parameters follow the program's tree of a dense decoder: ``embed``
(V, D), ``lm_head`` (D, V) when the head is not tied,
``final_norm/scale``, and in the one stack ``layers``, for each layer,
``ln1/scale``, ``attn/{wq,wk,wv,wo}``, ``ln2/scale``, ``ffn/{wi,wg,wo}``
(``wi`` the up projection, ``wg`` the gate).

The reference's forward pass is written straight from the architecture's
description in ``jax.numpy``, in float32 under
``default_matmul_precision("highest")``, with no cache, no paging and no
kernels: pre-norm RMSNorm (eps 1e-5, scale only), rotary embeddings on the
two halves of each head, grouped query attention with causal masking and a
1/sqrt(head_dim) scale, SwiGLU feed-forward (silu of the gate times the up
projection), a final RMSNorm and the output head (the transposed embedding
when tied).  It imports nothing of the system under test: it makes its
weights from the seed with ``weights.py``, one layer at a time.
``reference.py`` turns its logits into the gaps ``correct`` is decided by.

The work counts are the work, not an implementation of it: padded lanes,
padded chunk rows, the positions a gather copies past a sequence's end and
the scores a masked chunk attention computes over empty positions are all
waste, and none is counted.  A roofline share built on these counts
therefore reads the same work whichever kernel a later change puts on the
path.  Weights and the KV cache are taken as bfloat16, the type they are
served in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.flops import BYTES, Work
from benchmarks.chip.reference import mm, operand
from benchmarks.chip.weights import Stack, Tree, base_key, make_leaves

EPS = 1e-5


# ------------------------------------------------------------ parameters
def head_dim(sizes: dict) -> int:
    return sizes.get("head_dim") or sizes["d_model"] // sizes["num_heads"]


def global_shapes(sizes: dict) -> dict:
    D, V = sizes["d_model"], sizes["vocab_size"]
    shapes = {"embed": (V, D), "final_norm/scale": (D,)}
    if not sizes["tie_embeddings"]:
        shapes["lm_head"] = (D, V)
    return shapes


def layer_shapes(sizes: dict) -> dict:
    D, F = sizes["d_model"], sizes["d_ff"]
    H, KVH = sizes["num_heads"], sizes["num_kv_heads"]
    hd = head_dim(sizes)
    return {"ln1/scale": (D,), "attn/wq": (D, H * hd),
            "attn/wk": (D, KVH * hd), "attn/wv": (D, KVH * hd),
            "attn/wo": (H * hd, D), "ln2/scale": (D,),
            "ffn/wi": (D, F), "ffn/wg": (D, F), "ffn/wo": (F, D)}


def params(sizes: dict) -> Tree:
    return Tree(global_shapes(sizes),
                {"layers": Stack(tuple(range(sizes["num_layers"])),
                                 layer_shapes(sizes))})


# ------------------------------------------------------------- reference
def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, pos, theta: float):
    """x: (S, heads, hd); rotate the first half against the second."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_one(sizes: dict, precision: str, w: dict, h, length):
    """One decoder layer over one sequence h: (S, D); positions >= length
    are padding and never attended to by real positions."""
    S, D = h.shape
    H, KVH = sizes["num_heads"], sizes["num_kv_heads"]
    hd = head_dim(sizes)
    G = H // KVH
    pos = jnp.arange(S)
    x = _rms(h, w["ln1/scale"])
    q = mm(x, w["attn/wq"], precision).reshape(S, H, hd)
    k = mm(x, w["attn/wk"], precision).reshape(S, KVH, hd)
    v = mm(x, w["attn/wv"], precision).reshape(S, KVH, hd)
    q = _rope(q, pos, sizes["rope_theta"])
    k = _rope(k, pos, sizes["rope_theta"])
    qg = operand(q, precision).reshape(S, KVH, G, hd)
    s = jnp.einsum("skgd,tkd->kgst", qg,
                   operand(k, precision)) / np.sqrt(hd)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", operand(p, precision),
                   operand(v, precision))
    h = h + mm(o.reshape(S, H * hd), w["attn/wo"], precision)
    x = _rms(h, w["ln2/scale"])
    ff = jax.nn.silu(mm(x, w["ffn/wg"], precision)) * mm(
        x, w["ffn/wi"], precision)
    return h + mm(ff, w["ffn/wo"], precision)


@functools.lru_cache(maxsize=None)
def _programs(sizes_key: tuple, precision: str):
    sizes = dict(sizes_key)

    def embed(key, tokens):
        g = make_leaves(key, global_shapes(sizes), jnp.float32)
        return jnp.take(g["embed"], tokens, axis=0)

    def layer(key, l, h, lengths):
        w = make_leaves(key, layer_shapes(sizes), jnp.float32, l)
        return jax.lax.map(
            lambda a: _layer_one(sizes, precision, w, a[0], a[1]),
            (h, lengths))

    def head(key, h, targets):
        """Per position: the best logit, the logits of ``targets`` (S, K)
        and the first choice; the (S, V) logits never leave the chip."""
        g = make_leaves(key, global_shapes(sizes), jnp.float32)
        w = g["embed"].T if sizes["tie_embeddings"] else g["lm_head"]

        def one(a):
            x, t = a
            z = mm(_rms(x, g["final_norm/scale"]), w, precision)
            return (z.max(-1), jnp.take_along_axis(z, t, axis=-1),
                    jnp.argmax(z, -1).astype(jnp.int32))
        return jax.lax.map(one, (h, targets))

    return jax.jit(embed), jax.jit(layer), jax.jit(head)


def summarize(sizes: dict, seed: int, tokens: np.ndarray,
              lengths: np.ndarray, targets: np.ndarray,
              precision: str = "f32"):
    """The reference over ``tokens`` (N, S) int32, sequence i real up to
    ``lengths[i]``: per position its best logit (N, S), the logits of
    ``targets`` (N, S, K) and its first choice (N, S).  Layer weights are
    made one layer at a time and dropped after use."""
    for k, want in (("norm_type", "rmsnorm"), ("act", "swiglu"),
                    ("qkv_bias", False)):
        if sizes.get(k, want) != want:
            raise ValueError(f"the reference has no {k}={sizes[k]!r}")
    key = base_key(seed)
    embed, layer, head = _programs(tuple(sorted(sizes.items())), precision)
    with jax.default_matmul_precision("highest"):
        h = embed(key, jnp.asarray(tokens))
        lens = jnp.asarray(lengths, jnp.int32)
        for l in range(sizes["num_layers"]):
            h = layer(key, jnp.asarray(l, jnp.int32), h, lens)
        out = head(key, h, jnp.asarray(targets, jnp.int32))
    return tuple(np.asarray(x) for x in out)



# ----------------------------------------------------------------- work
def layer_matmul_params(sizes: dict) -> int:
    """Weights one token multiplies through in one layer: the Q, K, V and
    output projections and the feed-forward matrices."""
    D, F = sizes["d_model"], sizes["d_ff"]
    hd = head_dim(sizes)
    attn = D * hd * (2 * sizes["num_heads"] + 2 * sizes["num_kv_heads"])
    ffn = (3 if sizes.get("act", "swiglu") == "swiglu" else 2) * D * F
    return attn + ffn


def weight_bytes(sizes: dict) -> int:
    """Every weight one forward step reads once: the layers, their norms,
    the final norm and the output head (the tied embedding when tied).
    The embedding rows a step looks up are counted with the head when the
    two are tied, and left out otherwise (a few rows per token)."""
    D, V, L = sizes["d_model"], sizes["vocab_size"], sizes["num_layers"]
    per_layer = layer_matmul_params(sizes) + 2 * D
    return BYTES * (L * per_layer + D + V * D)


def kv_bytes_per_token(sizes: dict) -> int:
    return (BYTES * 2 * sizes["num_layers"] * sizes["num_kv_heads"]
            * head_dim(sizes))


def _attn_flops_per_pair(sizes: dict) -> int:
    """FLOPs of one query attending to one key in every layer: the score
    and the weighted value, each 2 * heads * head_dim."""
    return 4 * sizes["num_layers"] * sizes["num_heads"] * head_dim(sizes)


def decode_step(sizes: dict, lanes: int, context_tokens: int) -> Work:
    """One decode iteration of ``lanes`` sequences whose contexts sum to
    ``context_tokens`` (each lane's context includes the token it feeds,
    whose KV the step writes).  Each lane computes logits for one token."""
    D, V, L = sizes["d_model"], sizes["vocab_size"], sizes["num_layers"]
    flops = (lanes * 2 * (L * layer_matmul_params(sizes) + D * V)
             + context_tokens * _attn_flops_per_pair(sizes))
    kv = kv_bytes_per_token(sizes)
    return Work(flops, weight_bytes(sizes) + context_tokens * kv)


def prefill_chunk(sizes: dict, start: int, tokens: int) -> Work:
    """One prefill chunk of ``tokens`` prompt tokens at positions
    ``[start, start + tokens)``: each attends causally to the ``start``
    cached tokens and to the chunk's own earlier tokens; logits come out
    for the chunk's last token only."""
    D, V, L = sizes["d_model"], sizes["vocab_size"], sizes["num_layers"]
    pairs = tokens * start + tokens * (tokens + 1) // 2
    flops = (tokens * 2 * L * layer_matmul_params(sizes) + 2 * D * V
             + pairs * _attn_flops_per_pair(sizes))
    kv = kv_bytes_per_token(sizes)
    return Work(flops, weight_bytes(sizes) + (start + tokens) * kv)
