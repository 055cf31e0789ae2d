"""Plain reference of the dense GQA decoder, and the comparison that decides
whether a run served the right tokens.

The forward pass is written straight from the architecture's description
in ``jax.numpy``, in float32 under ``default_matmul_precision("highest")``,
with no cache, no paging and no kernels: pre-norm RMSNorm (eps 1e-5,
scale only), rotary embeddings on the two halves of each head, grouped
query attention with causal masking and a 1/sqrt(head_dim) scale, SwiGLU
feed-forward (silu of the gate times the up projection), a final RMSNorm
and the output head (the transposed embedding when tied).  It imports
nothing of the system under test: it reads the configuration's sizes and
makes its weights from the seed with ``weights.py``, one layer at a time.

It runs teacher-forced over each sampled request's prompt followed by the
tokens the system served, all padded to one length so that one compiled
program serves every run.  At the position before each served token it
reads the reference's best logit and the served token's logit; the gap
between them is zero where the served token is the reference's first
choice.  The widest gap over the sample is the number ``correct`` is
decided by.  The system samples greedily, so a gap above rounding means a
token the model would not have chosen.

``precision="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 (e4m3, scaled per tensor to its
largest magnitude), the lower precision a later change might be tempted
by.  Its reading is the gap, by the float32 reference's logits, of the
token the fp8 forward puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .weights import base_key, make_globals, make_layer

EPS = 1e-5
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q(x, precision: str):
    """Round a matrix-product operand to the run's precision."""
    if precision == "f32":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, b, precision: str):
    return _q(a, precision) @ _q(b, precision)


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
    hd = sizes.get("head_dim") or D // H
    G = H // KVH
    pos = jnp.arange(S)
    x = _rms(h, w["ln1/scale"])
    q = _mm(x, w["attn/wq"], precision).reshape(S, H, hd)
    k = _mm(x, w["attn/wk"], precision).reshape(S, KVH, hd)
    v = _mm(x, w["attn/wv"], precision).reshape(S, KVH, hd)
    q = _rope(q, pos, sizes["rope_theta"])
    k = _rope(k, pos, sizes["rope_theta"])
    qg = _q(q, precision).reshape(S, KVH, G, hd)
    s = jnp.einsum("skgd,tkd->kgst", qg, _q(k, precision)) / np.sqrt(hd)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", _q(p, precision), _q(v, precision))
    h = h + _mm(o.reshape(S, H * hd), w["attn/wo"], precision)
    x = _rms(h, w["ln2/scale"])
    ff = jax.nn.silu(_mm(x, w["ffn/wg"], precision)) * _mm(
        x, w["ffn/wi"], precision)
    return h + _mm(ff, w["ffn/wo"], precision)


@functools.lru_cache(maxsize=None)
def _programs(sizes_key: tuple, precision: str):
    sizes = dict(sizes_key)

    def embed(key, tokens):
        g = make_globals(key, sizes, jnp.float32)
        return jnp.take(g["embed"], tokens, axis=0)

    def layer(key, l, h, lengths):
        w = make_layer(key, sizes, l, jnp.float32)
        return jax.lax.map(
            lambda a: _layer_one(sizes, precision, w, a[0], a[1]),
            (h, lengths))

    def head(key, h, targets):
        """Per position: the best logit, the logits of ``targets`` (S, K)
        and the first choice; the (S, V) logits never leave the chip."""
        g = make_globals(key, sizes, jnp.float32)
        w = g["embed"].T if sizes["tie_embeddings"] else g["lm_head"]

        def one(a):
            x, t = a
            z = _mm(_rms(x, g["final_norm/scale"]), w, precision)
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


def pack(samples, seq_len: int, rows: int):
    """(tokens (rows, seq_len), lengths, spans): each sample is
    ``(prompt, served)``; its sequence is the prompt then every served
    token but the last, and ``spans[i]`` the positions whose logits
    predict the served tokens.  Unused rows are one padding token."""
    if len(samples) > rows:
        raise ValueError(f"{len(samples)} samples for {rows} rows")
    tokens = np.zeros((rows, seq_len), np.int32)
    lengths = np.ones((rows,), np.int32)
    spans = []
    for i, (prompt, served) in enumerate(samples):
        seq = list(prompt) + list(served[:-1])
        if len(seq) > seq_len:
            raise ValueError(f"sequence of {len(seq)} exceeds {seq_len}")
        tokens[i, :len(seq)] = seq
        lengths[i] = len(seq)
        spans.append((len(prompt) - 1, len(prompt) - 1 + len(served)))
    return tokens, lengths, spans


def _targets(spans, chosen_lists, shape):
    """(N, S, K) token ids: column k holds chosen_lists[k][i] along
    sample i's span, 0 elsewhere."""
    t = np.zeros(shape + (len(chosen_lists),), np.int32)
    for k, chosen in enumerate(chosen_lists):
        for i, (a, b) in enumerate(spans):
            t[i, a:b, k] = chosen[i]
    return t


def _widest(best, picked, spans) -> list:
    """Per sample, the widest gap of the picked logits below the best."""
    return [float(np.max(best[i, a:b] - picked[i, a:b]))
            for i, (a, b) in enumerate(spans)]


def served_gaps(sizes: dict, seed: int, samples, seq_len: int,
                rows: int) -> list:
    """Per sample, the widest gap by which a served token's reference
    logit lies below the reference's best logit at its position."""
    tokens, lengths, spans = pack(samples, seq_len, rows)
    t = _targets(spans, [[list(s) for _, s in samples]], tokens.shape)
    best, picked, _ = summarize(sizes, seed, tokens, lengths, t, "f32")
    return _widest(best, picked[..., 0], spans)


def control_gaps(sizes: dict, seed: int, samples, seq_len: int, rows: int,
                 precision: str = "fp8"):
    """(served, control): per sample the widest gap of the served tokens
    and of the tokens the ``precision`` forward puts first at the same
    positions, both by the float32 reference."""
    tokens, lengths, spans = pack(samples, seq_len, rows)
    zero = np.zeros(tokens.shape + (1,), np.int32)
    _, _, first = summarize(sizes, seed, tokens, lengths, zero, precision)
    firsts = [first[i, a:b] for i, (a, b) in enumerate(spans)]
    t = _targets(spans, [[list(s) for _, s in samples], firsts],
                 tokens.shape)
    best, picked, _ = summarize(sizes, seed, tokens, lengths, t, "f32")
    return (_widest(best, picked[..., 0], spans),
            _widest(best, picked[..., 1], spans))
