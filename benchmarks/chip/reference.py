"""The comparison that decides whether a run served the right tokens,
shared by every architecture.  Each architecture's plain reference, its
forward pass, lives in its own module under ``archs/`` as ``summarize``:

    summarize(sizes, seed, tokens, lengths, targets, precision)
        -> (best (N, S), logits of targets (N, S, K), first choice (N, S))

over ``tokens`` (N, S) int32, sequence i real up to ``lengths[i]``,
written straight from the architecture's description in float32 under
``default_matmul_precision("highest")``, with no cache, no paging and no
kernels, its weights made from the seed with ``weights.py`` one layer at
a time.  It imports nothing of the system under test.

The reference runs teacher-forced over each sampled request's prompt
followed by the tokens the system served, all padded to one length so
that one compiled program serves every run.  At the position before each
served token it reads the reference's best logit and the served token's
logit; the gap between them is zero where the served token is the
reference's first choice.  The widest gap over the sample is the number
``correct`` is decided by.  The system samples greedily, so a gap above
rounding means a token the model would not have chosen.

``precision="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 (e4m3, scaled per tensor to its
largest magnitude) by ``operand`` and ``mm`` here, the lower precision a
later change might be tempted by.  Its reading is the gap, by the float32
reference's logits, of the token the fp8 forward puts first.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def operand(x, precision: str):
    """Round a matrix-product operand to the run's precision."""
    if precision == "f32":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(a, b, precision: str):
    return operand(a, precision) @ operand(b, precision)


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


def served_gaps(summarize, sizes: dict, seed: int, samples, seq_len: int,
                rows: int) -> list:
    """Per sample, the widest gap by which a served token's reference
    logit lies below the reference's best logit at its position.
    ``summarize`` is the architecture module's forward."""
    tokens, lengths, spans = pack(samples, seq_len, rows)
    t = _targets(spans, [[list(s) for _, s in samples]], tokens.shape)
    best, picked, _ = summarize(sizes, seed, tokens, lengths, t, "f32")
    return _widest(best, picked[..., 0], spans)


def control_gaps(summarize, sizes: dict, seed: int, samples, seq_len: int,
                 rows: int, precision: str = "fp8"):
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
