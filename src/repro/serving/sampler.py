"""Token samplers, host-free: everything here is jit-traceable so the engine
can fold sampling and termination into its single fused decode dispatch
(one host sync per *iteration* instead of one ``int(jnp.argmax(...))`` per
slot)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Termination reason codes returned by :func:`sample_and_reason` — index into
# REASONS to recover the engine's string reasons.  Priority order matches the
# engine's historical host-side chain (eos > length > ctx > true_len).
REASON_NONE = 0
REASON_EOS = 1
REASON_LENGTH = 2
REASON_CTX = 3
REASON_TRUE_LEN = 4
REASONS = ("", "eos", "length", "ctx", "true_len")


def greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def token_keys(base_key, rids, idx):
    """Per-token sampling keys: fold each lane's request id and the token's
    generation index into the engine seed.

    The stream for ``(rid, idx)`` is a pure function of those two values —
    independent of batch composition, warmup traffic, preemption history,
    and of whether the token was scored by a plain decode step or inside a
    verify-k dispatch.  That last property is what makes speculative
    temperature/top-k sampling reproduce the non-speculative token stream
    exactly: an accepted draft position sees the same logits (same context)
    and the same key as the step that would have sampled it one-at-a-time.

    ``rids``/``idx``: (B,) int32 -> (B,) keys.
    """
    def one(r, i):
        return jax.random.fold_in(jax.random.fold_in(base_key, r), i)
    return jax.vmap(one)(jnp.asarray(rids, jnp.int32),
                         jnp.asarray(idx, jnp.int32))


def temperature(logits, key, temp: float = 1.0, top_k: int = 0):
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[..., -1:]
        logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
    return jax.random.categorical(key, logits / max(temp, 1e-6)).astype(jnp.int32)


def sample_tokens(logits, keys, *, greedy_sampling: bool,
                  temp: float = 1.0, top_k: int = 0):
    """Per-lane keyed sampling: logits (B, V), keys (B,) -> (B,) int32.

    Each lane draws with its own :func:`token_keys` key, so a lane's sample
    is independent of which other requests share the batch."""
    if greedy_sampling:
        return greedy(logits)
    return jax.vmap(lambda lg, k: temperature(lg, k, temp=temp,
                                              top_k=top_k))(logits, keys)


def _reason_of(tok, new_gen, new_ctx, true_len, *, eos_token,
               max_new_tokens, max_seq_len):
    """Termination chain (eos > length > ctx > true_len), broadcastable."""
    return jnp.where(
        tok == eos_token, REASON_EOS,
        jnp.where(new_gen >= max_new_tokens, REASON_LENGTH,
                  jnp.where(new_ctx >= max_seq_len - 1, REASON_CTX,
                            jnp.where(new_gen >= true_len,
                                      REASON_TRUE_LEN, REASON_NONE))))


def sample_and_reason(logits, keys, *, greedy_sampling: bool,
                      temp: float, top_k: int, eos_token: int,
                      max_new_tokens: int, max_seq_len: int,
                      new_gen, new_ctx, true_len):
    """Fused sampling + termination, fully device-side.

    ``keys``: (B,) per-lane keys from :func:`token_keys`.  ``new_gen``/
    ``new_ctx`` are each slot's generated count / context length *after*
    accepting this token; ``true_len`` is the per-slot trace stop (pass a
    huge value when ``respect_true_len`` is off).  Returns
    ``(tokens (B,) int32, reason (B,) int32)`` with reason codes from
    REASON_* (0 = keep decoding).  Traced under the ``sample`` scope.
    """
    with jax.named_scope("sample"):
        tok = sample_tokens(logits, keys, greedy_sampling=greedy_sampling,
                            temp=temp, top_k=top_k)
        reason = _reason_of(tok, new_gen, new_ctx, true_len,
                            eos_token=eos_token,
                            max_new_tokens=max_new_tokens,
                            max_seq_len=max_seq_len)
        return tok, reason.astype(jnp.int32)


def verify_and_reason(logits, drafts, n_drafts, keys, active, *,
                      greedy_sampling: bool, temp: float, top_k: int,
                      eos_token: int, max_new_tokens: int, max_seq_len: int,
                      base_gen, base_ctx, true_len):
    """Verify-k acceptance + sampling + termination, fully device-side.

    Exact-match verification: position ``i`` of each lane is sampled with
    that token's own :func:`token_keys` key; draft ``drafts[:, i]`` (i >= 1)
    is accepted iff it equals the sample at position ``i - 1`` and every
    earlier draft was accepted.  Because an accepted position's logits come
    from exactly the context the sequential path would have seen, the
    emitted stream is token-identical to non-speculative decoding for *any*
    sampling method — greedy or temperature/top-k.

    ``logits``: (B, K1, V) — position i's next-token logits given the fed
    token and drafts[:, 1:i+1]; ``drafts``: (B, K1) with column 0 the fed
    previous token (never matched) and columns 1..k the draft tokens
    (zero-padded past ``n_drafts``); ``keys``: (B, K1) per-position keys;
    ``base_gen``/``base_ctx``: (B,) generated count / context length
    *before* this dispatch, so the token emitted at position i has
    ``new_gen = base_gen + 1 + i``.  Emission stops at the first terminal
    token even when later drafts match.

    Returns ``(samples (B, K1), n_emit (B,), reason (B,))`` — the caller
    emits ``samples[b, :n_emit[b]]`` and applies ``reason[b]`` to the last
    of them; inactive lanes emit nothing.  Traced under the ``sample``
    scope.
    """
    with jax.named_scope("sample"):
        B, K1, _ = logits.shape
        flat = logits.reshape(B * K1, logits.shape[-1])
        if greedy_sampling:
            s = greedy(flat).reshape(B, K1)
        else:
            kflat = keys.reshape(B * K1, *keys.shape[2:])
            s = jax.vmap(lambda lg, k: temperature(lg, k, temp=temp,
                                                   top_k=top_k))(
                flat, kflat).reshape(B, K1)
        pos = jnp.arange(K1)[None, :]                          # (1, K1)
        prev = jnp.roll(s, 1, axis=1)                          # prev[:, i] = s[:, i-1]
        match = (pos == 0) | ((drafts == prev)
                              & (pos <= n_drafts[:, None]))
        acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
        m_cand = acc.sum(axis=1)                               # 1 + accepted drafts
        new_gen = base_gen[:, None] + 1 + pos
        new_ctx = base_ctx[:, None] + 1 + pos
        reason = _reason_of(s, new_gen, new_ctx, true_len[:, None],
                            eos_token=eos_token, max_new_tokens=max_new_tokens,
                            max_seq_len=max_seq_len)
        first_term = jnp.min(jnp.where(reason > 0, pos, K1), axis=1)
        m = jnp.clip(jnp.minimum(m_cand, first_term + 1), 1, K1)
        n_emit = jnp.where(active, m, 0).astype(jnp.int32)
        last = jnp.take_along_axis(reason, (m - 1)[:, None], axis=1)[:, 0]
        reason_out = jnp.where(active, last, 0).astype(jnp.int32)
        return s, n_emit, reason_out


def decode_epilogue(logits, active, new_gen, new_ctx, true_len, rids,
                    base_key, *, greedy_sampling: bool = True,
                    temp: float = 1.0, top_k: int = 0, eos_token: int = 1,
                    max_new_tokens: int = 128, max_seq_len: int = 256):
    """The fused decode step's tail over its last-position logits (B, V):
    each lane's key from ``(rids, new_gen - 1)`` (the generation index of
    the token being sampled), then :func:`sample_and_reason`; lanes not
    ``active`` get reason 0.  Returns ``(tokens (B,), reason (B,))``."""
    keys = None if greedy_sampling else token_keys(base_key, rids,
                                                   new_gen - 1)
    tok, reason = sample_and_reason(
        logits, keys, greedy_sampling=greedy_sampling, temp=temp,
        top_k=top_k, eos_token=eos_token, max_new_tokens=max_new_tokens,
        max_seq_len=max_seq_len, new_gen=new_gen, new_ctx=new_ctx,
        true_len=true_len)
    return tok, jnp.where(active, reason, 0)


def verify_epilogue(logits, tokens, n_drafts, active, base_gen, base_ctx,
                    true_len, rids, base_key, *,
                    greedy_sampling: bool = True, temp: float = 1.0,
                    top_k: int = 0, eos_token: int = 1,
                    max_new_tokens: int = 128, max_seq_len: int = 256):
    """The fused verify-k step's tail over its logits (B, K1, V): a key
    per draft position ``(rids, base_gen + i)``, then
    :func:`verify_and_reason`.  Returns ``(samples (B, K1), n_emit (B,),
    reason (B,))``."""
    B, K1 = tokens.shape
    keys = None
    if not greedy_sampling:
        rr = jnp.repeat(jnp.asarray(rids, jnp.int32), K1)
        ii = (jnp.asarray(base_gen, jnp.int32)[:, None]
              + jnp.arange(K1, dtype=jnp.int32)[None, :]).reshape(-1)
        keys = token_keys(base_key, rr, ii).reshape(B, K1, -1)
    return verify_and_reason(
        logits, tokens, jnp.asarray(n_drafts, jnp.int32), keys, active,
        greedy_sampling=greedy_sampling, temp=temp, top_k=top_k,
        eos_token=eos_token, max_new_tokens=max_new_tokens,
        max_seq_len=max_seq_len, base_gen=base_gen, base_ctx=base_ctx,
        true_len=true_len)
