"""Largest share of the KV pool that the decode lanes of one iteration in
the window held: their context tokens, from the engine's iteration ring,
over the pool's capacity in tokens."""


def read(rec):
    if not rec.iters:
        return None
    return 100.0 * max(c for _, c, _, _ in rec.iters) / rec.pool_tokens
