"""Mean milliseconds of a decode iteration in the window, from the
engine's iteration ring (host clock around the fused dispatch and its
one sync)."""


def read(rec):
    if not rec.iters:
        return None
    return 1e3 * sum(dt for _, _, _, dt in rec.iters) / len(rec.iters)
