"""Seconds from process start to the first request: JAX start-up, the
weights, the engine with its warm-up, the compile-cache loads."""


def read(rec):
    return rec.setup_s
