"""Runs at toy widths with the timed path broken underneath: each fault
a serving cell can have has to turn ``correct`` false.  One-chip cells
have no exchange between chips, so that fault does not apply; for a
batch, the rows of the decode step's output are misaligned."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_chip_tiny import run_tiny, tiny_cell
from benchmarks.chip import harness

BUILD = harness.build


def token_altered(engine):
    """Every decoded token is the next id of the vocabulary, where the
    sampler produces it."""
    decode = engine.kv.decode
    vocab = engine.model.cfg.vocab_size

    def wrapped(*a, **k):
        tok, reason = decode(*a, **k)
        return (tok + 1) % vocab, reason
    engine.kv.decode = wrapped


def state_unchanged(engine):
    """The decode step hands back the KV pool it was given: the fed
    token's keys and values are never written."""
    decode, pool = engine.kv.decode, engine.kv.pool
    jax.block_until_ready(jnp.copy(pool.k))     # warm the copy program

    def wrapped(*a, **k):
        k0, v0 = jnp.copy(pool.k), jnp.copy(pool.v)
        out = decode(*a, **k)
        pool.k, pool.v = k0, v0
        return out
    engine.kv.decode = wrapped


def rows_misaligned(engine):
    """The batch's rows come back shifted by one: each lane is handed the
    token its neighbour's row produced."""
    decode = engine.kv.decode

    def wrapped(*a, **k):
        tok, reason = decode(*a, **k)
        return np.roll(np.asarray(tok), 1), reason
    engine.kv.decode = wrapped


def compiles_in_window(engine):
    """Each decode step compiles a program of its own."""
    decode = engine.kv.decode
    n = [0]

    def wrapped(*a, **k):
        n[0] += 1
        jax.jit(lambda x, i=n[0]: x + i)(jnp.zeros((n[0],)))
        return decode(*a, **k)
    engine.kv.decode = wrapped


@pytest.mark.parametrize("fault,fails", [
    (token_altered, "logit_gap"), (state_unchanged, "logit_gap"),
    (rows_misaligned, "logit_gap"), (compiles_in_window, "compiles_serving")])
def test_fault_turns_correct_false(monkeypatch, fault, fails):
    def broken(cell, seed, device):
        gw, engine, params = BUILD(cell, seed, device)
        fault(engine)
        return gw, engine, params
    monkeypatch.setattr(harness, "build", broken)
    cell = tiny_cell("granite-3-8b", "sharegpt-1.28rps")
    out = run_tiny(cell, seed=11)
    c = out["compared"][fails]
    assert not out["correct"]
    assert c["value"] is not None and c["value"] > c["limit"], out["compared"]
