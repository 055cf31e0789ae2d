"""Record the small profiler trace that ``test_bench_trace_reduce.py``
reads.  Run once on a chip; the trace is committed beside the tests.

    python3 tests/bench_chip/make_trace_fixture.py OUT_DIR

Inside a ``bench:window`` span it runs six iterations of a mock serving
loop on the chip: a host-only ``Scheduler.plan`` span that sleeps 3 ms
(an idle gap the reduction must charge to it), then a ``decode`` span
(``lanes=4``, ``ctx=1000``) around one execution of ``decode_step``, a
jitted matmul chain, and every other iteration a ``prefill`` span
(``tokens=64``, ``start=0``) around ``prefill_chunk``.  Each device call
is waited for, so program executions never overlap.
"""
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@jax.jit
def decode_step(x, w):
    for _ in range(4):
        x = jnp.tanh(x @ w)
    return x


@jax.jit
def prefill_chunk(x, w):
    return jnp.tanh(x @ w) @ w


def main(out: str) -> None:
    w = jnp.full((1024, 1024), 1e-3, jnp.bfloat16)
    x = jnp.ones((256, 1024), jnp.bfloat16)
    decode_step(x, w).block_until_ready()
    prefill_chunk(x, w).block_until_ready()
    jax.profiler.start_trace(out)
    with TraceAnnotation("bench:window"):
        for i in range(6):
            with TraceAnnotation("bench:ServingEngine.step"):
                with TraceAnnotation("bench:Scheduler.plan"):
                    time.sleep(0.003)
                with TraceAnnotation("bench:decode", lanes=4, ctx=1000):
                    decode_step(x, w).block_until_ready()
                if i % 2 == 0:
                    with TraceAnnotation("bench:prefill", tokens=64, start=0):
                        prefill_chunk(x, w).block_until_ready()
    jax.profiler.stop_trace()
    print(jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
