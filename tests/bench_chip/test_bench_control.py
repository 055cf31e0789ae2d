"""The control of the correctness check, at toy widths: the reference
computed in float8 in the system's place reads a gap above the limit, so
a run served by it comes out not correct, while the float32 reference's
own greedy tokens read no gap at all."""
import numpy as np

from bench_chip_tiny import tiny_cell
from benchmarks.chip import harness, reference, traffic

CELL = tiny_cell("granite-3-8b", "sharegpt-1.28rps")
ARCH = CELL.architecture
SIZES = dict(CELL.config["arch"])
TINY_GAP_LIMIT = CELL.config["check"]["logit_gap_limit"]
SEQ, ROWS, SEED = 128, 4, 2 ** 34 + 5


def greedy_samples(n_new=24):
    """Prompts from the traffic generator, continued greedily by the
    float32 reference itself."""
    rng = traffic.token_rng(SEED)
    prompts = [[2 + int(t) % (SIZES["vocab_size"] - 2)
                for t in rng.integers(0, 8192, n)] for n in (9, 30, 17)]
    served = [[] for _ in prompts]
    for _ in range(n_new):
        samples = [(p, s + [0]) for p, s in zip(prompts, served)]
        tokens, lengths, spans = reference.pack(samples, SEQ, ROWS)
        zero = np.zeros(tokens.shape + (1,), np.int32)
        _, _, first = ARCH.summarize(SIZES, SEED, tokens, lengths, zero)
        for i, (a, b) in enumerate(spans):
            served[i].append(int(first[i, b - 1]))
    return list(zip(prompts, served))


def test_fp8_control_fails_the_check_and_float32_passes():
    samples = greedy_samples()
    served, control = reference.control_gaps(ARCH.summarize, SIZES, SEED,
                                             samples, SEQ, ROWS, "fp8")
    assert max(served) == 0.0
    assert max(control) > 2 * TINY_GAP_LIMIT
    conf = {"check": {"logit_gap_limit": TINY_GAP_LIMIT}}
    recs = []
    as_control = harness.compared(conf, recs, 0, control)
    assert as_control["logit_gap"]["value"] > TINY_GAP_LIMIT
    assert harness.compared(conf, recs, 0, served)["logit_gap"]["value"] \
        <= TINY_GAP_LIMIT
