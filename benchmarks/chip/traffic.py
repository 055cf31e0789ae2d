"""The one traffic generator: turns a traffic file (``traffic/<name>.json``)
and a seed into the requests a run sends.

Lengths follow the Alpaca and ShareGPT shapes ALISE synthesises its traces
from (paper section 4.1): a prompt length drawn lognormal, an output length
drawn lognormal around the median of one of ``n_clusters`` topic clusters,
and prompt tokens drawn from the cluster's vocabulary slice, Zipf-skewed,
with shared noise tokens (``repro/core/trace.py`` holds the program's own
copy).  Every length is cut to the traffic file's ``max_prompt`` and
``max_output``; no prompt is lengthened.

The work is the same for every seed.  The traffic file's ``shape_seed``
fixes the sizes, service classes and arrival instants; the run's seed only
shuffles which request comes when and draws the prompt tokens.  So two
seeds offer the same set of requests in another order, and the spread
between seeds is the system's, not the generator's.

Arrivals are open loop: Poisson at ``rate`` per second from ``ramp_s``
before the window to ``drain_cap_s`` after it; each segment (ramp,
window, after) has its own fixed set of sizes, shuffled within the
segment.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

TOPIC_TOKENS = 64               # tokens per topic signature
TOKEN_SPACE = 8192              # synthetic prompt token space


@dataclass
class Spec:
    """One request as the generator draws it (tokens come later)."""
    prompt_len: int
    output_len: int
    interactive: bool
    shape: str
    cluster: int


def _draw(rng, traffic: dict, n: int) -> List[Spec]:
    shapes = traffic["shapes"]
    names = sorted(shapes)
    share = np.array([shapes[k]["share"] for k in names], float)
    out = []
    for _ in range(n):
        name = names[int(rng.choice(len(names), p=share / share.sum()))]
        s = shapes[name]
        # cluster medians are a property of the shape, as in the paper's
        # topic clusters: fixed by the shape's name, not by any seed
        med_rng = np.random.default_rng(
            [ord(c) for c in name] + [s["n_clusters"]])
        medians = np.exp(med_rng.uniform(np.log(s["out_median_lo"]),
                                         np.log(s["out_median_hi"]),
                                         s["n_clusters"]))
        c = int(rng.integers(s["n_clusters"]))
        plen = int(np.clip(rng.lognormal(s["in_mu"], s["in_sigma"]), 4,
                           traffic["max_prompt"]))
        olen = int(np.clip(rng.lognormal(np.log(medians[c]),
                                         s["length_noise"] * s["out_sigma"]),
                           1, min(s["out_cap"], traffic["max_output"])))
        out.append(Spec(plen, olen, bool(rng.random()
                                         < traffic["interactive_share"]),
                        name, c))
    return out


def prompt_tokens(rng, spec: Spec, vocab: int) -> List[int]:
    """Cluster signature tokens (Zipf within the cluster's slice) mixed
    with noise tokens, mapped into [2, vocab): 0 pads and 1 ends."""
    n = spec.prompt_len
    sig_base = (spec.cluster * TOPIC_TOKENS) % (TOKEN_SPACE // 2)
    n_sig = max((n * 3) // 5, 1)
    ranks = np.minimum(rng.zipf(1.6, n_sig) - 1, TOPIC_TOKENS - 1)
    noise = TOKEN_SPACE // 2 + rng.integers(0, TOKEN_SPACE // 2, n - n_sig)
    toks = np.concatenate([sig_base + ranks, noise])
    rng.shuffle(toks)
    return [2 + int(t) % (vocab - 2) for t in toks]


@dataclass
class Arrival:
    """An open-loop request: ``due`` seconds from the window's start
    (negative in the ramp), and whether it is measured."""
    due: float
    spec: Spec
    measured: bool


def open_loop(traffic: dict, seconds: float, seed: int) -> List[Arrival]:
    fixed = np.random.default_rng(traffic["shape_seed"])
    rate, ramp, drain = (traffic["rate"], traffic["ramp_s"],
                         traffic["drain_cap_s"])
    segments = [(-ramp, 0.0, False), (0.0, float(seconds), True),
                (float(seconds), seconds + drain, False)]
    shuffle = np.random.default_rng([seed, 1])
    out: List[Arrival] = []
    for i, (a, b, measured) in enumerate(segments):
        seg = np.random.default_rng([traffic["shape_seed"], i])
        t, dues = a, []
        while True:
            t += fixed.exponential(1.0 / rate)
            if t >= b:
                break
            dues.append(t)
        specs = _draw(seg, traffic, len(dues))
        order = shuffle.permutation(len(specs))
        out += [Arrival(d, specs[j], measured) for d, j in zip(dues, order)]
    return out


def token_rng(seed: int):
    return np.random.default_rng([seed, 2])
