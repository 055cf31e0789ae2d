"""Operations and bytes of a step's work, shared by every architecture.

Each architecture's module under ``archs/`` counts its own steps from the
configuration's sizes and the real context lengths, as ``Work``:

    decode_step(sizes, lanes, context_tokens) -> Work
    prefill_chunk(sizes, start, tokens) -> Work

It counts the work, not an implementation of it: padding, and positions
a kernel copies or scores past a sequence's end, are waste and are not
counted, so a roofline share built on the counts reads the same work
whichever kernel a later change puts on the path.
"""
from __future__ import annotations

from dataclasses import dataclass

BYTES = 2                       # bfloat16 weights and KV


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_seconds(self, peak) -> float:
        """The least time a chip with ``peak`` could take: the larger of
        the compute bound and the memory bound."""
        return max(self.flops / peak.bf16_flops,
                   self.bytes / peak.hbm_bytes_per_s)
