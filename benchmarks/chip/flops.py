"""Operations and bytes of the work a dense GQA decoder does, from the
configuration's sizes and the real context lengths.

This counts the work, not an implementation of it: padded lanes, padded
chunk rows, the positions a gather copies past a sequence's end and the
scores a masked chunk attention computes over empty positions are all
waste, and none is counted.  A roofline share built on these counts
therefore reads the same work whichever kernel a later change puts on the
path.

``sizes`` is the ``arch`` block of a configuration file (``d_model``,
``num_layers``, ``num_heads``, ``num_kv_heads``, ``head_dim`` or none,
``d_ff``, ``vocab_size``, ``tie_embeddings``, ``act``).  Weights and the
KV cache are taken as bfloat16, the type they are served in.
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


def head_dim(sizes: dict) -> int:
    return sizes.get("head_dim") or sizes["d_model"] // sizes["num_heads"]


def layer_matmul_params(sizes: dict) -> int:
    """Weights one token multiplies through in one layer: the Q, K, V and
    output projections and the feed-forward matrices."""
    D, F = sizes["d_model"], sizes["d_ff"]
    hd = head_dim(sizes)
    attn = D * hd * (2 * sizes["num_heads"] + 2 * sizes["num_kv_heads"])
    ffn = (3 if sizes.get("act", "swiglu") == "swiglu" else 2) * D * F
    return attn + ffn


def weight_bytes(sizes: dict) -> int:
    """Every weight one forward step reads once: the layers, their norms,
    the final norm and the output head (the tied embedding when tied).
    The embedding rows a step looks up are counted with the head when the
    two are tied, and left out otherwise (a few rows per token)."""
    D, V, L = sizes["d_model"], sizes["vocab_size"], sizes["num_layers"]
    per_layer = layer_matmul_params(sizes) + 2 * D
    return BYTES * (L * per_layer + D + V * D)


def kv_bytes_per_token(sizes: dict) -> int:
    return (BYTES * 2 * sizes["num_layers"] * sizes["num_kv_heads"]
            * head_dim(sizes))


def _attn_flops_per_pair(sizes: dict) -> int:
    """FLOPs of one query attending to one key in every layer: the score
    and the weighted value, each 2 * heads * head_dim."""
    return 4 * sizes["num_layers"] * sizes["num_heads"] * head_dim(sizes)


def decode_step(sizes: dict, lanes: int, context_tokens: int) -> Work:
    """One decode iteration of ``lanes`` sequences whose contexts sum to
    ``context_tokens`` (each lane's context includes the token it feeds,
    whose KV the step writes).  Each lane computes logits for one token."""
    D, V, L = sizes["d_model"], sizes["vocab_size"], sizes["num_layers"]
    flops = (lanes * 2 * (L * layer_matmul_params(sizes) + D * V)
             + context_tokens * _attn_flops_per_pair(sizes))
    kv = kv_bytes_per_token(sizes)
    return Work(flops, weight_bytes(sizes) + context_tokens * kv)


def prefill_chunk(sizes: dict, start: int, tokens: int) -> Work:
    """One prefill chunk of ``tokens`` prompt tokens at positions
    ``[start, start + tokens)``: each attends causally to the ``start``
    cached tokens and to the chunk's own earlier tokens; logits come out
    for the chunk's last token only."""
    D, V, L = sizes["d_model"], sizes["vocab_size"], sizes["num_layers"]
    pairs = tokens * start + tokens * (tokens + 1) // 2
    flops = (tokens * 2 * L * layer_matmul_params(sizes) + 2 * D * V
             + pairs * _attn_flops_per_pair(sizes))
    kv = kv_bytes_per_token(sizes)
    return Work(flops, weight_bytes(sizes) + (start + tokens) * kv)
