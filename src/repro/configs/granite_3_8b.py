"""granite-3-8b — dense GQA decoder.

Source: hf:ibm-granite/granite-3.0-8b-base (config.json).
40L d_model=4096 32H (GQA kv=8) d_ff=12800 vocab=49155, tied embeddings,
RoPE theta 10000.  Not modelled: the checkpoint's muP multipliers
(embedding x12, attention x1/128, residual x0.22, logits /16) — the serve
path runs random weights, and none of them changes a shape or a FLOP.
"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="granite-3-8b",
    family="dense",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=12800,
    vocab_size=49155,
    norm_type="rmsnorm",
    act="swiglu",
    tie_embeddings=True,
)


def smoke_config() -> ArchConfig:
    return CONFIG.scaled(num_layers=2, d_model=64, num_heads=8, num_kv_heads=2,
                         d_ff=128, vocab_size=512)


# One TPU v5e (16 GiB HBM) serving the published widths.
#
#   source:     hf:ibm-granite/granite-3.0-8b-base, config.json
#   reduced:    num_layers 40 -> 24.  Every width is as published (hidden
#               4096, 32 query / 8 KV heads of 128, FFN 12800, vocab 49155).
#   deployment: a 40-layer model split by depth over two chips as pipeline
#               stages; this chip holds the first 24 layers and the (tied)
#               embedding.
#   why 24:     weights are 199.2M parameters a layer (398 MB bf16) plus a
#               403 MB embedding, so 24 layers are 9.97 GB.  The paged KV
#               pool for 16 decode lanes x 2048 tokens is 24 x 2 x 8 x 128 x
#               2 B = 96 KiB a token, 3.22 GB.  compiled.memory_analysis()
#               of the fused decode step and of a 1024-token prefill chunk,
#               compiled for v5e, gives 13.22 GB and 13.46 GB at peak
#               (tests/test_chip_compile.py: test_chip_config_fits_one_v5e
#               asserts 1.5 GiB of the 17.18 GB stays free for the runtime,
#               the swap staging and the sampler).  28 layers would need
#               15.6 GB before any temporaries.
CHIP_LAYERS = 24


def chip_config() -> ArchConfig:
    return CONFIG.scaled(num_layers=CHIP_LAYERS)
