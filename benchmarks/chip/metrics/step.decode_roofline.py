"""Share of its roofline the fused decode step reaches in the traced
window: the least time of its work (the larger of FLOPs / peak and bytes /
bandwidth, as the cell's architecture module counts them: for the dense
decoder, the weights plus the KV of the real context lengths) over the
device time of the decode programs."""
from benchmarks.chip.readers import decode_roofline_pct


def read(rec):
    return decode_roofline_pct(rec)
