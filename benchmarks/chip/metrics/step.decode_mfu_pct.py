"""Decode FLOPs in the traced window over (device time of the decode
programs x the chip's peak)."""
from benchmarks.chip.readers import decode_work


def read(rec):
    got = decode_work(rec)
    if got is None or rec.peak is None:
        return None
    work, dev_s = got
    return 100.0 * sum(w.flops for w in work) / (dev_s * rec.peak.bf16_flops)
