"""Pallas TPU kernels (validated in interpret mode on CPU)."""


def interpret_mode(platform: str) -> bool:
    """Whether Pallas kernels run interpreted on a device of ``platform``.

    The CPU has no Mosaic compiler, so kernels are interpreted there; on a
    TPU they compile.  No other platform has an implementation here, and
    it is refused rather than silently run one way or the other."""
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(f"no Pallas kernel mode for platform {platform!r} "
                     "(supported: 'tpu' compiled, 'cpu' interpreted)")
