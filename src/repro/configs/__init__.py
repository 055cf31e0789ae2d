"""Architecture registry: the 10 assigned architectures + the paper's own models.

Each assigned architecture lives in its own module (``repro/configs/<id>.py``)
exposing ``CONFIG`` (full size) and ``smoke_config()`` (reduced, CPU-runnable).
"""
from __future__ import annotations

import importlib

from repro.models.config import ArchConfig, ShapeSpec, SHAPES, cell_is_supported

# assigned architecture id -> module name
_ASSIGNED = {
    "internvl2-2b": "internvl2_2b",
    "mamba2-2.7b": "mamba2_2p7b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "command-r-35b": "command_r_35b",
    "qwen1.5-32b": "qwen1p5_32b",
    "granite-3-8b": "granite_3_8b",
    "stablelm-3b": "stablelm_3b",
    "dbrx-132b": "dbrx_132b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "jamba-1.5-large-398b": "jamba_1p5_large_398b",
}

ASSIGNED_ARCHS = tuple(_ASSIGNED)


def get_config(name: str) -> ArchConfig:
    if name in _ASSIGNED:
        mod = importlib.import_module(f"repro.configs.{_ASSIGNED[name]}")
        return mod.CONFIG
    from repro.configs import paper_models
    if name in paper_models.CONFIGS:
        return paper_models.CONFIGS[name]
    raise KeyError(f"unknown architecture {name!r}; known: "
                   f"{sorted(list(_ASSIGNED) + list(paper_models.CONFIGS))}")


def get_smoke_config(name: str) -> ArchConfig:
    if name in _ASSIGNED:
        mod = importlib.import_module(f"repro.configs.{_ASSIGNED[name]}")
        return mod.smoke_config()
    return get_config(name).scaled(num_layers=2, d_model=64, num_heads=4,
                                   num_kv_heads=2, d_ff=128, vocab_size=256)


def get_chip_config(name: str) -> ArchConfig:
    """The published widths, cut in depth to what one chip holds with a
    real KV working set (the module's ``chip_config()``)."""
    mod = (importlib.import_module(f"repro.configs.{_ASSIGNED[name]}")
           if name in _ASSIGNED else None)
    if not hasattr(mod, "chip_config"):
        raise KeyError(f"{name!r} defines no one-chip config "
                       "(configs/<arch>.py: chip_config())")
    return mod.chip_config()


def all_cells():
    """Yield every (arch_name, shape_name, supported, reason) dry-run cell."""
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok, reason = cell_is_supported(cfg, shape)
            yield arch, sname, ok, reason


__all__ = ["ArchConfig", "ShapeSpec", "SHAPES", "ASSIGNED_ARCHS",
           "get_config", "get_smoke_config", "get_chip_config", "all_cells",
           "cell_is_supported"]
