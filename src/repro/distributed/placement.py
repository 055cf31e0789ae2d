"""Per-replica JAX device placement (cluster scale-out).

Production runs one device (or mesh) per model replica; the gateway's
per-engine pump then overlaps *compute* across replicas, not just swap
DMA.  CI has no accelerator, so the fallback is
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set *before* jax
imports — see the tier job in ``.github/workflows/ci.yml``), which splits
the host into N real XLA devices: the multi-device code paths are
exercised, not mocked.

Placement is intentionally thin: commit the replica's parameters with
``device_put`` (:func:`place_params`).  The engine runs where its
parameters live — it allocates its KV pool and prefix store there and
runs warmup and every step with that device as JAX's default — so no
serve-time code needs to know about placement at all.
"""
from __future__ import annotations

from typing import List, Sequence, Union

import jax

DeviceSpec = Union[None, str, Sequence]


def device_label(dev) -> str:
    """Stable ``platform:id`` label (``cpu:0``) used for replica
    attribution in gauges, bench WARN rows, and ``--devices`` specs."""
    return f"{dev.platform}:{dev.id}"


def available_devices(spec: DeviceSpec = None) -> List:
    """Resolve a device spec to a list of JAX devices.

    ``None`` / ``"auto"``: every device.  ``"cpu"`` / ``"gpu"`` /
    ``"tpu"``: every device of that platform.  ``"cpu:0,cpu:2"`` or
    ``"0,2"``: explicit devices by label or flat ``jax.devices()`` index.
    A sequence of device objects passes through.
    """
    devs = jax.devices()
    if spec is None or spec in ("auto", ""):
        return list(devs)
    if not isinstance(spec, str):
        return list(spec)
    by_label = {device_label(d): d for d in devs}
    picked = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if part in by_label:
            picked.append(by_label[part])
        elif part.isdigit():
            if int(part) >= len(devs):
                raise ValueError(f"device index {part} out of range "
                                 f"(have {len(devs)} devices)")
            picked.append(devs[int(part)])
        else:
            plat = [d for d in devs if d.platform == part]
            if not plat:
                raise ValueError(f"no devices match {part!r} "
                                 f"(have {sorted(by_label)})")
            picked.extend(plat)
    if not picked:
        raise ValueError(f"device spec {spec!r} selected no devices")
    return picked


def assign_devices(n_replicas: int, spec: DeviceSpec = None) -> List:
    """Round-robin ``n_replicas`` over the resolved device list (replica
    ``i`` -> ``devices[i % len(devices)]``).  With one device the
    assignment degenerates to today's shared-device layout."""
    devs = available_devices(spec)
    return [devs[i % len(devs)] for i in range(n_replicas)]


def place_params(params, device):
    """Commit a parameter pytree to one device.  Jitted programs follow
    committed operands, so this single transfer pins the whole replica's
    compute (prefill, fused decode, swap quantization) to ``device``."""
    if device is None:
        return params
    return jax.device_put(params, device)
