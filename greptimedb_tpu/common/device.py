"""The accelerator a node runs on: named at start-up, never assumed.

With `JAX_PLATFORMS` unset JAX falls back to the CPU when it finds no
usable TPU, and every query still answers — slowly, on the wrong
backend, with nothing saying so. Chip-owning roles call
`require_device()` first: it initialises the backend, returns what came
up, and refuses a silent CPU fallback.
"""

from __future__ import annotations

import os
from typing import Any, Dict


def device_info() -> Dict[str, Any]:
    """platform / device_kind / device_count as JAX reports them, plus
    bytes_in_use / peak_bytes_in_use where the backend keeps memory
    stats (the CPU backend does not). Initialises the backend."""
    import jax
    devs = jax.devices()
    info: Dict[str, Any] = {"platform": devs[0].platform,
                            "device_kind": devs[0].device_kind,
                            "device_count": len(devs)}
    stats = devs[0].memory_stats()
    if stats:
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            if key in stats:
                info[key] = int(stats[key])
    return info


def require_device() -> Dict[str, Any]:
    """device_info(), or SystemExit when the platform was left to JAX
    (`JAX_PLATFORMS` unset) and what came up is not the TPU."""
    info = device_info()
    if not os.environ.get("JAX_PLATFORMS") and info["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: JAX came up on platform={info['platform']!r} "
            f"({info['device_kind']}, {info['device_count']} device(s)) "
            "with JAX_PLATFORMS unset. Set JAX_PLATFORMS=cpu to run on "
            "the CPU on purpose.")
    return info
