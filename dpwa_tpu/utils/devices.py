"""Device policy for examples and entry points.

JAX freezes its platform choice at first backend initialization.
:func:`ensure_devices` has two behaviours and neither hides an accelerator:

- default: use the platform JAX selected (the TPU on a TPU host) and raise
  when it has fewer than ``n`` devices;
- ``cpu``: the emulated host mesh of SURVEY.md §4, on request only
  (``--devices cpu``).  Launching with ``JAX_PLATFORMS=cpu
  XLA_FLAGS=--xla_force_host_platform_device_count=N`` gives the same mesh
  through the default behaviour; that is how the tests run.
"""

from __future__ import annotations

import os

_FORCE_FLAG = "xla_force_host_platform_device_count"


def ensure_devices(n: int, mode: str = "auto"):
    """Return ``n`` JAX devices, or raise saying how to get them.

    ``mode="cpu"`` asks for the emulated CPU mesh and must run before any
    backend exists (XLA reads ``XLA_FLAGS`` once per process); every other
    mode takes ``jax.devices()`` as JAX selected them."""
    import jax

    if mode == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if _FORCE_FLAG not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} --{_FORCE_FLAG}={n}".strip()
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    platform = devices[0].platform
    if len(devices) < n or (mode == "cpu" and platform != "cpu"):
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} ({platform}); for an "
            f"emulated mesh start python with JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--{_FORCE_FLAG}={n}, or pass --devices cpu before "
            "anything has used jax"
        )
    return devices[:n]
