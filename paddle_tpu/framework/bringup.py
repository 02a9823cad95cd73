"""Backend bring-up.

JAX initialises in-process, once, on the first device touch, and an
initialisation error propagates to the caller: a chip belongs to one
process at a time, so nothing here starts a child to look at it first,
and nothing lands on the CPU because the chip was slow or missing.

- :func:`pallas_enabled` is the common backend gate of the Pallas kernels.
- :func:`force_cpu` pins the cpu platform for processes that must not
  take the chip (the test suite, spawned DataLoader workers).
"""
from __future__ import annotations

import os

#: Platform names that mean "a real TPU is on the other end".
TPU_PLATFORMS = ("tpu",)


def pallas_enabled() -> bool:
    """Common gate for custom Pallas kernels: not disabled by env, and the
    live backend is a TPU. Kernel-specific shape ceilings stack on top of
    this (flash_attention._pallas_ok, fused_embedding._eligible)."""
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS") == "1":
        return False
    import jax

    return jax.default_backend() in TPU_PLATFORMS


def backends_initialized() -> bool:
    """True once jax has committed to a set of live backends."""
    from jax._src import xla_bridge as xb

    return bool(xb._backends)


def force_cpu(n_devices: int | None = None) -> None:
    """Pin the cpu platform.

    ``n_devices`` requests that many virtual host devices
    (``--xla_force_host_platform_device_count``); it only takes effect
    when backends have not initialized yet."""
    if n_devices is not None and not backends_initialized():
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
