"""SPMD-layer helpers tied to the installed JAX.

``shard_map`` is ``jax.shard_map`` (kwarg ``check_vma``): the installed
JAX is the only one supported, so it is resolved directly.
``force_host_devices`` gives the CPU platform N virtual devices so the
multi-chip SPMD paths run without TPU hardware.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def force_host_devices(env=None, n: int = 8) -> None:
    """Ensure ``XLA_FLAGS`` forces an ``n``-virtual-device host
    platform, so multi-chip SPMD paths run without TPU hardware. Must
    run before the jax BACKEND initializes (importing jax is fine: the
    flag is read at client creation, not at import). Mutates ``env``
    in place (default ``os.environ``); a pre-existing
    ``xla_force_host_platform_device_count`` flag wins, so an
    operator's own device count is respected. The single copy of the
    idiom shared by tests/conftest.py and ``scripts/check_plans.py
    --bench``."""
    import os

    target = os.environ if env is None else env
    flags = target.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        target["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
