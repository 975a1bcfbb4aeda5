"""Production mesh factories.

Functions, not module-level constants — importing this module never
touches jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
import; everything else sees the real (single) CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Target: TPU v5e pod(s). 16x16 = 256 chips single-pod;
    (pod=2, 16, 16) = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_fl_mesh(*, clients: int = 16, model: int = 16,
                 multi_pod: bool = False):
    """Mesh for pod-scale federated runs: the "data" axis hosts FL clients
    (one client per slice), "model" is tensor-parallel within a client,
    and the "pod" axis carries HFL's hierarchy tier in multi-pod runs."""
    if multi_pod:
        return jax.make_mesh((2, clients, model), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
    return jax.make_mesh((clients, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def largest_divisor_at_most(n: int, k: int) -> int:
    """The largest divisor of `n` that is <= `k` (>= 1)."""
    k = max(1, min(k, n))
    while n % k:
        k -= 1
    return k


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples).

    Requested axis sizes are clamped to DIVISORS of the available device
    count, not just its magnitude: `min(data, n)` alone builds impossible
    factorizations at non-power-of-two device counts (6 devices, data=4
    -> a 4x1 mesh stranding two devices, or a make_mesh failure), so each
    axis takes the largest divisor of the remaining devices instead."""
    n = len(jax.devices())
    data = largest_divisor_at_most(n, data)
    model = largest_divisor_at_most(n // data, model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_client_mesh(devices: int = 0):
    """1-D ("data",) mesh for the mesh-sharded fused executor
    (DESIGN.md §11): the stacked CLIENT axis is partitioned over "data";
    there is no model axis (the paper CNN fits on any device — the scale
    problem is the client count). `devices` <= 0 uses every device;
    otherwise it must not exceed the available count (a silent clamp
    would change the sharding the caller validated client divisibility
    against)."""
    n = len(jax.devices())
    if devices <= 0:
        devices = n
    if devices > n:
        raise ValueError(
            f"mesh_devices={devices} exceeds the {n} available device(s) "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"before importing jax for a CPU testbed)")
    return jax.make_mesh((devices,), ("data",), axis_types=(AxisType.Auto,))
