"""Production mesh construction.

Functions, not module constants — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Production target: TPU v5e, 256 chips/pod.
  single pod: (16, 16)    ("data", "model")
  two pods:   (2, 16, 16) ("pod", "data", "model")
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

# The checkout root (src/repro/launch/ -> three levels up).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axis types. `devices` (optional) selects
    an explicit subset — needed when the mesh is smaller than the platform
    (multi-partition-per-device runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. A `JAX_COMPILATION_CACHE_DIR` set in the
    environment wins and is left to JAX; otherwise the cache lives at the
    fixed path `<checkout>/.jax_cache` (git-ignored). The path is part of
    the cache key, so it never depends on a temporary name, pid or time.
    Call it from `main()` only — importing a module never enables it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(num_devices: int | None = None, axis: str = "parts"):
    """1-D mesh over available (possibly forced-host) devices, for the
    PipeGCN SPMD backend and small-scale tests."""
    n = num_devices or len(jax.devices())
    return make_mesh((n,), (axis,))


def partition_layout(num_parts: int, parts_per_device: int = 1,
                     num_devices: int | None = None) -> tuple[int, int]:
    """Device→partition mapping for the decoupled SPMD path.

    Returns (n_devices, n_local) with num_parts = n_devices * n_local;
    partition p lives on device p // n_local (device-major, matching how a
    (P, ...) leading-axis array shards over a 1-D mesh). The partition
    count is a convergence/accuracy knob (paper Tab. 4 sweeps 2–16), so it
    must not be pinned to whatever hardware is present."""
    if parts_per_device < 1:
        raise ValueError(f"parts_per_device must be >= 1, got {parts_per_device}")
    if num_parts % parts_per_device:
        raise ValueError(
            f"num_parts={num_parts} is not a multiple of "
            f"parts_per_device={parts_per_device}")
    n_dev = num_parts // parts_per_device
    avail = num_devices if num_devices is not None else len(jax.devices())
    if n_dev > avail:
        raise ValueError(
            f"num_parts={num_parts} / parts_per_device={parts_per_device} "
            f"needs {n_dev} devices but only {avail} are available — raise "
            "parts_per_device")
    return n_dev, parts_per_device


def make_partition_mesh(num_parts: int, parts_per_device: int = 1,
                        axis: str = "parts"):
    """1-D mesh sized num_parts // parts_per_device over the first devices,
    for `PipeGCN.make_spmd_step` with any partitions-per-device ratio."""
    n_dev, _ = partition_layout(num_parts, parts_per_device)
    return make_mesh((n_dev,), (axis,), devices=jax.devices()[:n_dev])


def make_survivor_mesh(plan, axis: str = "parts"):
    """1-D mesh over an ElasticPlan's surviving devices.

    When the survivor ids address devices the platform still exposes
    (the drill case: a *logical* loss on healthy hardware), the mesh is
    built from exactly those devices — deterministic, so a mid-run
    recovery and a fresh launch on the survivors pick identical
    hardware. Otherwise (the device really is gone and the remainder
    renumbered) the first ``plan.n_devices`` available devices serve."""
    devs = jax.devices()
    if plan.survivors[-1] < len(devs):
        sel = [devs[i] for i in plan.survivors]
    else:
        sel = devs[:plan.n_devices]
    if len(sel) < plan.n_devices:
        raise ValueError(
            f"survivor mesh needs {plan.n_devices} devices but only "
            f"{len(devs)} are available")
    return make_mesh((plan.n_devices,), (axis,), devices=sel)


# Hardware constants for the roofline model (TPU v5e).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
HBM_BYTES = 16e9                # per chip
