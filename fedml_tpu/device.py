"""Device discovery — parity with ``fedml.device.get_device`` (reference
``python/fedml/device/device.py:43``).

The reference maps processes→GPUs from YAML ``gpu_util`` specs
(``gpu_mapping_mpi.py`` etc.).  On TPU the runtime owns placement: jax
enumerates chips and the mesh (core/mesh.py) assigns work, so ``get_device``
just returns the default device (or CPU when ``using_gpu``-equivalent
``using_tpu`` is false) and the mapping YAMLs become mesh-shape args
(``mesh_client/mesh_data/mesh_model/mesh_seq``).

The platform is whatever jax picks (``JAX_PLATFORMS`` is the only way to
choose).  Nothing here probes, retries or falls back: a backend that cannot
be created raises at the caller (docs/ARCHITECTURE.md "Devices and
processes").
"""

from __future__ import annotations

import jax


def initialize_backend():
    """``jax.devices()`` — the error of a backend that cannot be created
    reaches the caller."""
    return jax.devices()


def require_chip_free(who: str) -> None:
    """One process for each chip: ``who`` is about to start children that
    create their own jax backend, so this process must not hold an
    accelerator — a child that needs the chip its parent holds fails or
    hangs.  A launcher either stays off jax or owns the chip and runs
    everything in-process; it never does both (docs/ARCHITECTURE.md
    "Devices and processes")."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() and \
            jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{who} starts child processes that need the accelerator, but "
            f"this process already holds the {jax.default_backend()} "
            f"backend; run the work in this process or launch from one "
            f"that has not touched jax")


def get_device(args=None):
    """Reference ``device/device.py:43`` maps processes→GPUs from YAML
    ``gpu_util`` specs; here the simulation engines own placement through
    the mesh, and only MULTI-PROCESS modes (cross-silo/cross-cloud workers
    sharing one host) need a per-rank pick: rank r gets local device
    ``r % n`` (round-robin, the reference's default mapping), overridable
    with an explicit ``args.device_map`` list of device indices."""
    prefer_host = args is not None and not bool(
        getattr(args, "using_tpu", getattr(args, "using_gpu", True)))
    if prefer_host:
        return jax.devices("cpu")[0]      # the user asked for the host
    devices = initialize_backend()
    if args is not None and len(devices) > 1:
        dev_map = getattr(args, "device_map", None)
        rank = int(getattr(args, "rank", 0) or 0)
        if dev_map:
            return devices[int(list(dev_map)[rank % len(list(dev_map))])
                           % len(devices)]
        multiproc = str(getattr(args, "training_type", "")) in (
            "cross_silo", "cross_cloud", "cross_device")
        if multiproc and rank > 0:
            return devices[rank % len(devices)]
    return devices[0]


def device_count() -> int:
    return len(initialize_backend())
