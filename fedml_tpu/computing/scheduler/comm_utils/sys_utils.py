"""Host/system introspection for agents (reference ``comm_utils/
sys_utils.py`` — GPU inventory via nvidia-smi, versions, env collection).
TPU-era: accelerator inventory from the PCI bus, cpu/mem from /proc.
"""

from __future__ import annotations

import os
import platform
import sys
from typing import Any, Dict, Tuple


def _accelerator_inventory() -> Tuple[str, int]:
    """Count this host's TPU chips WITHOUT creating a jax backend.

    An agent is a launcher: the jobs it starts are the processes that use
    the chips, and a chip belongs to one process at a time — so the agent
    itself never initializes jax's backend (docs/ARCHITECTURE.md "Devices
    and processes").  The chips are counted the way jax itself decides
    whether a TPU is there: PCI ids in sysfs.  A scan that finds none says
    nothing about other accelerators, or about a sysfs this process cannot
    see, so it reports ``"unknown"`` rather than "no accelerator"."""
    from jax._src import hardware_utils
    n_chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    return ("tpu", n_chips) if n_chips else ("unknown", 0)


def get_sys_runner_info() -> Dict[str, Any]:
    info: Dict[str, Any] = {
        "os": platform.system(),
        "kernel": platform.release(),
        "python": sys.version.split()[0],
        "cpu_count": os.cpu_count() or 1,
    }
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    info["mem_total_bytes"] = int(line.split()[1]) * 1024
                elif line.startswith("MemAvailable:"):
                    info["mem_available_bytes"] = int(line.split()[1]) * 1024
    except OSError:
        pass
    import jax          # the import creates no backend
    info["accelerator"], info["num_chips"] = _accelerator_inventory()
    info["jax_version"] = jax.__version__
    try:
        import fedml_tpu
        info["fedml_tpu_version"] = fedml_tpu.__version__
    except Exception:
        pass
    return info


def cpu_load_1min() -> float:
    try:
        return os.getloadavg()[0]
    except OSError:
        return 0.0


__all__ = ["get_sys_runner_info", "cpu_load_1min"]
