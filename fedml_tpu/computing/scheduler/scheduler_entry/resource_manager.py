"""Resource inventory + matching (reference ``scheduler_entry/
resource_manager.py`` + GPU discovery in ``comm_utils/sys_utils.py`` via
nvidia-smi).  The TPU inventory comes from ``jax.devices()``; CPU/memory from
/proc — no external tooling.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .job_config import ComputingRequirements


@dataclass
class DeviceResource:
    """One schedulable device (an agent's host)."""

    device_id: int
    num_chips: int = 0          # accelerator chips (TPU/GPU)
    device_type: str = "CPU"    # "TPU" | "GPU" | "CPU"
    num_cpus: int = 1
    mem_bytes: int = 0
    tags: Dict[str, str] = field(default_factory=dict)
    chips_in_use: int = 0

    @property
    def chips_free(self) -> int:
        return max(0, self.num_chips - self.chips_in_use)


def local_inventory(device_id: int = 0) -> DeviceResource:
    """Inventory of this host, built from the same introspection the agents
    report (``comm_utils.sys_utils.get_sys_runner_info`` — the chips are
    counted there without creating a jax backend)."""
    from ..comm_utils.sys_utils import get_sys_runner_info
    info = get_sys_runner_info()
    return DeviceResource(
        device_id=device_id, num_chips=int(info["num_chips"]),
        device_type=str(info["accelerator"]).upper(),
        num_cpus=int(info.get("cpu_count", 1)),
        mem_bytes=int(info.get("mem_total_bytes", 0)))


class ResourcePool:
    """Registry of agent resources; greedy first-fit matcher (the reference
    delegates matching to its cloud backend — here it is explicit)."""

    def __init__(self):
        self._devices: Dict[int, DeviceResource] = {}

    def register(self, res: DeviceResource) -> None:
        self._devices[res.device_id] = res

    def unregister(self, device_id: int) -> None:
        self._devices.pop(device_id, None)

    def devices(self) -> List[DeviceResource]:
        return list(self._devices.values())

    def match(self, req: ComputingRequirements,
              num_workers: int = 1) -> Optional[List[DeviceResource]]:
        """Pick ``num_workers`` devices (across every registered host)
        satisfying the full ask — chips, CPUs, memory, and tag
        constraints — or None.  The reference delegates this multi-host
        matching to its cloud backend GPU catalog
        (``launch_manager.py:417``); here it is explicit over the agents'
        reported inventories."""
        want_type = req.device_type.upper()
        min_mem = int(req.minimum_memory_gb * (1 << 30))
        picked: List[DeviceResource] = []
        for res in sorted(self._devices.values(),
                          key=lambda r: -r.chips_free):
            if want_type and want_type != "CPU" and res.device_type != want_type:
                continue
            if res.chips_free < req.minimum_num_gpus:
                continue
            if res.num_cpus < req.minimum_num_cpus:
                continue
            if min_mem and res.mem_bytes < min_mem:
                continue
            if any(res.tags.get(k) != v for k, v in req.tags.items()):
                continue
            picked.append(res)
            if len(picked) == num_workers:
                break
        if len(picked) < num_workers:
            return None
        for res in picked:
            res.chips_in_use += req.minimum_num_gpus
        return picked

    def release(self, device_ids: List[int], chips_each: int) -> None:
        for did in device_ids:
            res = self._devices.get(did)
            if res is not None:
                res.chips_in_use = max(0, res.chips_in_use - chips_each)


__all__ = ["DeviceResource", "ResourcePool", "local_inventory"]
