"""Shares of a peak by arithmetic: work the run counted (tokens, rounds),
times what a unit of it requires (a function of ``rooflines/``), over the
window and the chip's peak from ``peaks.json``."""

from __future__ import annotations

import importlib
from typing import Optional


def read(args: dict, run) -> Optional[float]:
    """``per_unit``: ``<module>.<function>`` under ``rooflines/``, called with
    (configuration, cell) -> operations (or bytes) one unit requires;
    ``units``: names of the run's counters, summed; ``peak``: the key of
    ``peaks.json`` to divide by.  Returns percent of that peak over the
    measured window and the chips the cell uses."""
    module, func = args["per_unit"].rsplit(".", 1)
    per_unit = getattr(importlib.import_module(f"rooflines.{module}"), func)(
        run.cfg, run.cell)
    units = [run.counters.get(u) for u in args["units"]]
    if any(u is None for u in units) or not run.window_s:
        return None
    total = per_unit * sum(units)
    if total <= 0:
        return None
    chips = int(run.cell.get("chips", 1))
    return 100.0 * total / run.window_s / (run.peak[args["peak"]] * chips)
