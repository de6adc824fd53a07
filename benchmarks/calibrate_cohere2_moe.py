#!/usr/bin/env python3
"""The readings the limits of a ``serve_cohere2_moe`` cell were set from
(PERF.md gives them), as ``calibrate_mla_moe.py`` makes them for the latent
cell.  Not part of a benchmark run: many seeds in one process on the chip.

    python3 benchmarks/calibrate_cohere2_moe.py <cell> --seeds 1,2,... --seconds 20 [--control 3] [--fault 0] [--out FILE]
    python3 benchmarks/calibrate_cohere2_moe.py <cell> --replay FILE

On every seed a window at the cell's own load, then the sampled requests
through the reference: every checked position's gap and routing margin, by
class of length.  On the first ``--control`` seeds also the gap of the token
that the reference in int8 puts first at the same positions: the control that
has to fail a limit.  On the first ``--fault`` seeds a second window with a
fault planted in the program (the window mask dropped in layer 1, which keeps
its rotary embedding), the upper reading of ``served_gap``.  Every reading
goes through the cell's own limits and the comparison the harness makes of
them (``checks``, ``correct``; the control's as ``control_checks``,
``control_correct``): the program's has to read true, the control's and the
fault's false.  ``--out`` gets one JSON line a seed with the positions' numbers
whole; ``--replay`` judges such a file again by the cell's limits as they
stand, without a chip (the two exact counts are not in it and are left out).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import harness  # noqa: E402
from calibrate import _open, emit  # noqa: E402


def read_seed(drv, cell, cfg, peak, seed, seconds, quant):
    """One window on ``seed`` and its sample through the reference."""
    run = harness.Run(cell, cfg, seed, seconds, peak)
    state = drv.setup(run)
    drv.window(state, run, seconds)
    result = drv.finish(state, run)
    records, picks, rows, reference_s = drv.forced_sample(state, run, quant)
    return result, records, picks, rows, reference_s


def judged(drv, cell, rows, counts, key="gaps") -> dict:
    """``rows`` through the cell's limits, as ``check`` and the harness do it."""
    checks = drv.held_to({**drv.numbers(rows, float(cell["check"]["near_tie_margin"]), key), **counts},
                         cell["check"]["limits"])
    return {"checks": checks, "correct": drv.passes(checks)}


def replay(drv, cell, path: str) -> int:
    with open(path) as f:
        for line in f:
            kept = json.loads(line)
            rows = [{k: np.where(np.asarray(v) >= 1e30, np.inf, np.asarray(v))
                     for k, v in kept.items() if k != "seed"}]
            out = {"seed": kept["seed"], **judged(drv, cell, rows, {})}
            if "control_gaps" in rows[0]:
                control = judged(drv, cell, rows, {}, "control_gaps")
                out.update(control_checks=control["checks"], control_correct=control["correct"])
            emit(**out)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--replay", default="")
    opts = ap.parse_args()
    from drivers import serve_cohere2_moe as drv
    if opts.replay:     # no device is asked for
        return replay(drv, harness.load_json("workloads", f"{opts.cell}.json"), opts.replay)
    cell, cfg, _, peak = _open(opts.cell)
    from fedml_tpu.llm.model import LlamaConfig
    margin = float(cell["check"]["near_tie_margin"])
    length = int(cell["engine"]["buf_len"])
    for i, seed in enumerate(int(s) for s in opts.seeds.split(",")):
        t0 = time.perf_counter()
        quant = "int8" if i < opts.control else None
        result, records, picks, rows, reference_s = read_seed(drv, cell, cfg, peak, seed, opts.seconds, quant)
        counts = drv.answered(records, length)
        out = {"seed": seed, "metrics": {k: v[0] for k, v in result["metrics"].items()},
               "attempted": result["attempted"], "failed": result["failed"],
               **judged(drv, cell, rows, counts),
               "by_class": {c: drv.numbers([r for r, p in zip(rows, picks) if p["class"] == c], margin)
                            for c in sorted(set(p["class"] for p in picks))},
               "checked_tokens": sum(len(r["tokens"]) for r in picks), "reference_s": reference_s,
               "seconds_all": time.perf_counter() - t0, **drv.readings(rows)}
        if quant:
            control = judged(drv, cell, rows, counts, "control_gaps")
            out.update(control_checks=control["checks"], control_correct=control["correct"],
                       control_gap_over_all=float(max(r["control_gaps"].max() for r in rows)))
        emit(**out)
        if opts.out:
            os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
            with open(opts.out, "a") as f:
                f.write(json.dumps({"seed": seed, **{
                    k: np.concatenate([r[k] for r in rows]).astype(float).round(6).tolist()
                    for k in rows[0]}}).replace("Infinity", "1e30") + "\n")
        del rows
        gc.collect()
        if i < opts.fault:
            window, rope = LlamaConfig.layer_window, LlamaConfig.layer_rope
            LlamaConfig.layer_window = lambda self, j: 0 if j == 1 else window(self, j)
            LlamaConfig.layer_rope = lambda self, j: j == 1 or rope(self, j)
            try:
                _, records, _, rows, _ = read_seed(drv, cell, cfg, peak, seed, opts.seconds, None)
            finally:
                LlamaConfig.layer_window, LlamaConfig.layer_rope = window, rope
            emit(seed=seed, fault="the window mask dropped in layer 1",
                 **judged(drv, cell, rows, drv.answered(records, length)), **drv.readings(rows))
            del rows
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
