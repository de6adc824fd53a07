#!/usr/bin/env python3
"""The readings the limits of a ``serve_mla_moe`` cell were set from (PERF.md
gives them), as ``calibrate.py serve`` makes them for the dense cells.  Not
part of a benchmark run: many seeds in one process on the chip.

    python3 benchmarks/calibrate_mla_moe.py <cell> --seeds 1,2,... --seconds 20 [--control 3] [--fault 0] [--out FILE]

On every seed a window at the cell's own load, then the sampled requests
through the reference: every checked position's gap and routing margin.  On
the first ``--control`` seeds also the gap of the token that the reference in
int8 puts first at the same positions: the control that has to fail a limit.
On the first ``--fault`` seeds a second window with a fault planted in the
program (the outputs of the first third of the held experts dropped), the upper
reading of ``served_gap``.  ``--out`` gets one JSON line a seed with the
positions' numbers whole.
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
    _, picks, rows, _ = drv.forced_sample(state, run, quant)
    return result, picks, rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", type=int, default=0)
    ap.add_argument("--out", default="")
    opts = ap.parse_args()
    import jax.numpy as jnp
    from drivers import serve_mla_moe as drv
    from fedml_tpu.llm import moe
    cell, cfg, _, peak = _open(opts.cell)
    margin = float(cell["check"]["near_tie_margin"])
    for i, seed in enumerate(int(s) for s in opts.seeds.split(",")):
        t0 = time.perf_counter()
        quant = "int8" if i < opts.control else None
        result, picks, rows = read_seed(drv, cell, cfg, peak, seed, opts.seconds, quant)
        out = {"seed": seed, "metrics": {k: v[0] for k, v in result["metrics"].items()},
               "attempted": result["attempted"], "failed": result["failed"],
               "at_the_cells_margin": drv.numbers(rows, margin),
               "checked_tokens": sum(len(r["tokens"]) for r in picks),
               "seconds_all": time.perf_counter() - t0, **drv.readings(rows)}
        if quant:
            out["control_at_the_cells_margin"] = drv.numbers(rows, margin, "control_gaps")
            out["control_gap_over_all"] = float(max(r["control_gaps"].max() for r in rows))
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
            first, count, _ = drv.weights.held(cfg)
            real = moe.expert_ffn
            moe.expert_ffn = lambda x, gates, experts, *rest: real(
                x, jnp.where(experts < first + max(count // 3, 1), 0.0, gates), experts, *rest)
            try:
                _, _, rows = read_seed(drv, cell, cfg, peak, seed, opts.seconds, None)
            finally:
                moe.expert_ffn = real
            emit(seed=seed, fault="outputs of the first third of the held experts dropped",
                 at_the_cells_margin=drv.numbers(rows, margin), **drv.readings(rows))
            del rows
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
