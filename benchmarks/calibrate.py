#!/usr/bin/env python3
"""The readings the limits were set from (PERF.md gives them).  Not part of a benchmark run: each sub-command reads many seeds in one
process on the chip, because set-up is long.

    python3 benchmarks/calibrate.py fedround <cell> --seeds 1,2,... [--control 3] [--fault 3]
    python3 benchmarks/calibrate.py serve <cell> --seeds 1,2,... --seconds 20 [--control 3]

``fedround``: the program's first three rounds against the plain reference on
every seed (the lower readings); on the first ``--control`` seeds the reference
in int8 in the program's place, and on the first ``--fault`` seeds the
reference with half of every batch left out (the upper readings).
``serve``: a window at the cell's own load on every seed, the served tokens'
widest gap, and on the first ``--control`` seeds the gap of the token int8
puts first.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import harness  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


def _open(cell_name: str):
    cell = harness.load_json("workloads", f"{cell_name}.json")
    cfg = harness.load_json("configs", f"{cell['config']}.json")
    harness.place_compile_cache()
    devices, peak = harness.find_devices(int(cell.get("chips", 1)),
                                         harness.load_json("peaks.json"))
    return cell, cfg, devices, peak


def fedround(opts) -> None:
    import jax
    from drivers import fedround as drv
    cell, cfg, _, _ = _open(opts.cell)
    t = cell["traffic"]
    seeds = [int(s) for s in opts.seeds.split(",")]
    api = drv.build_api(cfg, t, seeds[0])
    got = {}
    for seed in seeds:
        t0 = time.perf_counter()
        drv._dataset_from_seed(api.dataset, t, cfg["vocab_size"], seed)
        api.seed = int(seed) % (2 ** 31 - 1)
        drv.install_weights(api, cfg, seed)
        got[seed] = drv.first_rounds(api, 3)
        emit(seed=seed, program_losses=got[seed]["losses"],
             program_s=time.perf_counter() - t0)
    del api
    gc.collect()
    jax.clear_caches()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        want = drv.follow(cfg, t, seed, got[seed]["staged"], 3)
        row = {"seed": seed, "reference_s": time.perf_counter() - t0,
               "reference_losses": want["losses"],
               "program": drv.compare(got[seed], want, 3),
               "program_2": drv.compare(got[seed], want, 2)}
        if i < opts.control:
            low = drv.follow(cfg, t, seed, got[seed]["staged"], 3, quant="int8")
            row["control_int8"] = drv.compare(low, want, 3)
            row["control_int8_2"] = drv.compare(low, want, 2)
        if i < opts.fault:
            half = drv.follow(cfg, t, seed, got[seed]["staged"], 3,
                              rows=int(t["batch"]) // 2)
            row["fault_half_batch"] = drv.compare(half, want, 3)
            row["fault_half_batch_2"] = drv.compare(half, want, 2)
        emit(**row)


def _serve_window(cell, cfg, peak, seed, seconds):
    from drivers import serve as drv
    run = harness.Run(cell, cfg, seed, seconds, peak)
    state = drv.setup(run)
    drv.window(state, run, seconds)
    return drv, run, state, drv.finish(state, run)


def serve(opts) -> None:
    import jax
    cell, cfg, _, peak = _open(opts.cell)
    seeds = [int(s) for s in opts.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        drv, run, state, result = _serve_window(cell, cfg, peak, seed, opts.seconds)
        records = state.pop("records")
        state.pop("client").close()
        state.pop("srv").stop()
        state.clear()
        gc.collect()
        jax.clear_caches()
        picks = drv.sample(records, int(cell["check"]["sample"]), seed)
        base, adapters = drv.reference_weights(cfg, seed, [r["adapter"] for r in picks])
        quant = "int8" if i < opts.control else None
        rows = [drv.forced(cfg, base, adapters, r, int(cell["engine"]["buf_len"]), quant)
                for r in picks]
        out = {"seed": seed, "metrics": {k: v[0] for k, v in result["metrics"].items()},
               "attempted": result["attempted"], "failed": result["failed"],
               "served_gap": max(r["served_gap"] for r in rows),
               "served_gaps": [r["served_gap"] for r in rows],
               "checked_tokens": sum(len(r["tokens"]) for r in picks),
               "seconds_all": time.perf_counter() - t0}
        if quant:
            out["control_gap"] = max(r["control_gap"] for r in rows)
            out["control_gaps"] = [r["control_gap"] for r in rows]
        emit(**out)
        del base, adapters
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    p = sub.add_parser("fedround")
    p.add_argument("cell")
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--fault", type=int, default=3)
    p = sub.add_parser("serve")
    p.add_argument("cell")
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--control", type=int, default=3)
    opts = ap.parse_args()
    {"fedround": fedround, "serve": serve}[opts.what](opts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
