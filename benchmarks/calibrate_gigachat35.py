#!/usr/bin/env python3
"""The readings the limits of a ``serve_gigachat35`` cell were set from
(PERF.md gives them), as ``calibrate_lfm2_moe.py`` makes them for the
short-convolution cell.  Not part of a benchmark run: many seeds in one
process on the chip.

    python3 benchmarks/calibrate_gigachat35.py <cell> --seeds 1,2,... --seconds 20 [--control 1]
                                               [--witness 0] [--fault 0] [--reference 1]
                                               [--out FILE]
    python3 benchmarks/calibrate_gigachat35.py <cell> --replay FILE

On every seed a window at the cell's own load, the matrix state of sampled
slots against the rule fed the program's own projections (``state_gap``), then
the sampled requests through the reference: every checked position's gap and
routing margin.  On the first ``--control`` seeds also the gap of the token
that the reference in int8 puts first at the same positions: the control that
has to fail a limit; on the first ``--witness`` seeds the same for the
reference in bfloat16.  On the first ``--fault`` seeds one more window for each
of ``FAULTS``, the timed path broken underneath (``--fault 2,1``: the first
fault on two seeds, the second on one).  With ``--reference 0`` no window's
sample goes through the reference: each is judged by the state and the counts
alone (chip time).  Every reading goes through the cell's own limits and the
comparison the harness makes of them (``checks``, ``correct``; the control's
as ``control_checks``, ``control_correct``): the program's has to read true,
the control's and the faults' false.  ``--out`` gets one JSON line
a seed with the positions' numbers whole; ``--replay`` judges such a file
again by the cell's limits as they stand, without a chip.
"""

from __future__ import annotations

import argparse
import contextlib
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
from calibrate_cohere2_moe import judged, replay  # noqa: E402
from calibrate_lfm2_moe import differ_share  # noqa: E402


def _state_leaves(pool, fn):
    """``pool`` with ``fn`` over the leaves that are linear-attention rows of
    state."""
    import jax
    from fedml_tpu.llm.model import LINEAR_LEAVES
    return jax.tree_util.tree_map_with_path(
        lambda path, p: fn(p) if getattr(path[-1], "key", None) in LINEAR_LEAVES else p, pool)


# -- the timed path broken underneath: each has to read ``correct`` false ---------

@contextlib.contextmanager
def state_in_bfloat16():
    """The matrix state is kept in bfloat16 between steps and between calls
    (the rule still computes in float32 within a call)."""
    import jax.numpy as jnp
    from fedml_tpu.llm import linear_attention as la
    real = la.STATE_DTYPE
    la.STATE_DTYPE = jnp.bfloat16
    try:
        yield
    finally:
        la.STATE_DTYPE = real


@contextlib.contextmanager
def last_chunk_state_lost():
    """A request's final chunk leaves its slot's linear-attention rows at
    zero: the first tick starts from nothing."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine as Engine
    real = Engine._prefill_chunk

    def chunk(self, tracer, i, s, cs, final):
        out = real(self, tracer, i, s, cs, final)
        if final:
            self._pool = _state_leaves(self._pool, lambda p: p.at[i].set(0))
        return out

    Engine._prefill_chunk = chunk
    try:
        yield
    finally:
        Engine._prefill_chunk = real


FAULTS = {"the matrix state kept in bfloat16 between steps": state_in_bfloat16,
          "the last chunk's state lost on its way to the first tick": last_chunk_state_lost}


def read_seed(drv, cell, cfg, peak, seed, seconds, quant, reference=True):
    """One window on ``seed``, the matrix state of its sampled slots against
    the rule (``drv.read_state``), and its sample through the reference (none
    where ``reference`` is false: no gaps, the state and the counts only)."""
    run = harness.Run(cell, cfg, seed, seconds, peak)
    state = drv.setup(run)
    drv.window(state, run, seconds)
    result = drv.finish(state, run)
    held = drv.read_state(state, run)
    if not reference:
        return result, held, drv.free_program(state, run), [], [], 0.0
    records, picks, rows, reference_s = drv.forced_sample(state, run, quant)
    return result, held, records, picks, rows, reference_s


def _judged(drv, cell, rows, counts) -> dict:
    """``judged`` where the reference ran; the state and the counts alone
    where it did not."""
    if rows:
        return judged(drv, cell, rows, counts)
    checks = drv.held_to(counts, cell["check"]["limits"])
    return {"checks": checks, "correct": drv.passes(checks)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--fault", default="0")
    ap.add_argument("--reference", type=int, default=1)
    ap.add_argument("--out", default="")
    ap.add_argument("--replay", default="")
    opts = ap.parse_args()
    from drivers import serve_gigachat35 as drv
    if opts.replay:     # no device is asked for
        return replay(drv, harness.load_json("workloads", f"{opts.cell}.json"), opts.replay)
    cell, cfg, _, peak = _open(opts.cell)
    length = int(cell["engine"]["buf_len"])
    reference = bool(opts.reference)
    for i, seed in enumerate(int(s) for s in opts.seeds.split(",")):
        t0 = time.perf_counter()
        quant = ("int8",) * (i < opts.control) + ("bfloat16",) * (i < opts.witness)
        result, held, records, picks, rows, reference_s = read_seed(
            drv, cell, cfg, peak, seed, opts.seconds, quant, reference)
        counts = {**drv.answered(records, length), "state_gap": held["state_gap"]}
        out = {"seed": seed, "metrics": {k: v[0] for k, v in result["metrics"].items()},
               "attempted": result["attempted"], "failed": result["failed"],
               "cut_at_close": result["notes"].get("cut_at_close"),
               **{k: v for k, v in held.items() if k != "state_gap"},
               **_judged(drv, cell, rows, counts),
               "checked_tokens": sum(len(r["tokens"]) for r in picks), "reference_s": reference_s,
               "seconds_all": time.perf_counter() - t0, **drv.readings(rows)}
        if rows:
            out["differ_share"] = differ_share(rows)
        if "int8" in quant and rows:
            control = judged(drv, cell, rows, counts, "control_gaps")
            out.update(control_checks=control["checks"], control_correct=control["correct"])
        if "bfloat16" in quant and rows:
            out.update(witness_checks=judged(drv, cell, rows, counts, "witness_gaps")["checks"],
                       witness_differ_share=differ_share(rows, "witness_gaps"))
        emit(**out)
        if opts.out and rows:
            os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
            with open(opts.out, "a") as f:
                f.write(json.dumps({"seed": seed, **{
                    k: np.concatenate([r[k] for r in rows]).astype(float).round(6).tolist()
                    for k in rows[0]}}).replace("Infinity", "1e30") + "\n")
        del rows
        gc.collect()
        fault = [int(n) for n in str(opts.fault).split(",")]
        fault += fault[-1:] * (len(FAULTS) - len(fault))
        for (name, change), upto in zip(FAULTS.items(), fault):
            if i >= upto:
                continue
            with change():
                _, held, records, _, rows, _ = read_seed(drv, cell, cfg, peak, seed, opts.seconds, None,
                                                         reference)
            counts = {**drv.answered(records, length), "state_gap": held["state_gap"]}
            emit(seed=seed, fault=name, **{k: v for k, v in held.items() if k != "state_gap"},
                 **_judged(drv, cell, rows, counts), **drv.readings(rows),
                 **({"differ_share": differ_share(rows)} if rows else {}))
            del rows
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
