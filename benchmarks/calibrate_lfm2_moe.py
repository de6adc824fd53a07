#!/usr/bin/env python3
"""The readings the limits of a ``serve_lfm2_moe`` cell were set from
(PERF.md gives them), as ``calibrate_cohere2_moe.py`` makes them for the mixed
cell.  Not part of a benchmark run: many seeds in one process on the chip.

    python3 benchmarks/calibrate_lfm2_moe.py <cell> --seeds 1,2,... --seconds 20 [--control 3] [--witness 0]
                                             [--fault 0] [--variant 0] [--out FILE]
    python3 benchmarks/calibrate_lfm2_moe.py <cell> --replay FILE

On every seed a window at the cell's own load, then the sampled requests
through the reference: every checked position's gap and routing margin, and
the share of a sparse layer's tokens at which the selection bias changed the
experts chosen (``bias_changed_share``, by layer, over the first sampled
request).  On the first ``--control`` seeds also the gap of the token that the
reference in int8 puts first at the same positions: the control that has to
fail a limit.  On the first ``--witness`` seeds the same for the reference in
bfloat16 (``witness_checks``, and ``differ_share``: the share of the served
and of the witness's tokens that are not the reference's best): plain
``jax.numpy`` in the configuration's own precision, so a program that reads
what the witness reads departs by its precision and not by a fault.  On the
first ``--fault`` seeds three more windows, each with one of ``FAULTS``
planted in the program; on the first ``--variant`` seeds one more for each of
``VARIANTS``, a sound program with one precision changed.  Every reading goes
through the cell's own limits and the comparison the harness makes of them
(``checks``, ``correct``; the control's as ``control_checks``,
``control_correct``): the program's has to read true, the control's false; a
fault that reads true is one the cell's ``correct`` does not see at this size
(``PERF.md`` section 2 says which).  ``--out`` gets one JSON line a seed with
the positions' numbers whole; ``--replay`` judges such a file again by the
cell's limits as they stand, without a chip (the two exact counts are not in
it and are left out).
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
from calibrate_cohere2_moe import judged, read_seed, replay  # noqa: E402


# -- the timed path broken underneath: each has to read ``correct`` false ---------

def _state_leaves(pool, fn):
    """``pool`` with ``fn`` over the leaves that are rows of state."""
    import jax
    return jax.tree_util.tree_map_with_path(
        lambda path, p: fn(p) if getattr(path[-1], "key", None) == "conv_state" else p, pool)


@contextlib.contextmanager
def state_not_handed_to_the_first_tick():
    """A request's final chunk leaves its slot's rows of state at zero: the
    first tick starts from nothing."""
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


@contextlib.contextmanager
def tick_does_not_write_state_back():
    """After a tick every row of state is what it was before it."""
    import jax.numpy as jnp
    from fedml_tpu.serving.batching import ContinuousBatchingEngine as Engine
    real = Engine._dispatch

    def dispatch(self, live):
        kept = []
        _state_leaves(self._pool, lambda p: kept.append(jnp.copy(p)) or p)
        real(self, live)
        rows = iter(kept)
        self._pool = _state_leaves(self._pool, lambda p: next(rows))

    Engine._dispatch = dispatch
    try:
        yield
    finally:
        Engine._dispatch = real


@contextlib.contextmanager
def bias_left_out_of_the_selection():
    """The experts are the ``k`` largest scores: the bias is read and dropped."""
    from fedml_tpu.llm import moe
    real = moe.route
    moe.route = lambda scores, top_k, n_group=1, topk_group=1, norm_topk=True, scale=1.0, bias=None: \
        real(scores, top_k, n_group, topk_group, norm_topk, scale)
    try:
        yield
    finally:
        moe.route = real


FAULTS = {"the final chunk's state not handed to the first tick": state_not_handed_to_the_first_tick,
          "a tick that does not write its lanes' rows back": tick_does_not_write_state_back,
          "the bias left out of the selection": bias_left_out_of_the_selection}


# -- a sound program with one precision changed: where a gap comes from ------------

@contextlib.contextmanager
def router_in_float32():
    """The router's product in true float32 (six bfloat16 passes on the chip,
    whose default rounds a float32 operand to bfloat16 in the matrix unit)."""
    import flax.linen as nn
    import jax
    real = nn.Dense.__call__

    def call(self, x):
        if self.name != "router":
            return real(self, x)
        with jax.default_matmul_precision("highest"):
            return real(self, x)

    nn.Dense.__call__ = call
    try:
        yield
    finally:
        nn.Dense.__call__ = real


VARIANTS = {"the router's product in float32": router_in_float32}


def differ_share(rows, key="gaps") -> float:
    """The share of all checked positions whose token is not the reference's best."""
    return float(np.mean(np.concatenate([r[key] for r in rows]) > 0))


def bias_changed_share(drv, cfg, seed, pick) -> list:
    """By sparse layer: the share of one request's positions at which the
    selection bias changed the experts chosen, by the reference."""
    import jax.numpy as jnp
    base, adapters = drv.reference_weights(cfg, seed, [pick["adapter"]])
    ids = pick["prompt_ids"] + pick["tokens"]
    moved = drv.ref.bias_changed(base, adapters.get(pick["adapter"]), jnp.asarray([ids], jnp.int32), cfg)
    return [float(x) for x in np.asarray(moved).mean(axis=(1, 2))]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--fault", type=int, default=0)
    ap.add_argument("--variant", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--replay", default="")
    opts = ap.parse_args()
    from drivers import serve_lfm2_moe as drv
    if opts.replay:     # no device is asked for
        return replay(drv, harness.load_json("workloads", f"{opts.cell}.json"), opts.replay)
    cell, cfg, _, peak = _open(opts.cell)
    length = int(cell["engine"]["buf_len"])
    for i, seed in enumerate(int(s) for s in opts.seeds.split(",")):
        t0 = time.perf_counter()
        quant = ("int8",) * (i < opts.control) + ("bfloat16",) * (i < opts.witness)
        result, records, picks, rows, reference_s = read_seed(drv, cell, cfg, peak, seed, opts.seconds, quant)
        counts = drv.answered(records, length)
        out = {"seed": seed, "metrics": {k: v[0] for k, v in result["metrics"].items()},
               "attempted": result["attempted"], "failed": result["failed"],
               **judged(drv, cell, rows, counts),
               "checked_tokens": sum(len(r["tokens"]) for r in picks), "reference_s": reference_s,
               "bias_changed_share": bias_changed_share(drv, cfg, seed, picks[0]) if picks else None,
               "seconds_all": time.perf_counter() - t0, "differ_share": differ_share(rows),
               **drv.readings(rows)}
        if "int8" in quant:
            control = judged(drv, cell, rows, counts, "control_gaps")
            out.update(control_checks=control["checks"], control_correct=control["correct"],
                       control_gap_over_all=float(max(r["control_gaps"].max() for r in rows)))
        if "bfloat16" in quant:
            out.update(witness_checks=judged(drv, cell, rows, counts, "witness_gaps")["checks"],
                       witness_differ_share=differ_share(rows, "witness_gaps"))
        emit(**out)
        if opts.out:
            os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
            with open(opts.out, "a") as f:
                f.write(json.dumps({"seed": seed, **{
                    k: np.concatenate([r[k] for r in rows]).astype(float).round(6).tolist()
                    for k in rows[0]}}).replace("Infinity", "1e30") + "\n")
        del rows
        gc.collect()
        for kind, changes in (("fault", FAULTS), ("variant", VARIANTS)):
            for name, change in changes.items() if i < getattr(opts, kind) else ():
                with change():
                    _, records, _, rows, _ = read_seed(drv, cell, cfg, peak, seed, opts.seconds, None)
                emit(seed=seed, **{kind: name}, **judged(drv, cell, rows, drv.answered(records, length)),
                     differ_share=differ_share(rows), **drv.readings(rows))
                del rows
                gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
