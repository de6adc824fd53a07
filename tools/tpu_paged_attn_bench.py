"""The paged-attention kernel alone against the ``jnp`` walk it replaces on
the chip, at the shapes of the mixed serving cell's two paged programs
(``serve-mixed-12k.command-a-plus-ep8-d4``: 128 query and 8 kv heads of 128,
16-token pages, a full table of 946 entries over a pool of 14,401 pages and
a ring of 321 over 7,521, window 4,096).  A tick: 48 lanes, 12 at about
12.5k tokens and 36 at about 1.2k.  A chunk: 1,024 rows ending at depths 1k,
5k and 13k.  For each, microseconds a call against the two floors the
roofline knows: the live K/V bytes over 819 GB/s and the masked pairs' flops
over 197 TFLOP/s; and the widest gap between the two results.  The kernel
is swept over pages a step and query-tile sizes.  One JSON line a
measurement, on stdout and in ``chiprun_out/paged_attn_bench.jsonl``.  Fails
off the TPU.

    python tools/tpu_paged_attn_bench.py [--reps 20] [--only tick|chunk]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.llm import model as M
from fedml_tpu.ops import paged_attention as pa

HBM_BYTES_PER_S = 819e9         # TPU v5e (benchmarks/peaks.json)
FLOPS_PER_S = 197e12
G, REP, D, PTOK, WINDOW = 8, 16, 128, 16, 4096
#: table kind -> (entries, pool pages, window, ring)
TABLES = {"full": (946, 14401, 0, False), "ring": (321, 7521, WINDOW, True)}
#: pages a step (tick), and (pages a step, query tile) (chunk)
TICK_SWEEP = (8, 16, 32, 64)
CHUNK_SWEEP = ((16, 32), (32, 32), (64, 32), (16, 64), (32, 64))
#: a tick's lanes as (count, least depth, most), and where a chunk ends
TICK_LANES = ((12, 12000, 13000), (36, 1000, 1400))
CHUNK = 1024
CHUNK_ENDS = (1024, 5120, 13312)


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def lanes(kind, depths, s, rng):
    """Tables and positions of lanes whose last query stands at
    ``depths``: each lane's blocks on pages of its own."""
    entries, pages, window, ring = TABLES[kind]
    tables = np.zeros((len(depths), entries), np.int32)
    pos = np.zeros((len(depths), s), np.int32)
    free = iter(rng.permutation(np.arange(1, pages)))
    for i, depth in enumerate(depths):
        pos[i] = depth - s + 1 + np.arange(s)
        first = max(0, pos[i, 0] - window + 1) // PTOK if window else 0
        for j in range(first, depth // PTOK + 1):
            tables[i, j % entries if ring else j] = next(free)
    return jnp.asarray(tables), jnp.asarray(pos)


def floors(kind, pos):
    """(bytes, flops): live K/V a call has to read and the masked pairs'
    matrix flops (scores and weighted sum, every query head)."""
    window = TABLES[kind][2]
    pos = np.asarray(pos).astype(np.int64)
    seen = np.minimum(pos + 1, window) if window else pos + 1
    live = pos[:, -1] + 1 - (np.maximum(pos[:, 0] - window + 1, 0)
                             if window else 0)
    return (int(live.sum()) * 2 * G * D * 2,
            int(seen.sum()) * G * REP * D * 4)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--only", choices=("tick", "chunk"))
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()!r}")
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/paged_attn_bench.jsonl", "a")

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    scale = D ** -0.5
    tick_depths = [int(d) for n, lo, hi in TICK_LANES
                   for d in rng.integers(lo, hi, n)]
    shapes = {"tick": (len(tick_depths), 1, [("mixed", tick_depths)],
                       TICK_SWEEP),
              "chunk": (1, CHUNK, [(f"depth_{d}", [d - 1])
                                   for d in CHUNK_ENDS], CHUNK_SWEEP)}
    for shape, (b, s, loads, sweep) in shapes.items():
        if opts.only and shape != opts.only:
            continue
        q = jax.random.normal(keys[0], (b, G, REP, s, D), bf)
        for kind, (entries, pages, window, ring) in TABLES.items():
            pool_k = jax.random.normal(keys[1], (pages, PTOK, G, D), bf)
            pool_v = jax.random.normal(keys[2], (pages, PTOK, G, D), bf)
            for load, depths in loads:
                tables, pos = lanes(kind, depths, s, rng)
                args = (q, pool_k, pool_v, tables, pos)
                nbytes, flops = floors(kind, pos)

                def report(impl, fn, want=None, **more):
                    secs = timed(fn, args, opts.reps)
                    got = fn(*args).astype(jnp.float32)
                    gap = None if want is None else float(
                        jnp.max(jnp.abs(got - want)))
                    emit(shape=shape, table=kind, load=load, impl=impl,
                         us=round(secs * 1e6, 1),
                         bytes_floor_us=round(nbytes / HBM_BYTES_PER_S * 1e6, 1),
                         flops_floor_us=round(flops / FLOPS_PER_S * 1e6, 1),
                         hbm_share=round(nbytes / secs / HBM_BYTES_PER_S, 4),
                         mxu_share=round(flops / secs / FLOPS_PER_S, 4),
                         max_abs_gap=gap, **more)
                    return got

                want = report("jnp_walk", jax.jit(
                    lambda *a: M._walk_pages_jnp(
                        *a, window=window, ring=ring, sm_scale=scale,
                        dtype=bf)))
                for point in sweep:
                    npg, tile = (point, 0) if shape == "tick" else point
                    report("pallas", jax.jit(
                        lambda *a: pa.paged_attention(
                            *a, window=window, ring=ring, sm_scale=scale,
                            pages_per_step=npg, tile=tile)), want,
                        pages_per_step=npg, q_tile=tile or None,
                        pages=pa.visited_pages(
                            np.asarray(pos), np.ones(b, np.int32),
                            window=window, ring=ring, entries=entries,
                            ptok=PTOK, tile=tile))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
