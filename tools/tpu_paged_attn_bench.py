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

``--pool latent``: the latent-attention kernel (``ops/latent_attention.py``)
against the ``jnp`` forms of ``llm/mla.py`` over the gathered window (absorbed;
for a chunk also expanded), at
the shapes of the latent cell's programs (``serve-saturated-2k.
a.x-k1-ep16-d7``: 64 heads, a pool of 10,241 pages of 16 rows of 640, rank
512, tables of 213 entries).  A tick: 64 lanes at 1,800-2,600 cached tokens.
A chunk: 512 rows at offsets 0, 1,024 and 1,792.  The floors are the live
latent's bytes and the absorbed products' flops (``2·h·(2·rank + rope)`` a
visible pair); ``kernel_us`` is the ``pallas_call`` without the two small
products around it.
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

from fedml_tpu.llm import mla
from fedml_tpu.llm import model as M
from fedml_tpu.ops import latent_attention as la
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


#: the latent cell: heads, rank, rope, nope, v, row, entries, pool pages
L_H, L_RANK, L_ROPE, L_NOPE, L_V, L_ROW = 64, 512, 64, 128, 128, 640
L_ENTRIES, L_PAGES, L_LANES, L_CHUNK = 213, 10241, 64, 512
L_TICK_DEPTHS = (1800, 2600)
L_CHUNK_OFFSETS = (0, 1024, 1792)
L_TICK_SWEEP = (8, 16, 32, 64)
L_CHUNK_SWEEP = ((8, 16), (16, 16), (32, 16), (8, 32), (16, 32), (32, 32),
                 (16, 64))


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def measure(emit, fn, args, reps, nbytes, flops, want=None, **fields):
    """One JSON line for ``fn(*args)``: microseconds a call against the two
    floors, and the widest gap to ``want``; returns the result in float32."""
    secs = timed(fn, args, reps)
    got = fn(*args).astype(jnp.float32)
    gap = None if want is None else float(jnp.max(jnp.abs(got - want)))
    emit(us=round(secs * 1e6, 1),
         bytes_floor_us=round(nbytes / HBM_BYTES_PER_S * 1e6, 1),
         flops_floor_us=round(flops / FLOPS_PER_S * 1e6, 1),
         hbm_share=round(nbytes / secs / HBM_BYTES_PER_S, 4),
         mxu_share=round(flops / secs / FLOPS_PER_S, 4),
         max_abs_gap=gap, **fields)
    return got


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


def latent(opts, emit):
    """The latent pool's read: the kernel against the two ``jnp`` forms."""
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    scale = (L_NOPE + L_ROPE) ** -0.5
    split = (L_RANK, L_NOPE)
    pool = jax.random.normal(keys[2], (L_PAGES, PTOK, L_ROW), bf)
    pool = pool.at[..., L_RANK + L_ROPE:].set(0)
    w_kvb = jax.random.normal(keys[3], (L_RANK, L_H, L_NOPE + L_V), bf) \
        * L_RANK ** -0.5
    shapes = {
        "tick": (1, [("cell", [int(d) for d in rng.integers(
            *L_TICK_DEPTHS, L_LANES)])], [(n, 0) for n in L_TICK_SWEEP]),
        "chunk": (L_CHUNK, [(f"offset_{o}", [o + L_CHUNK - 1])
                            for o in L_CHUNK_OFFSETS], L_CHUNK_SWEEP)}
    for shape, (s, loads, sweep) in shapes.items():
        if opts.only and shape != opts.only:
            continue
        for load, depths in loads:
            b = len(depths)
            q_nope = jax.random.normal(keys[0], (b, L_H, s, L_NOPE), bf)
            q_rope = jax.random.normal(keys[1], (b, L_H, s, L_ROPE), bf)
            tables = np.zeros((b, L_ENTRIES), np.int32)
            pos = np.zeros((b, s), np.int32)
            free = iter(rng.permutation(np.arange(1, L_PAGES)))
            for i, depth in enumerate(depths):
                pos[i] = depth - s + 1 + np.arange(s)
                for j in range(depth // PTOK + 1):
                    tables[i, j] = next(free)
            args = (q_nope, q_rope, pool, jnp.asarray(tables), w_kvb,
                    jnp.asarray(pos))
            nbytes = int((pos[:, -1] + 1).sum()) * L_ROW * 2
            flops = int((pos + 1).sum()) * 2 * L_H * (2 * L_RANK + L_ROPE)

            def report(impl, fn, want=None, **more):
                return measure(emit, fn, args, opts.reps, nbytes, flops, want,
                               pool="latent", shape=shape, load=load,
                               impl=impl, **more)

            def window(attend):
                return jax.jit(lambda qn, qr, pool, tables, w, pos: attend(
                    qn, qr, pool[tables].reshape(b, -1, L_ROW), w, pos,
                    scale, split))

            want = report("jnp_absorbed", window(mla.attend_absorbed))
            if shape == "chunk":    # a tick's 64 windows multiplied out to
                # 64 heads are 9 GB: no program runs that form there
                report("jnp_expanded", window(mla.attend_expanded), want)
            q = mla.absorb_query(q_nope, q_rope, w_kvb[..., :L_NOPE], L_ROW,
                                 bf)
            for npg, tile in sweep:
                def kernel(q, pool, tables, pos):
                    return la.latent_attention(
                        q, pool, tables, pos, rank=L_RANK, sm_scale=scale,
                        pages_per_step=npg, tile=tile)
                alone = timed(jax.jit(kernel), (q,) + args[2:4] + args[5:],
                              opts.reps)
                report("pallas", jax.jit(
                    lambda qn, qr, pool, tables, w, pos: mla.unabsorb(
                        kernel(mla.absorb_query(qn, qr, w[..., :L_NOPE],
                                                L_ROW, bf), pool, tables,
                               pos), w[..., L_NOPE:])), want,
                    kernel_us=round(alone * 1e6, 1), pages_per_step=npg,
                    q_tile=tile or None, pages=pa.visited_pages(
                        pos, np.ones(b, np.int32), window=0, ring=False,
                        entries=L_ENTRIES, ptok=PTOK, tile=tile))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--only", choices=("tick", "chunk"))
    ap.add_argument("--pool", choices=("kv", "latent"), default="kv")
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

    if opts.pool == "latent":
        return latent(opts, emit)
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
                    return measure(emit, fn, args, opts.reps, nbytes, flops,
                                   want, shape=shape, table=kind, load=load,
                                   impl=impl, **more)

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
