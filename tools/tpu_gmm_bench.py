"""The grouped-matmul kernels alone against ``jax.lax.ragged_dot`` on the
chip, at the shapes of the latent serving cell's expert layer
(``bf16[512|4096, 7168] x bf16[12, 7168, 2048]`` and back): microseconds a
call and the share of the chip's memory bandwidth over the bytes of the
experts that were hit, for the calls ``swiglu`` makes (gate and up fused,
down, the two chained).  One JSON line a measurement, on stdout and in
``chiprun_out/gmm_bench.jsonl``.  Fails off the TPU.

    python tools/tpu_gmm_bench.py [--reps 30]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from fedml_tpu.ops import grouped_matmul as gm

HBM_BYTES_PER_S = 819e9         # TPU v5e (benchmarks/peaks.json)
D, F, HELD = 7168, 2048, 12
#: rows an expert got: a tick of the cell (170 pairs on 10 of 12 experts),
#: a prefill chunk (256 on 12) and a tick at the deployment's load
LOADS = {
    "tick": (512, [23, 17, 0, 21, 14, 19, 0, 16, 22, 12, 15, 11]),
    "chunk": (4096, [25, 18, 30, 12, 22, 28, 16, 24, 20, 19, 21, 21]),
    "tick_full": (512, [42] * 12),
}


def timed(call, args, reps):
    fn = jax.jit(call)
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=30)
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()!r}")
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/gmm_bench.jsonl", "a")

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    w_gate = jax.random.normal(keys[0], (HELD, D, F), bf) * D ** -0.5
    w_up = jax.random.normal(keys[1], (HELD, D, F), bf) * D ** -0.5
    w_down = jax.random.normal(keys[2], (HELD, F, D), bf) * F ** -0.5

    for load, (m, sizes) in LOADS.items():
        hit = sum(s > 0 for s in sizes)
        sizes = jnp.asarray(sizes, jnp.int32)
        rows = jax.random.normal(keys[3], (m, D), bf)
        act = jax.random.normal(keys[4], (m, F), bf)
        ragged = lambda a, w, s: jax.lax.ragged_dot(
            a, w, s, preferred_element_type=jnp.float32)
        calls = {   # name -> (reference, kernel, operands, matrices)
            "down": (ragged, gm.grouped_matmul, (act, w_down, sizes), 1),
            "gate_up": (lambda r, g, u, s: jax.nn.silu(ragged(r, g, s))
                        * ragged(r, u, s), gm.gated_matmul,
                        (rows, w_gate, w_up, sizes), 2),
            "swiglu": (gm.swiglu_ragged, gm.swiglu_pallas,
                       (rows, w_gate, w_up, w_down, sizes), 3),
        }
        for name, (ref, kernel, args, mats) in calls.items():
            bytes_hit = hit * D * F * 2 * mats

            def report(impl, secs, **more):
                emit(load=load, call=name, impl=impl, us=round(secs * 1e6, 1),
                     hbm_share_hit=round(bytes_hit / secs / HBM_BYTES_PER_S, 4),
                     experts_hit=hit, **more)

            report("ragged_dot", timed(ref, args, opts.reps))
            report("pallas", timed(kernel, args, opts.reps),
                   visited=int(gm.visited_tiles(sizes, m)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
