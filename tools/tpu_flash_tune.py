"""Sweep Pallas flash-attention block sizes on the TPU.

The fixed 512/512 tiles are not universally right.  This sweeps
(block_q, block_k) per shape and times, to ``block_until_ready``, what a
training step runs: the Pallas forward, the ``dq`` pass and the dK/dV pass
in one chain, against the gradient of the blockwise scan (its forward and
its backward).  The winner and the table entry are decided on that total;
the forward alone is timed beside it and the backward is the difference.
It prints one JSON line whose ``paste`` field is ready to paste into
``fedml_tpu/ops/attention.py::_TUNED_BLOCKS``.  Run it on the chip, as the
one process that holds it:

    python3 tools/tpu_flash_tune.py [shape index ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (batch, q_heads, kv_heads, seq, head_dim) — bench shape first, then the
# sweep shapes bench.py --attn exercises, a 7B-ish GQA slice, then the
# round of fedlora-round.mistral-7b-d12 (4 clients x batch 2, 32 q heads on
# 8 kv heads of 128)
SHAPES = [
    (4, 16, 16, 1024, 64),
    (2, 16, 16, 2048, 64),
    (1, 16, 16, 4096, 64),
    (4, 8, 8, 1024, 128),
    (1, 8, 8, 4096, 128),
    (1, 32, 8, 2048, 128),
    (8, 32, 8, 1024, 128),
]
BLOCKS = (256, 512, 1024)
REPS = 8


def _time_chained(fn, x0, reps=REPS, min_total_s=1.0):
    """Time reps-long jitted chains of fn, dispatched back-to-back n times
    (async dispatches pipeline in device program order; waiting on the last
    waits on them all), growing n until wall-clock >= min_total_s; returns
    s/call.  The table this feeds gates the autotune-or-fallback policy — a
    single short sample can crown a losing tile."""
    import jax

    f = jax.jit(lambda x: _chain(fn, x, reps))
    jax.block_until_ready(f(x0))  # compile
    n, total = 1, 0.0
    for _ in range(4):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = f(x0)
        jax.block_until_ready(out)
        total = time.perf_counter() - t0
        if total >= min_total_s:
            break
        per = max(total / n, 1e-6)
        n = min(int(min_total_s * 1.3 / per) + 1, 512)
    return total / (n * reps)


def _chain(fn, x, reps):
    import jax

    def body(c, _):
        return fn(c), ()
    out, _ = jax.lax.scan(body, x, None, length=reps)
    return out


def _fold(q, dq, dk, dv):
    """The chain's next ``q``: it depends on all three gradients, so no pass
    that computes one of them is dead code under ``jit``."""
    import jax.numpy as jnp

    tail = jnp.sum(dk.astype(jnp.float32)) + jnp.sum(dv.astype(jnp.float32))
    return (q + 1e-3 * dq + 1e-9 * tail).astype(q.dtype)


def flash_forward(k, v, bq, bk):
    """The Pallas forward alone at one tile, as a function of ``q``."""
    from fedml_tpu.ops import attention as A

    return lambda q: A.flash_attention_fwd_pallas(
        q, k, v, True, None, block_q=bq, block_k=bk)


def flash_step(k, v, g, bq, bk):
    """Forward, ``dq`` and dK/dV of the Pallas kernels at one tile, as a
    function of ``q`` (``g`` is the output's cotangent)."""
    from fedml_tpu.ops import attention as A

    def step(q):
        out, lse = A.flash_attention_fwd_pallas(
            q, k, v, True, None, block_q=bq, block_k=bk, return_lse=True)
        dq, dk, dv = A.flash_attention_bwd_pallas(
            q, k, v, out, lse, g, True, None, block_q=bq, block_k=bk)
        return _fold(q, dq, dk, dv)
    return step


def scan_forward(k, v):
    """The blockwise scan's forward alone, as a function of ``q``."""
    from fedml_tpu.ops import attention as A

    return lambda q: A.blockwise_attention(q, k, v, True)


def scan_step(k, v, g):
    """``jax.grad`` of the blockwise scan in ``q``, ``k`` and ``v``: its
    forward and its backward, as a function of ``q``."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import attention as A

    def loss(q, k, v):
        out = A.blockwise_attention(q, k, v, True)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    def step(q):
        return _fold(q, *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    return step


def choose(rows, base_total_s):
    """The tile with the least forward + backward time, and whether it
    beats the scan's forward + backward (only then does the shape get a
    table entry: losers stay on the blockwise path, ``_use_pallas``)."""
    ok = [r for r in rows if "total_s" in r]
    if not ok:
        return None, False
    best = min(ok, key=lambda r: r["total_s"])
    return best, base_total_s / best["total_s"] >= 1.0


def _timed(fwd, total, x):
    fwd_s = _time_chained(fwd, x)
    total_s = _time_chained(total, x)
    return {"fwd_s": round(fwd_s, 6), "bwd_s": round(total_s - fwd_s, 6),
            "total_s": round(total_s, 6)}


def main():
    import jax
    import jax.numpy as jnp

    # optional argv: indices into SHAPES (resumable sweep), e.g. "1 2 3"
    idxs = [int(a) for a in sys.argv[1:]] or list(range(len(SHAPES)))

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    results = []
    table = {}
    for (b, h, h_kv, s, d) in [SHAPES[i] for i in idxs]:
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
        # grouped KV consumed natively by both paths (no repeat needed)
        kg = jnp.asarray(rng.standard_normal((b, h_kv, s, d)), jnp.bfloat16)
        vg = jnp.asarray(rng.standard_normal((b, h_kv, s, d)), jnp.bfloat16)
        g = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)

        base = _timed(scan_forward(kg, vg), scan_step(kg, vg, g), q)
        rows = []
        for bq in BLOCKS:
            if bq > s:
                continue
            for bk in BLOCKS:
                if bk > s:
                    continue
                try:
                    row = _timed(flash_forward(kg, vg, bq, bk),
                                 flash_step(kg, vg, g, bq, bk), q)
                except Exception as e:  # noqa: BLE001 — record and move on
                    rows.append({"bq": bq, "bk": bk, "error": repr(e)[:120]})
                    continue
                row = {"bq": bq, "bk": bk, **row,
                       "vs_blockwise": round(base["total_s"] / row["total_s"], 3)}
                rows.append(row)
                print(f"[tune] b{b}_h{h}_kv{h_kv}_s{s}_d{d} {row}", flush=True)
        best, wins = choose(rows, base["total_s"])
        shape_key = f"b{b}_h{h}_kv{h_kv}_s{s}_d{d}"
        results.append({"shape": shape_key, "blockwise": base, "rows": rows,
                        "best": best})
        if wins:
            table[(s, d)] = (best["bq"], best["bk"])
        print(f"[tune] {shape_key}: blockwise fwd+bwd "
              f"{base['total_s']*1e3:.2f}ms (fwd {base['fwd_s']*1e3:.2f}) "
              f"best {best}", flush=True)

    # `paste` is literal _TUNED_BLOCKS entry lines (tuple keys/values),
    # i.e. actually ready to paste into fedml_tpu/ops/attention.py
    paste = "\n".join(f"    ({s}, {d}): ({bq}, {bk}),"
                      for (s, d), (bq, bk) in sorted(table.items()))
    print(json.dumps({
        "metric": "flash_block_tune",
        "value": len(table),
        "unit": "shapes_tuned",
        "vs_baseline": None,
        "device_kind": dev.device_kind,
        "paste": paste,
        "results": results,
    }))


if __name__ == "__main__":
    main()
