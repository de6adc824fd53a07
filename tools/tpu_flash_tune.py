"""Sweep Pallas flash-attention block sizes on the TPU.

The fixed 512/512 tiles are not universally right.  This sweeps
(block_q, block_k) per shape, timing the Pallas forward and backward
against the blockwise baseline (to ``block_until_ready``), and prints one
JSON line whose ``table`` field is ready to paste into
``fedml_tpu/ops/attention.py::_TUNED_BLOCKS``.  Run it on the chip, as the
one process that holds it.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

# (batch, q_heads, kv_heads, seq, head_dim) — bench shape first, then the
# sweep shapes bench.py --attn exercises, then a 7B-ish GQA slice.
SHAPES = [
    (4, 16, 16, 1024, 64),
    (2, 16, 16, 2048, 64),
    (1, 16, 16, 4096, 64),
    (4, 8, 8, 1024, 128),
    (1, 8, 8, 4096, 128),
    (1, 32, 8, 2048, 128),
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


def main():
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import attention as A

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

        base_s = _time_chained(
            lambda x: A.blockwise_attention(x, kg, vg, True), q)
        rows = []
        for bq in BLOCKS:
            if bq > s:
                continue
            for bk in BLOCKS:
                if bk > s:
                    continue
                try:
                    fwd_s = _time_chained(
                        lambda x, bq=bq, bk=bk: A.flash_attention_fwd_pallas(
                            x, kg, vg, True, None, block_q=bq, block_k=bk), q)
                except Exception as e:  # noqa: BLE001 — record and move on
                    rows.append({"bq": bq, "bk": bk, "error": repr(e)[:120]})
                    continue
                rows.append({"bq": bq, "bk": bk, "fwd_s": round(fwd_s, 6),
                             "vs_blockwise": round(base_s / fwd_s, 3)})
        ok = [r for r in rows if "fwd_s" in r]
        best = min(ok, key=lambda r: r["fwd_s"]) if ok else None
        # backward timing at the best fwd tile (do chained through dq)
        bwd_s = None
        if best is not None:
            out, lse = A.flash_attention_fwd_pallas(
                q, kg, vg, True, None, block_q=best["bq"],
                block_k=best["bk"], return_lse=True)

            def bwd(do, bq=best["bq"], bk=best["bk"]):
                dq, _, _ = A.flash_attention_bwd_pallas(
                    q, kg, vg, out, lse, do, True, None,
                    block_q=bq, block_k=bk)
                return dq
            try:
                bwd_s = _time_chained(bwd, q)
            except Exception as e:  # noqa: BLE001
                bwd_s = repr(e)[:120]
        shape_key = f"b{b}_h{h}_kv{h_kv}_s{s}_d{d}"
        results.append({"shape": shape_key, "blockwise_s": round(base_s, 6),
                        "rows": rows, "best": best, "bwd_s_at_best": bwd_s})
        # autotune-or-fallback: only shapes where flash WINS get a table
        # entry; losers stay on the blockwise path (attention._use_pallas)
        if best is not None and best["vs_blockwise"] >= 1.0:
            table[(s, d)] = (best["bq"], best["bk"])
        print(f"[tune] {shape_key}: blockwise {base_s*1e3:.2f}ms "
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
