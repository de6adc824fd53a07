"""Billion-parameter FedLLM execution probe.

Runs REAL federated LoRA rounds through the shipped ``FedLLMAPI`` on a
>=1B-parameter Llama config (bf16 base, fp32 adapters), measuring:

- wall-clock per federated round + tokens/sec and, on a device
  ``bench.PEAK_FLOPS`` knows, analytic MFU with LoRA-aware FLOPs
  ((4*N + 6*r)*T over the nominal peak; an unknown accelerator is an
  error, a CPU run reports no utilization);
- live array bytes (``jax.live_arrays``) vs the closed-form prediction in
  ``core/memory_estimate.py`` — the estimator must be an UPPER bound that
  is not wildly loose (checked: actual <= estimate <= 4x actual).

Default config ~1.08B params (dim 2048, 20 layers, GQA 16q/8kv, ffn 5632,
vocab 32000).  Runs on the platform jax gives it.  ``--dim``/``--layers``/...
override; ``--fast`` is a CI-scale smoke (~120M params).  ``--mesh N`` lays
the same config over N of this process's devices — real chips on a multi-chip
host; for a CPU rehearsal give the process N virtual ones
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``).

Usage: python tools/llm_scale_run.py [--rounds 2] [--seq 256] [--fast]
       python tools/llm_scale_run.py --layer7b   # true-7B per-layer bench
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _mfu(dev, flops: float, seconds: float) -> dict:
    """Utilization is a device metric: reported against the nominal peak of
    a device ``bench.PEAK_FLOPS`` knows (an unknown accelerator raises
    there); a CPU run reports none."""
    if dev.platform == "cpu":
        return {}
    from bench import _peak_flops
    return {"mfu": round(flops / seconds / _peak_flops(dev), 4)}


def layer7b_bench(args_cli):
    """One Llama-2-7B transformer layer (true 7B dims), LoRA step: measures
    the per-layer cost a 7B fine-tune pays 32x per step.  Fits one v5e chip
    (layer params 202M bf16 = 0.4 GiB) where the full 7B (13.5 GiB weights
    + activations) does not leave room for benching."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import fedml_tpu  # noqa: F401  (backend + compile-cache setup)
    from fedml_tpu.llm.model import Block, LlamaConfig

    cfg = LlamaConfig(vocab_size=32000, dim=4096, n_layers=1, n_heads=32,
                      n_kv_heads=32, ffn_dim=11008,
                      max_seq_len=args_cli.seq, dtype=jnp.bfloat16,
                      lora_rank=args_cli.lora_rank)
    batch, seq = 1, args_cli.seq
    block = Block(cfg)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, seq, cfg.dim), jnp.bfloat16)
    positions = jnp.arange(seq)
    variables = block.init(key, x, positions)
    params, lora = variables["params"], variables.get("lora", {})
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    n_lora = sum(int(np.prod(p.shape))
                 for p in jax.tree_util.tree_leaves(lora))
    tx = optax.sgd(1e-3)
    opt = tx.init(lora)

    # params ride as a jit ARGUMENT: closing over the 0.4 GiB weight tree
    # would inline it into the HLO as constants
    def loss_fn(lora, params, x):
        out = block.apply({"params": params, "lora": lora}, x, positions)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    @jax.jit
    def step(lora, opt, params, x):
        loss, g = jax.value_and_grad(loss_fn)(lora, params, x)
        upd, opt = tx.update(g, opt)
        return optax.apply_updates(lora, upd), opt, loss

    from bench import _readback, _timed_chain, measure_rtt
    state = [step(lora, opt, params, x)]
    _readback(state[0][2])
    rtt = measure_rtt()

    def run_n(k):
        lo, op, _ = state[0]
        for _ in range(k):
            lo, op, loss = step(lo, op, params, x)
        state[0] = (lo, op, loss)

    dt = _timed_chain(run_n, lambda: _readback(state[0][2]), n0=5, rtt=rtt)
    dev = jax.devices()[0]
    tokens = batch * seq
    flops = (4.0 * n_params + 6.0 * n_lora) * tokens
    result = {
        "metric": "llama7b_layer_step",
        "value": round(dt, 5),
        "unit": "s/layer-step",
        "vs_baseline": None,
        "n_layer_params": n_params,
        "n_lora_params": n_lora,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        **_mfu(dev, flops, dt),
        "tokens_per_sec_layer": round(tokens / dt, 1),
        "extrapolated_32layer_stack_step_s": round(dt * 32, 3),
        "extrapolated_32layer_stack_tokens_per_sec": round(
            tokens / (dt * 32), 1),
        "note": ("transformer stack only: tok_embed + lm_head "
                 "(2 x 32000 x 4096 = 262M params, ~1.3 layer-equivalents "
                 "of matmul for the head) are excluded from the x32 "
                 "extrapolation"),
        "config": {"dim": 4096, "ffn": 11008, "heads": 32, "seq": seq,
                   "batch": batch, "lora_rank": args_cli.lora_rank,
                   "dtype": "bfloat16"},
    }
    print(json.dumps(result))
    with open(os.path.join(REPO, "LLM_7B_LAYER.json"), "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=20)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--ffn", type=int, default=5632)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--clients-per-round", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--lora-rank", type=int, default=16)
    ap.add_argument("--xent-chunk", type=int, default=8192,
                    help="vocab chunk for the streaming fused cross-entropy "
                         "(ops/xent.py); 0 = dense logits path")
    ap.add_argument("--remat", default="full",
                    choices=("full", "dots", "none"),
                    help="block recompute policy (llm.model.LlamaConfig."
                         "remat); the memory estimate prices the same "
                         "policy, so the upper-bound check stays valid")
    ap.add_argument("--fast", action="store_true",
                    help="~120M-param smoke for CI")
    ap.add_argument("--dump-live", action="store_true",
                    help="print every live jax array (shape/dtype/bytes) "
                         "grouped by size — estimator calibration aid")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run the SAME config through the GSPMD mesh "
                         "regime on N of this process's devices (client x "
                         "model = N/2 x 2): base params laid out by the "
                         "TP/FSDP rules, cohort sharded over the client "
                         "axis")
    ap.add_argument("--layer7b", action="store_true",
                    help="single-layer microbench at Llama-2-7B dims "
                         "(dim 4096, ffn 11008, 32q/32kv heads): per-layer "
                         "fwd+bwd step time and MFU, extrapolated x32 — "
                         "the 7B per-layer evidence one 16GiB chip allows")
    args_cli = ap.parse_args()
    if args_cli.mesh:
        if args_cli.mesh < 2 or args_cli.mesh % 2:
            ap.error(f"--mesh {args_cli.mesh}: must be an even count >= 2 "
                     "(mesh layout is client x model with model=2)")
    if args_cli.layer7b:
        return layer7b_bench(args_cli)
    if args_cli.fast:
        args_cli.dim, args_cli.layers, args_cli.ffn, args_cli.vocab = \
            512, 8, 1408, 16000
        args_cli.seq, args_cli.rounds = 128, 1

    import numpy as np
    import jax

    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod
    from fedml_tpu.llm.fedllm import FedLLMAPI
    from fedml_tpu.core.memory_estimate import (FedLLMLayout,
                                                estimate_fedllm_memory)

    args = load_arguments()
    args.update(
        dataset="shakespeare", train_size=args_cli.clients_per_round * 64,
        test_size=32, seq_len=args_cli.seq, model="llama",
        llm_dim=args_cli.dim, llm_n_layers=args_cli.layers,
        llm_n_heads=args_cli.heads, llm_n_kv_heads=args_cli.kv_heads,
        llm_ffn_dim=args_cli.ffn, llm_max_seq_len=args_cli.seq,
        client_num_in_total=max(4, args_cli.clients_per_round),
        client_num_per_round=args_cli.clients_per_round,
        comm_round=args_cli.rounds, batch_size=1,
        llm_max_local_steps=args_cli.local_steps,
        lora_rank=args_cli.lora_rank, learning_rate=1e-4, random_seed=0,
        streaming_xent_chunk=args_cli.xent_chunk,
        llm_remat=args_cli.remat,
    )
    args = fedml_tpu.init(args, should_init_logs=False)
    # the LM loader caps vocab at the spec; force the big-vocab synthetic
    args.update(dataset="stackoverflow_nwp")
    dataset, vocab = data_mod.load(args)
    # overwrite vocab to the requested size (tokens stay in range: the
    # synthetic generator draws < spec vocab; clip for safety)
    dataset.train_x = np.minimum(dataset.train_x, args_cli.vocab - 1)
    dataset.train_y = np.minimum(dataset.train_y, args_cli.vocab - 1)
    dataset.test_x = np.minimum(dataset.test_x, args_cli.vocab - 1)
    dataset.test_y = np.minimum(dataset.test_y, args_cli.vocab - 1)
    dataset.num_classes = args_cli.vocab

    mesh = None
    if args_cli.mesh:
        from fedml_tpu.core.mesh import make_mesh
        n_model = 2
        mesh = make_mesh(client=args_cli.mesh // n_model, model=n_model)
        print(f"# mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"over {args_cli.mesh} {jax.devices()[0].platform} devices",
              file=sys.stderr, flush=True)

    t0 = time.time()
    api = FedLLMAPI(args, dataset, mesh=mesh)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(api.base_params))
    n_lora = sum(int(np.prod(p.shape))
                 for p in jax.tree_util.tree_leaves(api.global_lora))
    init_s = time.time() - t0
    print(f"# init: {n_params / 1e9:.3f}B base params, {n_lora / 1e6:.2f}M "
          f"adapter params, {init_s:.1f}s", file=sys.stderr, flush=True)

    # -- run rounds (first includes compile) -------------------------------
    t0 = time.time()
    m0 = api.train_one_round(0)
    jax.tree_util.tree_map(
        lambda a: np.asarray(a) if hasattr(a, "shape") else a, m0)
    compile_round_s = time.time() - t0
    timed = []
    for r in range(1, args_cli.rounds):
        t0 = time.time()
        m = api.train_one_round(r)
        loss = float(np.asarray(m["train_loss"]))
        timed.append(time.time() - t0)
    round_s = min(timed) if timed else compile_round_s
    tokens_per_round = (args_cli.clients_per_round * args_cli.local_steps
                        * 1 * args_cli.seq)
    # LoRA step FLOPs: frozen base = fwd + activation-grad matmuls only
    # (4NT); adapters pay the full 6T/param (see bench.py rationale)
    flops_per_round = (4.0 * n_params + 6.0 * n_lora) * tokens_per_round

    # -- live memory vs estimator ------------------------------------------
    # logical bytes count each sharded array once; PER-CHIP PHYSICAL bytes
    # (sum of addressable shard buffers per device — replicated terms cost
    # every replica) are what a real pod chip must hold, so the estimator
    # is judged against the max-loaded device, not the logical total
    from collections import Counter
    live = 0
    per_dev = Counter()
    for a in jax.live_arrays():
        live += a.nbytes
        try:
            for s in a.addressable_shards:
                per_dev[s.device.id] += int(
                    np.prod(s.data.shape)) * s.data.dtype.itemsize
        except Exception:                       # committed host/token arrays
            per_dev[0] += a.nbytes
    live_per_chip = max(per_dev.values()) if per_dev else live
    if args_cli.dump_live:
        groups = Counter()
        for a in jax.live_arrays():
            groups[(str(a.dtype), tuple(a.shape))] += a.nbytes
        for (dt, shp), nb in sorted(groups.items(), key=lambda kv: -kv[1]):
            print(f"# live {nb / 2**20:9.2f} MiB  {dt:10s} {shp}",
                  file=sys.stderr, flush=True)
        print("# per-device MiB: " + str(
            {d: round(v / 2**20, 1) for d, v in sorted(per_dev.items())}),
            file=sys.stderr, flush=True)
    layout = FedLLMLayout(
        n_params=n_params, n_lora_params=n_lora,
        n_clients=args_cli.clients_per_round,
        n_chips=max(args_cli.mesh, 1),
        model_shards=2 if args_cli.mesh else 1,
        batch_per_client=1, seq_len=args_cli.seq, dim=args_cli.dim,
        n_layers=args_cli.layers, remat=args_cli.remat,
        ffn_dim=args_cli.ffn,
        kv_dim=args_cli.kv_heads * (args_cli.dim // args_cli.heads))
    est = estimate_fedllm_memory(layout)

    dev = jax.devices()[0]

    result = {
        "metric": "fedllm_round_wall_clock",
        "value": round(round_s, 3),
        "unit": "s/round",
        "vs_baseline": None,
        "n_params": n_params,
        "n_params_b": round(n_params / 1e9, 3),
        "n_lora_params": n_lora,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "tokens_per_sec": round(tokens_per_round / round_s, 1),
        **_mfu(dev, flops_per_round, round_s),
        "compile_round_s": round(compile_round_s, 1),
        "init_s": round(init_s, 1),
        "train_loss": loss if timed else float(np.asarray(m0["train_loss"])),
        "live_bytes_gib": round(live / 2 ** 30, 3),
        "live_per_chip_gib": round(live_per_chip / 2 ** 30, 3),
        # per-chip estimate vs the max-loaded device's PHYSICAL bytes —
        # apples-to-apples: both count replicated terms per replica, so
        # the tightness here is the margin a real pod scheduler would see
        "estimator_gib": round(est["total_gib"], 3),
        "estimator_is_upper_bound": bool(est["total"] >= live_per_chip),
        "estimator_tightness": round(
            est["total"] / max(live_per_chip, 1), 2),
        "mesh": (dict(zip(mesh.axis_names,
                          [int(s) for s in mesh.devices.shape]))
                 if mesh is not None else None),
        "config": {"dim": args_cli.dim, "layers": args_cli.layers,
                   "heads": args_cli.heads, "kv_heads": args_cli.kv_heads,
                   "ffn": args_cli.ffn, "vocab": args_cli.vocab,
                   "seq": args_cli.seq, "lora_rank": args_cli.lora_rank,
                   "clients_per_round": args_cli.clients_per_round,
                   "local_steps": args_cli.local_steps, "dtype": "bfloat16",
                   "streaming_xent_chunk": args_cli.xent_chunk},
    }
    print(json.dumps(result))
    # per-mode artifacts: a --fast smoke or a mesh run must never
    # overwrite the flagship default-config artifact
    name = "LLM_SCALE_RUN"
    if args_cli.fast:
        name = "LLM_SCALE_FAST"
    if args_cli.mesh:
        name += "_MESH"
    out = os.path.join(REPO, name + ".json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
