"""Traffic of the kind "federated LoRA rounds": the window drives
``FedLLMAPI.train_one_round`` again and again, entered as a user enters it
(``load_arguments`` -> ``fedml_tpu.init`` -> ``data.load`` -> ``FedLLMAPI``).

The benchmark supplies the inputs from ``--seed``: the clients' rows (token ids
over the whole vocabulary), the partition, the frozen base and the adapters the
federation starts from.  Set-up drives the first three rounds through the very
object and call the window then uses; the plain reference follows them after
the window has closed."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import statistics
import time

import numpy as np

import weights
from reference import dense_decoder as ref


def _dataset_from_seed(dataset, t: dict, vocab: int, seed: int) -> None:
    """Rows drawn from the seed, all different; client c holds
    ``client_rows[c % len]`` of them (the same multiset for every seed, in an
    order drawn from the seed), so the merge's weights differ."""
    rng = np.random.default_rng([int(seed), 0xDA7A])
    n = int(t["clients_total"])
    sizes = rng.permutation(np.resize(np.asarray(t["client_rows"], np.int64), n))
    tokens = rng.integers(1, vocab, size=(int(sizes.sum()), int(t["seq_len"]) + 1),
                          dtype=np.int32)
    edges = np.concatenate([[0], np.cumsum(sizes)])
    dataset.train_x, dataset.train_y = tokens[:, :-1], tokens[:, 1:]
    dataset.test_x, dataset.test_y = tokens[:2, :-1], tokens[:2, 1:]
    dataset.client_idxs = {c: np.arange(edges[c], edges[c + 1]) for c in range(n)}
    dataset.num_classes = vocab


@contextlib.contextmanager
def _preset_as_configured(cfg: dict):
    """``config_from_args`` starts from the preset ``LLAMA2_7B`` and has no
    argument for ``rope_theta`` or the norm's epsilon: while the API is built
    the preset carries the configuration's, so the program makes its own model
    and jits its own round with them (PERF.md, Open questions)."""
    from fedml_tpu.llm import model
    preset = model.LLAMA2_7B
    model.LLAMA2_7B = dataclasses.replace(
        preset, rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]))
    try:
        yield
    finally:
        model.LLAMA2_7B = preset


def build_api(cfg: dict, t: dict, seed: int):
    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu.llm.fedllm import FedLLMAPI

    m = weights.dims(cfg)
    args = load_arguments()
    args.update(
        dataset="stackoverflow_nwp", train_size=4 * int(t["clients_total"]),
        test_size=32, seq_len=int(t["seq_len"]), model="llama",
        llm_dim=m["d"], llm_n_layers=m["layers"], llm_n_heads=m["h"],
        llm_n_kv_heads=m["kv"], llm_ffn_dim=m["f"],
        llm_max_seq_len=int(t["seq_len"]),
        client_num_in_total=int(t["clients_total"]),
        client_num_per_round=int(t["clients_per_round"]), comm_round=1 << 30,
        batch_size=int(t["batch"]), epochs=int(t["epochs"]),
        llm_max_local_steps=int(t["local_steps"]),
        lora_rank=int(cfg["lora"]["rank"]), lora_alpha=float(cfg["lora"]["alpha"]),
        learning_rate=float(t["learning_rate"]), random_seed=int(seed) % (2 ** 31 - 1),
        streaming_xent_chunk=int(t["streaming_xent_chunk"]),
        llm_remat=str(t["remat"]), partition_method="homo",
        model_dtype=str(cfg.get("compute_dtype", "bfloat16")))
    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, _ = data_mod.load(args)
    _dataset_from_seed(dataset, t, m["v"], seed)
    with _preset_as_configured(cfg):
        api = FedLLMAPI(args, dataset)
    got = {k: getattr(api.cfg, k) for k in ("rope_theta", "norm_eps")}
    want = {"rope_theta": float(cfg["rope_theta"]), "norm_eps": float(cfg["rms_norm_eps"])}
    if got != want:
        raise RuntimeError(f"the program built its model with {got}, the configuration "
                           f"states {want}")
    return api


def install_weights(api, cfg: dict, seed: int) -> None:
    """The program's own initial weights go; the benchmark's, drawn from the
    seed, take their place, as a checkpoint's would."""
    import jax
    want = jax.eval_shape(lambda: (weights.make_base(cfg, 0), weights.make_lora(cfg, 0)))
    for ours, theirs, what in ((want[0], api.base_params, "base"),
                               (want[1], api.global_lora, "adapters")):
        diff = weights.same_layout(ours, theirs)
        if diff:
            raise RuntimeError(f"the {what} the benchmark makes do not fit the program: {diff}")
    api.base_params = None
    api.global_lora = None
    # the eager ``model.init`` under ``nn.remat`` leaves the whole initial tree
    # referenced from jax's trace cache (a closure over the flax scope): without
    # this the chip holds two bases (PERF.md, Open questions).  Nothing has
    # compiled yet that the window needs.
    jax.clear_caches()
    gc.collect()
    api.base_params = weights.make_base(cfg, seed)
    api.global_lora = weights.make_lora(cfg, seed)


def one_round(api, r: int) -> float:
    import jax
    loss = api.train_one_round(r)["train_loss"]
    jax.block_until_ready(api.global_lora)
    return float(loss)


def first_rounds(api, n: int) -> dict:
    """Rounds 0..n-1 through the window's own call, with what the program
    staged for each (the reference has to follow the same rows) and the
    adapters after each."""
    import jax
    staged = []
    dataset = api.dataset
    orig = dataset.cohort_batches

    def recording(*a, **kw):
        out = orig(*a, **kw)
        staged.append(tuple(np.array(o) for o in out))
        return out

    dataset.cohort_batches = recording
    losses, loras = [], []
    try:
        for r in range(n):
            losses.append(one_round(api, r))
            loras.append(jax.device_get(api.global_lora))
    finally:
        del dataset.cohort_batches
    return {"losses": losses, "loras": loras, "staged": staged}


def setup(run) -> dict:
    cfg, t = run.cfg, run.cell["traffic"]
    t0 = time.perf_counter()
    api = build_api(cfg, t, run.seed)
    init_s = time.perf_counter() - t0
    install_weights(api, cfg, run.seed)
    t1 = time.perf_counter()
    first = first_rounds(api, 3)
    run.note(init_s=init_s, first_rounds_s=time.perf_counter() - t1,
             first_losses=first["losses"])
    x = first["staged"][0][0]
    return {"api": api, "first": first, "next_round": 3,
            "tokens_per_round": int(np.prod(x.shape))}


def window(state: dict, run, seconds: float) -> None:
    api = state["api"]
    r = state["next_round"]
    rounds, bad = 0, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with run.span("bench.round"):
            loss = api.train_one_round(r)["train_loss"]
        with run.span("bench.wait"):
            import jax
            jax.block_until_ready(api.global_lora)
        bad += 0 if math.isfinite(loss) else 1
        r += 1
        rounds += 1
    state["done"] = (t0, time.perf_counter(), rounds, bad)


def finish(state: dict, run) -> dict:
    t0, t1, rounds, bad = state["done"]
    tokens = rounds * state["tokens_per_round"]
    run.counters.update(rounds=rounds, tokens_trained=tokens)
    return {"window": (t0, t1), "attempted": rounds, "failed": bad,
            "metrics": {"train_tokens_per_s": (tokens / (t1 - t0), "tokens/s")},
            "notes": {"rounds": rounds, "round_s": (t1 - t0) / max(rounds, 1)}}


# -- the comparison ----------------------------------------------------------------

def leaf_norms(tree_a, tree_b) -> np.ndarray:
    """Norm of (a - b), leaf by leaf, in float64 on the host."""
    import jax
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
                     for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                                     jax.tree_util.tree_leaves(tree_b))])


def leaf_gaps(prog: np.ndarray, want: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """The gap between the program's norm and the reference's, leaf by leaf,
    against that leaf's reference norm or the median leaf's, whichever is
    larger."""
    floor = statistics.median(want[keep])
    return np.abs(prog[keep] - want[keep]) / np.maximum(want[keep], floor)


def follow(cfg: dict, t: dict, seed: int, staged, n_rounds: int, quant=None,
           rows=None) -> dict:
    """The plain reference through the first ``n_rounds`` rounds on the rows
    the program staged."""
    import jax
    base = weights.make_base(cfg, seed)
    lora0 = weights.make_lora(cfg, seed)
    lora, losses, loras = lora0, [], []
    grad0 = None
    for r in range(n_rounds):
        x, y, mask, w = staged[r]
        if not mask.all():
            raise RuntimeError("a staged client has a padded step: the cell's rows "
                               "and steps no longer agree")
        if grad0 is None:
            _, g = ref.loss_and_grad(lora, base, x[0, 0][:rows], y[0, 0][:rows], cfg, quant)
            grad0 = np.array([float(np.linalg.norm(np.asarray(l, np.float64)))
                              for l in jax.tree_util.tree_leaves(g)])
        lora, loss = ref.federated_round(base, lora, x, y, w, cfg,
                                         float(t["learning_rate"]), quant, rows)
        losses.append(loss)
        loras.append(jax.device_get(lora))
    return {"losses": losses, "loras": loras, "lora0": jax.device_get(lora0),
            "grad0": grad0}


def compare(got: dict, want: dict, n_rounds: int) -> dict:
    """The numbers compared, by name: each round's loss (relative gap), and the
    norm of the adapters' change after the first round and after the last one
    followed, by the worst leaf and by the median leaf.  Leaves whose first gradient in the reference
    is under a thousandth of the median leaf's are left out of the change."""
    out = {}
    for r in range(n_rounds):
        out[f"loss_r{r + 1}"] = abs(got["losses"][r] - want["losses"][r]) / abs(want["losses"][r])
    keep = want["grad0"] >= 1e-3 * statistics.median(want["grad0"])
    for r in sorted({0, n_rounds - 1}):
        gaps = leaf_gaps(leaf_norms(got["loras"][r], want["lora0"]),
                         leaf_norms(want["loras"][r], want["lora0"]), keep)
        out[f"dnorm_r{r + 1}"] = float(np.max(gaps))            # the worst leaf
        out[f"dnorm_med_r{r + 1}"] = float(np.median(gaps))    # steadier from seed to seed
    return out


def check(state: dict, run, result: dict) -> dict:
    import jax
    first = state.pop("first")
    state.clear()              # the API, its base and its programs go
    gc.collect()
    jax.clear_caches()
    spec = run.cell["check"]
    n = int(spec["rounds"])
    want = follow(run.cfg, run.cell["traffic"], run.seed, first["staged"], n)
    numbers = compare(first, want, n)
    return {k: {"value": v, "limit": float(spec["limits"][k])}
            for k, v in numbers.items() if k in spec["limits"]}
