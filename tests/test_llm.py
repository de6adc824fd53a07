"""FedLLM path: transformer correctness, attention implementations agree,
ring attention matches dense attention on a sharded mesh, LoRA federation
reduces loss with base weights frozen."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.arguments import load_arguments


def test_blockwise_matches_dense_attention():
    from fedml_tpu.ops.attention import blockwise_attention

    key = jax.random.PRNGKey(0)
    b, h, s, d = 2, 3, 70, 16  # s not a multiple of block: exercises padding
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, h, s, d))
               for i in range(3))

    def dense_attn(q, k, v, causal):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
        if causal:
            mask = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(mask, scores, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores), v)

    for causal in (True, False):
        out = blockwise_attention(q, k, v, causal=causal, block_k=32)
        ref = dense_attn(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


def test_blockwise_attention_grads():
    from fedml_tpu.ops.attention import blockwise_attention, flash_attention

    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 2, 33, 8))
               for i in range(3))

    def dense_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (8 ** 0.5)
        mask = jnp.tril(jnp.ones((33, 33), bool))
        s = jnp.where(mask, s, -1e30)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s), v) ** 2)

    def fa_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None) ** 2)

    g_ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(fa_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-3)


def test_ring_attention_matches_dense():
    from fedml_tpu.ops.ring_attention import ring_attention
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = 4
    devices = np.array(jax.devices()[:n_dev])
    mesh = Mesh(devices, ("seq",))
    b, h, s, d = 1, 2, 64, 8  # s split 16 per device
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, h, s, d))
               for i in range(3))

    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq", causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None)))
    out = ring(q, k, v)

    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    ref = jnp.einsum("bhqk,bhkd->bhqd",
                     jax.nn.softmax(jnp.where(mask, scores, -1e30)), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def _llm_args(**over):
    args = load_arguments()
    args.update(model="tiny_llama", dataset="shakespeare", seq_len=32,
                client_num_in_total=6, client_num_per_round=3, comm_round=3,
                batch_size=4, learning_rate=3e-3, random_seed=9,
                llm_max_local_steps=4, lora_rank=4, partition_method="homo")
    args.update(**over)
    return args


def test_llama_forward_shapes():
    from fedml_tpu.llm.model import LlamaLM, TINY

    model = LlamaLM(TINY)
    tokens = jnp.ones((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 16, TINY.vocab_size)
    assert "lora" not in variables  # rank 0 → no adapter collection


@pytest.mark.slow
def test_fedllm_lora_federation():
    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.llm.fedllm import FedLLMAPI

    args = fedml_tpu.init(_llm_args())
    dataset, vocab = data_mod.load(args)
    # shrink dataset for test speed
    dataset.train_x, dataset.train_y = dataset.train_x[:600], dataset.train_y[:600]
    dataset.test_x, dataset.test_y = dataset.test_x[:100], dataset.test_y[:100]
    from fedml_tpu.core.data.noniid_partition import partition
    dataset.client_idxs = partition(dataset.train_y[:, 0], 6, "homo", 0.5, 0)

    api = FedLLMAPI(args, dataset)
    base_before = jax.tree_util.tree_leaves(api.base_params)[0].copy()
    nll0 = api.evaluate()
    api.train()
    nll1 = api.evaluate()
    assert nll1 < nll0, (nll0, nll1)
    # base weights frozen — only adapters moved
    base_after = jax.tree_util.tree_leaves(api.base_params)[0]
    np.testing.assert_array_equal(np.asarray(base_before),
                                  np.asarray(base_after))
    # adapters actually non-zero after training
    b_leaves = [np.asarray(l) for p, l in
                jax.tree_util.tree_flatten_with_path(api.global_lora)[0]
                if any(getattr(k, "key", "") == "B" for k in p)]
    assert max(np.abs(b).max() for b in b_leaves) > 0


def _small_llm_dataset(args):
    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.core.data.noniid_partition import partition

    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, _ = data_mod.load(args)
    dataset.train_x, dataset.train_y = (dataset.train_x[:600],
                                        dataset.train_y[:600])
    dataset.test_x, dataset.test_y = (dataset.test_x[:100],
                                      dataset.test_y[:100])
    dataset.client_idxs = partition(dataset.train_y[:, 0], 6, "homo", 0.5, 0)
    return dataset


@pytest.mark.slow
def test_fedllm_mesh_matches_single_device():
    """Mesh regime (client-axis sharded cohort, TP-ruled base) must
    reproduce the single-device LoRA federation numerics."""
    from fedml_tpu.core.mesh import make_mesh
    from fedml_tpu.llm.fedllm import FedLLMAPI

    args = _llm_args(client_num_per_round=4, comm_round=2)
    dataset = _small_llm_dataset(args)

    api_sp = FedLLMAPI(args, dataset)
    lora_sp = api_sp.train()

    mesh = make_mesh(client=4, model=2)
    api_mesh = FedLLMAPI(args, dataset, mesh=mesh)
    lora_mesh = api_mesh.train()

    for a, b in zip(jax.tree_util.tree_leaves(lora_sp),
                    jax.tree_util.tree_leaves(lora_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_fedllm_mesh_nondivisible_cohort():
    from fedml_tpu.core.mesh import make_mesh
    from fedml_tpu.llm.fedllm import FedLLMAPI

    args = _llm_args(client_num_per_round=3, comm_round=1)  # 3 vs 4 shards
    dataset = _small_llm_dataset(args)
    mesh = make_mesh(client=4)
    api = FedLLMAPI(args, dataset, mesh=mesh)
    out = api.train_one_round(0)
    assert np.isfinite(out["train_loss"])


def test_llm_configuration_dataclasses_roundtrip():
    from fedml_tpu.llm.configurations import (DatasetArguments,
                                              ExperimentArguments,
                                              ModelArguments)

    args = _llm_args()
    ma = ModelArguments.from_args(args)
    assert ma.model_name_or_path == "tiny_llama" and ma.lora_rank == 4
    da = DatasetArguments.from_args(args)
    assert da.truncation_max_length == 32
    ea = ExperimentArguments.from_args(args)
    assert ea.client_num_per_round == 3

    fresh = load_arguments()
    ma.apply_to(fresh); da.apply_to(fresh); ea.apply_to(fresh)
    assert fresh.model == "tiny_llama"
    assert fresh.seq_len == 32
    assert fresh.lora_rank == 4
    assert fresh.client_num_per_round == 3


def test_causal_lm_trainer_centralized(tmp_path):
    """Reference hf_trainer.py path: centralized fine-tune + checkpoint +
    resume; LoRA-only mode freezes the base weights."""
    from fedml_tpu.llm.trainer import CausalLMTrainer

    args = _llm_args(epochs=2, batch_size=4,
                     output_dir=str(tmp_path / "out"))
    dataset = _small_llm_dataset(args)
    trainer = CausalLMTrainer(args, dataset)
    base_before = np.asarray(
        jax.tree_util.tree_leaves(trainer.base_params)[0]).copy()
    nll0 = trainer.evaluate()
    out = trainer.train()
    nll1 = trainer.evaluate()
    assert nll1 < nll0, (nll0, nll1)
    assert len(out["history"]) == 2
    # LoRA-only: base unchanged
    np.testing.assert_array_equal(
        base_before, np.asarray(jax.tree_util.tree_leaves(
            trainer.base_params)[0]))

    # resume restores step count and state
    trainer.close()
    trainer2 = CausalLMTrainer(args, dataset)
    assert trainer2.resume_from_checkpoint()
    assert trainer2.global_step == trainer.global_step
    nll2 = trainer2.evaluate()
    np.testing.assert_allclose(nll2, nll1, rtol=1e-5)
    trainer2.close()


def test_ring_attention_gradients_match_dense():
    """Sequence-parallel TRAINING path: grads through ring attention
    (scan + ppermute under shard_map) must match dense attention grads."""
    from fedml_tpu.ops.ring_attention import ring_attention
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = 4
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    b, h, s, d = 1, 2, 32, 8
    key = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, h, s, d))
               for i in range(3))

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None))

    def ring_loss(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def dense_loss(q, k, v):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
        mask = jnp.tril(jnp.ones((s, s), bool))
        out = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(jnp.where(mask, scores, -1e30)), v)
        return jnp.sum(out ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=5e-5, rtol=1e-3)


def test_hf_tokenizer_adapter_offline(tmp_path):
    """HF tokenizer parity without egress: build a BPE tokenizer locally
    (tokenizers lib), save, reload via load_tokenizer, round-trip text, and
    serve generation through it."""
    from tokenizers import Tokenizer
    from tokenizers.models import BPE
    from tokenizers.trainers import BpeTrainer
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(BPE(unk_token="<unk>"))
    tok.pre_tokenizer = Whitespace()
    trainer = BpeTrainer(special_tokens=["<unk>", "<s>", "</s>"],
                         vocab_size=200)
    tok.train_from_iterator(
        ["the quick brown fox jumps over the lazy dog",
         "federated learning on tpu pods", "hello world"] * 20, trainer)
    fast = PreTrainedTokenizerFast(tokenizer_object=tok, unk_token="<unk>",
                                   bos_token="<s>", eos_token="</s>")
    path = tmp_path / "tok"
    fast.save_pretrained(str(path))

    from fedml_tpu.llm.tokenization import HFTokenizerAdapter, load_tokenizer
    loaded = load_tokenizer(str(path))
    assert isinstance(loaded, HFTokenizerAdapter)
    ids = loaded.encode("hello world")
    assert ids[0] == loaded.bos_id
    assert "hello world" in loaded.decode(ids)

    # unresolvable path -> byte tokenizer fallback, never a download
    fallback = load_tokenizer("/does/not/exist")
    assert fallback.vocab_size == 258


def test_lr_schedule_shapes():
    """HF-style schedules (reference ExperimentArguments.lr_scheduler_type):
    linear warmup then constant / linear / cosine decay."""
    from fedml_tpu.llm.trainer import make_lr_schedule

    s = make_lr_schedule(1e-3, "cosine", warmup_steps=10, total_steps=110)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1e-3) < 1e-9       # warmup peak
    assert float(s(5)) == pytest.approx(5e-4)    # mid-warmup
    assert float(s(60)) < 1e-3                   # decaying
    assert float(s(110)) == pytest.approx(0.0, abs=1e-9)

    lin = make_lr_schedule(2e-3, "linear", warmup_steps=0, total_steps=100)
    assert float(lin(0)) == pytest.approx(2e-3)
    assert float(lin(50)) == pytest.approx(1e-3)

    const = make_lr_schedule(1e-3, "constant", warmup_steps=4,
                             total_steps=100)
    assert float(const(50)) == pytest.approx(1e-3)

    with pytest.raises(ValueError):
        make_lr_schedule(1e-3, "polynomial", 0, 10)


@pytest.mark.slow
def test_gradient_accumulation_matches_large_batch(tmp_path):
    """accum=2 at half batch must produce the same trained params as one
    full-batch step stream (MultiSteps averages micro-grads; the epoch
    permutation is seed-deterministic so micro-batch pairs tile the full
    batches exactly)."""
    from fedml_tpu.llm.trainer import CausalLMTrainer

    base = dict(epochs=1, learning_rate=1e-3, lora_rank=4, random_seed=9)
    args_full = _llm_args(batch_size=8, **base)
    ds = _small_llm_dataset(args_full)
    t_full = CausalLMTrainer(args_full, ds)
    t_full.train()

    args_acc = _llm_args(batch_size=4, gradient_accumulation_steps=2,
                         **base)
    t_acc = CausalLMTrainer(args_acc, ds)
    t_acc.train()

    for a, b in zip(jax.tree_util.tree_leaves(t_full.lora),
                    jax.tree_util.tree_leaves(t_acc.lora)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_trainer_with_warmup_clip_trains(tmp_path):
    """Full training-control stack (cosine schedule + warmup + grad
    clipping + accumulation) still reduces eval NLL."""
    from fedml_tpu.llm.trainer import CausalLMTrainer

    args = _llm_args(epochs=2, batch_size=4, learning_rate=3e-3,
                     lr_scheduler_type="cosine", warmup_steps=5,
                     max_grad_norm=1.0, gradient_accumulation_steps=2,
                     output_dir=str(tmp_path / "out"))
    ds = _small_llm_dataset(args)
    trainer = CausalLMTrainer(args, ds)
    nll0 = trainer.evaluate()
    trainer.train()
    nll1 = trainer.evaluate()
    assert nll1 < nll0, (nll0, nll1)
    trainer.close()


def test_max_steps_budget_enforced(tmp_path):
    """max_steps caps optimizer updates (reference ExperimentArguments
    semantics), not just the LR horizon."""
    from fedml_tpu.llm.trainer import CausalLMTrainer

    args = _llm_args(epochs=5, batch_size=4, max_steps=7,
                     gradient_accumulation_steps=2,
                     output_dir=str(tmp_path / "out"))
    ds = _small_llm_dataset(args)
    trainer = CausalLMTrainer(args, ds)
    out = trainer.train()
    # 7 updates x 2 micro-steps = 14 micro-steps, regardless of epochs
    assert trainer.global_step == 14
    assert len(out["history"]) < 5  # stopped early
    trainer.close()


@pytest.mark.slow
def test_hetlora_rank_heterogeneity():
    """Per-client LoRA ranks (HetLoRA-style): homogeneous masks reproduce
    the plain path exactly; truncated clients never touch rank components
    they don't hold; components nobody holds collapse to zero."""
    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.llm.fedllm import FedLLMAPI

    def api_with(ranks):
        args = _llm_args(lora_rank=4, comm_round=2)
        if ranks is not None:
            args.update(lora_rank_per_client=ranks)
        ds = _small_llm_dataset(args)
        return FedLLMAPI(args, ds)

    # (a) homogeneous full-rank list ≡ no list at all
    a = api_with(None)
    b = api_with([4] * 6)
    for r in range(2):
        a.train_one_round(r)
        b.train_one_round(r)
    for la, lb in zip(jax.tree_util.tree_leaves(a.global_lora),
                      jax.tree_util.tree_leaves(b.global_lora)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-6)

    # (b) all clients rank 2 of 4: nobody holds components 2..3, so those
    # keep their INITIAL global values (zeroing them would be an
    # irreversible dead saddle) while components 0..1 train
    c = api_with([2] * 6)
    init = jax.tree_util.tree_map(lambda l: np.asarray(l).copy(),
                                  c.global_lora)
    c.train_one_round(0)
    nll0 = c.evaluate()
    c.train_one_round(1)
    flat = jax.tree_util.tree_flatten_with_path(c.global_lora)[0]
    init_flat = jax.tree_util.tree_flatten_with_path(init)[0]
    saw_a = False
    for (path, leaf), (_, leaf0) in zip(flat, init_flat):
        names = [getattr(p, "key", "") for p in path]
        arr, arr0 = np.asarray(leaf), np.asarray(leaf0)
        if "A" in names:
            saw_a = True
            np.testing.assert_array_equal(arr[:, 2:], arr0[:, 2:])
            assert np.any(arr[:, :2] != arr0[:, :2])  # held ranks trained
        elif "B" in names:
            np.testing.assert_array_equal(arr[2:, :], arr0[2:, :])
    assert saw_a
    assert c.evaluate() < nll0  # rank-2 federation still learns

    # (c) mixed ranks run and learn
    d = api_with([2, 2, 2, 4, 4, 4])
    n0 = d.evaluate()
    for r in range(2):
        d.train_one_round(r)
    assert d.evaluate() < n0

    # validation
    import pytest
    with pytest.raises(ValueError):
        api_with([5] * 6)       # above the global rank
    with pytest.raises(ValueError):
        api_with([4, 4])        # wrong length


def test_fedllm_per_client_eval_fairness():
    """Per-client NLL fairness view for the LLM federation: training must
    improve the mean AND the worst-served client; aggregates agree with
    the raw vector (the device-class signal HetLoRA deployments read)."""
    from fedml_tpu.llm.fedllm import FedLLMAPI

    args = _llm_args(comm_round=3, lora_rank=4,
                     lora_rank_per_client=[2, 2, 2, 4, 4, 4])
    ds = _small_llm_dataset(args)
    api = FedLLMAPI(args, ds)
    rep0 = api.evaluate_per_client()
    assert rep0["per_client_nll"].shape == (6,)
    for r in range(3):
        api.train_one_round(r)
    rep1 = api.evaluate_per_client()
    assert rep1["nll_mean"] < rep0["nll_mean"]
    assert rep1["nll_max"] < rep0["nll_max"]  # worst client improves too
    np.testing.assert_allclose(rep1["nll_mean"],
                               rep1["per_client_nll"].mean(), rtol=1e-6)
    assert rep1["nll_mean"] <= rep1["nll_p90"] <= rep1["nll_max"] + 1e-9


def test_fedllm_streaming_xent_matches_dense_loss():
    """streaming_xent_chunk swaps the training loss to the fused
    vocab-chunked path (ops/xent.py) — round losses must match the dense
    logits path to f32 tolerance (identical data/seed/schedule)."""
    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.core.data.noniid_partition import partition
    from fedml_tpu.llm.fedllm import FedLLMAPI

    losses = {}
    for chunk in (0, 64):
        args = fedml_tpu.init(_llm_args(streaming_xent_chunk=chunk,
                                        comm_round=2))
        dataset, _ = data_mod.load(args)
        dataset.train_x, dataset.train_y = (dataset.train_x[:300],
                                            dataset.train_y[:300])
        dataset.test_x, dataset.test_y = (dataset.test_x[:60],
                                          dataset.test_y[:60])
        dataset.client_idxs = partition(dataset.train_y[:, 0], 6, "homo",
                                        0.5, 0)
        api = FedLLMAPI(args, dataset)
        m0 = api.train_one_round(0)
        m1 = api.train_one_round(1)
        losses[chunk] = (float(m0["train_loss"]), float(m1["train_loss"]))
    d0, s0 = losses[0][0], losses[64][0]
    d1, s1 = losses[0][1], losses[64][1]
    assert abs(d0 - s0) < 5e-3 * max(1.0, abs(d0)), (d0, s0)
    assert abs(d1 - s1) < 5e-3 * max(1.0, abs(d1)), (d1, s1)


def test_remat_policy_value_parity():
    """remat is a pure recompute policy — "full"/"dots"/"none" must agree
    on loss and adapter gradients to float tolerance (only step time and
    HBM differ; not bitwise because XLA fuses each graph differently)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM, causal_nll

    import numpy as np

    results = {}
    for remat in ("full", "dots", "none"):
        cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                          n_kv_heads=2, ffn_dim=64, max_seq_len=32,
                          dtype=jnp.float32, lora_rank=4, remat=remat)
        model = LlamaLM(cfg)
        rng = jax.random.PRNGKey(0)
        toks = jax.random.randint(rng, (2, 32), 0, 128)
        v = model.init(rng, toks)
        params, lora = v["params"], v["lora"]

        def loss_fn(lora):
            logits = model.apply({"params": params, "lora": lora}, toks,
                                 train=True)
            return causal_nll(logits[:, :-1], toks[:, 1:])

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(lora)
        results[remat] = (float(loss), jax.tree.leaves(grads))

    l_full, g_full = results["full"]
    for other in ("dots", "none"):
        # not bitwise: XLA fuses the three graphs differently, so rounding
        # differs at the last ulp scale — but the POLICY must not change
        # the math beyond that
        l, g = results[other]
        assert abs(l - l_full) < 1e-5 * max(1.0, abs(l_full)), (other, l,
                                                                l_full)
        for a, b in zip(g, g_full):
            assert np.allclose(a, b, rtol=2e-4, atol=1e-6), other


def test_memory_estimate_remat_policies():
    """Estimator must price remat policies monotonically (full < dots <
    none), keep the north-star layout inside a v4 chip, and reject unknown
    chips loudly."""
    import pytest
    from fedml_tpu.core.memory_estimate import (
        FedLLMLayout, estimate_fedllm_memory, fits,
        northstar_llama2_7b_512clients)

    base = dict(n_params=6.74e9, n_lora_params=4 * 32 * 2 * 4096 * 16,
                n_clients=512, n_chips=256, model_shards=8,
                batch_per_client=1, seq_len=2048, dim=4096, n_layers=32)
    totals = {r: estimate_fedllm_memory(FedLLMLayout(**base, remat=r))["total"]
              for r in ("full", "dots", "none")}
    assert totals["full"] < totals["dots"] < totals["none"], totals
    assert fits(FedLLMLayout(**base), chip="v4")
    assert northstar_llama2_7b_512clients()["total_gib"] < 24
    with pytest.raises(ValueError):
        fits(FedLLMLayout(**base), chip="h100")


@pytest.mark.slow
def test_mesh_sharded_init_and_estimator_bound():
    """Round-5 sharded-accounting pin (round-4 VERDICT weak #3):

    1. mesh-regime init must materialize base weights DIRECTLY sharded —
       no full unsharded copy may survive init (the round-4 path leaked
       exactly 1x base weights onto device 0 via init-then-device_put);
    2. per-device physical bytes must be balanced across the mesh;
    3. the per-chip estimator must upper-bound the max-loaded device's
       physical bytes with tightness <= 1.6 (the pod-scheduling margin),
       on a base-weight-dominated config (the regime the estimator is
       for — pod scheduling of >=1B bases).
    """
    import gc

    from fedml_tpu.core.memory_estimate import (FedLLMLayout,
                                                estimate_fedllm_memory)
    from fedml_tpu.core.mesh import make_mesh
    from fedml_tpu.llm.fedllm import FedLLMAPI

    dim, layers, heads, kv_heads, ffn = 512, 8, 16, 8, 1408
    vocab, seq = 16000, 128
    args = _llm_args(model="llama", dataset="stackoverflow_nwp",
                     llm_dim=dim, llm_n_layers=layers, llm_n_heads=heads,
                     llm_n_kv_heads=kv_heads, llm_ffn_dim=ffn,
                     llm_max_seq_len=seq, seq_len=seq,
                     client_num_in_total=4, client_num_per_round=2,
                     comm_round=1, batch_size=1, llm_max_local_steps=1,
                     lora_rank=16, learning_rate=1e-4, random_seed=0)
    dataset = _small_llm_dataset(args)
    dataset.train_x = np.minimum(dataset.train_x, vocab - 1)
    dataset.train_y = np.minimum(dataset.train_y, vocab - 1)
    dataset.test_x = np.minimum(dataset.test_x, vocab - 1)
    dataset.test_y = np.minimum(dataset.test_y, vocab - 1)
    dataset.num_classes = vocab
    mesh = make_mesh(client=4, model=2)
    api = FedLLMAPI(args, dataset, mesh=mesh)
    gc.collect()

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(api.base_params))
    n_lora = sum(int(np.prod(p.shape))
                 for p in jax.tree_util.tree_leaves(api.global_lora))

    unsharded_weight_bytes = 0
    per_dev = {}
    for a in jax.live_arrays():
        try:
            shards = list(a.addressable_shards)
        except Exception:
            continue
        if len(shards) == 1 and a.nbytes >= dim * dim * 2:
            unsharded_weight_bytes += a.nbytes
        for s in shards:
            b = int(np.prod(s.data.shape)) * s.data.dtype.itemsize
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + b
    # (1) no weight-sized single-device array may exist post-init
    assert unsharded_weight_bytes == 0, (
        f"{unsharded_weight_bytes / 2**20:.1f} MiB of unsharded "
        "weight-sized arrays survived mesh init")
    # (2) balance: every device within 25% of the mean
    vals = np.array(sorted(per_dev.values()), float)
    assert vals.max() <= 1.25 * vals.mean(), per_dev
    # (3) estimator bounds the max-loaded device, tightly
    layout = FedLLMLayout(
        n_params=n_params, n_lora_params=n_lora, n_clients=2,
        n_chips=8, model_shards=2, batch_per_client=1, seq_len=seq,
        dim=dim, n_layers=layers, remat="full", ffn_dim=ffn,
        kv_dim=kv_heads * (dim // heads))
    est = estimate_fedllm_memory(layout)["total"]
    live_per_chip = vals.max()
    assert est >= live_per_chip, (est, live_per_chip)
    assert est / live_per_chip <= 1.6, (
        f"estimator tightness {est / live_per_chip:.2f} > 1.6 "
        f"(est {est / 2**20:.1f} MiB, live {live_per_chip / 2**20:.1f} MiB)")


def test_param_storage_dtype_policy():
    """Round-4 storage policy: frozen-base paths store matmul weights in
    ``LlamaConfig.store_dtype`` (bf16 halves HBM; the memory estimator
    prices 2 bytes/param), while anything TRAINED densely keeps f32
    masters (bf16 adamw loses updates below ~2^-9 relative).  Norm scales
    and MoE router kernels stay f32 everywhere."""
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.models.model_hub import create

    # 1. bf16 model init emits bf16 matmul weights, f32 norm scales
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=32,
                      dtype=jnp.bfloat16)
    params = LlamaLM(cfg).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    mats = {str(l.dtype) for l in jax.tree_util.tree_leaves(params)
            if l.ndim >= 2}
    norms = {str(l.dtype) for l in jax.tree_util.tree_leaves(params)
             if l.ndim == 1}
    assert mats == {"bfloat16"}, mats
    assert norms == {"float32"}, norms

    # 2. explicit param_dtype=f32 beats dtype (mixed-precision masters)
    cfg_f32 = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=4,
                          n_kv_heads=2, ffn_dim=64, max_seq_len=32,
                          dtype=jnp.bfloat16, param_dtype=jnp.float32)
    p32 = LlamaLM(cfg_f32).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]
    assert {str(l.dtype) for l in jax.tree_util.tree_leaves(p32)} \
        == {"float32"}

    # 3. generic dense-trained path (model_hub -> FlaxModel -> trainers)
    # keeps f32 masters even though LLAMA2_7B defaults to bf16 compute
    args = load_arguments()
    args.update(model="llama", llm_dim=32, llm_n_layers=1, llm_n_heads=4,
                llm_n_kv_heads=2, llm_ffn_dim=64, llm_max_seq_len=32,
                seq_len=16)
    dense = create(args, 64)
    pd = dense.init(jax.random.PRNGKey(0))
    assert {str(l.dtype) for l in jax.tree_util.tree_leaves(pd)} \
        == {"float32"}

    # 4. MoE: expert weights follow store_dtype, router kernel stays f32
    cfg_moe = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=4,
                          n_kv_heads=2, ffn_dim=64, max_seq_len=32,
                          dtype=jnp.bfloat16, n_experts=4)
    pm = LlamaLM(cfg_moe).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    flat = jax.tree_util.tree_flatten_with_path(pm)[0]
    router = [l for path, l in flat
              if any(getattr(k, "key", "") == "router" for k in path)]
    experts = [l for path, l in flat
               if any(getattr(k, "key", "") in ("w_gate", "w_up", "w_down")
                      and l.ndim == 3 for k in path)]
    assert router and all(l.dtype == jnp.float32 for l in router)
    assert experts and all(l.dtype == jnp.bfloat16 for l in experts)


def test_flash_autotune_fallback_policy(tmp_path, monkeypatch):
    """VERDICT r3 item 3: untuned shapes must never silently take the
    Pallas path — only shapes a sweep measured FASTER than blockwise get
    tuned-table entries, and load_tuned_blocks skips losing shapes."""
    import json
    from fedml_tpu.ops import attention as A

    # gate: tuned shape passes only on TPU; untuned never; env overrides
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    tuned_key = next(iter(A._TUNED_BLOCKS))
    assert A._use_pallas(*tuned_key)
    assert not A._use_pallas(12345, 77)          # untuned -> blockwise
    # the training round's shape takes the kernels at its swept tile; other
    # lengths and widths keep their path
    assert A._use_pallas(1024, 128)
    assert A._pick_blocks(1024, 128, None, None) == (1024, 1024)
    assert not A._use_pallas(1024, 192)          # latent attention's q/k width
    assert not A._use_pallas(12288, 128)         # a 12k-token pass
    monkeypatch.setenv("FEDML_TPU_FLASH_MODE", "off")
    assert not A._use_pallas(*tuned_key)
    monkeypatch.setenv("FEDML_TPU_FLASH_MODE", "force")
    assert A._use_pallas(12345, 77)
    monkeypatch.delenv("FEDML_TPU_FLASH_MODE")
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    assert not A._use_pallas(*tuned_key)         # CPU -> always blockwise
    monkeypatch.setenv("FEDML_TPU_FLASH_MODE", "force")
    with pytest.raises(RuntimeError, match="FEDML_TPU_FLASH_MODE=force"):
        A._use_pallas(*tuned_key)                # never a silent False
    monkeypatch.delenv("FEDML_TPU_FLASH_MODE")

    # loader: winner registered, loser skipped, junk lines tolerated
    art = tmp_path / "TPU_FLASH_TUNE.json"
    art.write_text(
        "[tune] progress line\n" + json.dumps({
            "metric": "flash_block_tune", "results": [
                {"shape": "b4_h16_kv16_s777_d64",
                 "best": {"bq": 256, "bk": 1024, "vs_blockwise": 2.4}},
                {"shape": "b1_h8_kv8_s888_d128",
                 "best": {"bq": 512, "bk": 512, "vs_blockwise": 0.7}},
            ]}) + "\n")
    before = dict(A._TUNED_BLOCKS)
    try:
        added = A.load_tuned_blocks(str(art))
        assert added == 1
        assert A._TUNED_BLOCKS[(777, 64)] == (256, 1024)
        assert (888, 128) not in A._TUNED_BLOCKS
        assert A.load_tuned_blocks(str(tmp_path / "missing.json")) == 0
    finally:
        A._TUNED_BLOCKS.clear()
        A._TUNED_BLOCKS.update(before)
