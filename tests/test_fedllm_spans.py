"""The round driver's live span tree (ISSUE 27): one ``fedllm.round`` per
``FedLLMAPI.train_one_round`` with its five phases under it, in order."""

import numpy as np
import pytest

from fedml_tpu import obs
from fedml_tpu.arguments import load_arguments
from tests.span_tree import children, named, spans_of

PHASES = ["fedllm.round.sample", "fedllm.round.batches", "fedllm.round.stage",
          "fedllm.round.dispatch", "fedllm.round.readback"]


@pytest.fixture(scope="module")
def api():
    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.core.data.noniid_partition import partition
    from fedml_tpu.llm.fedllm import FedLLMAPI

    args = load_arguments()
    args.update(model="llama", dataset="shakespeare", seq_len=16,
                llm_dim=32, llm_n_layers=1, llm_n_heads=2, llm_n_kv_heads=2,
                llm_ffn_dim=64, llm_max_seq_len=16,
                client_num_in_total=4, client_num_per_round=2, comm_round=3,
                batch_size=2, learning_rate=3e-3, random_seed=9,
                llm_max_local_steps=2, lora_rank=2, partition_method="homo")
    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, _ = data_mod.load(args)
    dataset.train_x, dataset.train_y = dataset.train_x[:64], dataset.train_y[:64]
    dataset.test_x, dataset.test_y = dataset.test_x[:8], dataset.test_y[:8]
    dataset.client_idxs = partition(dataset.train_y[:, 0], 4, "homo", 0.5, 0)
    api = FedLLMAPI(args, dataset)
    api.train_one_round(0)                  # compiles the round
    return api


def test_a_round_leaves_its_five_phases_in_order(api):
    staged = []
    orig = api.dataset.cohort_batches

    def recording(*a, **kw):
        staged.append(orig(*a, **kw))
        return staged[-1]

    api.dataset.cohort_batches = recording
    tracer = obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        losses = [api.train_one_round(r)["train_loss"] for r in (1, 2)]
        events = tracer.events()
        counted = tracer.summary()["counters"]["device_put_bytes"]
    finally:
        del api.dataset.cohort_batches
        obs.configure(enabled=False, reset=True)
    assert all(np.isfinite(losses))
    spans = spans_of([e for e in events if e["ph"] in "BE"])
    rounds = named(spans, "fedllm.round")
    assert [r["args"]["round"] for r in rounds] == [1, 2]
    for r, (x, y, mask, w) in zip(rounds, staged):
        kids = children(spans, r)
        assert [k["name"] for k in kids] == PHASES
        # one after the other, all inside the round
        edges = [r["t0"]] + [t for k in kids for t in (k["t0"], k["t1"])] + [r["t1"]]
        assert edges == sorted(edges)
        # the driver's count: clients x steps x batch x sequence
        assert r["args"]["tokens"] == int(np.prod(x.shape)) == 2 * 2 * 2 * 16
        assert r["args"]["clients"] == x.shape[0] == 2
        assert kids[2]["args"]["bytes"] > x.nbytes // 2
    # what the stage spans put on the device is what the counter holds
    stages = named(spans, "fedllm.round.stage")
    assert counted == sum(s["args"]["bytes"] for s in stages)


def test_tracing_changes_no_result_and_adds_no_transfer(api):
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    import jax

    def audited(traced):
        lora = api.global_lora
        if traced:
            obs.configure(enabled=True, reset=True, jax_hooks=False)
        try:
            with JaxRuntimeAudit() as audit:
                loss = api.train_one_round(5)["train_loss"]
            after = jax.device_get(api.global_lora)
        finally:
            obs.configure(enabled=False)
            api.global_lora = lora
        return audit, loss, after

    tracer = obs.get_tracer()
    tracer.reset()
    base, loss0, lora0 = audited(False)
    assert tracer.events() == []
    on, loss1, lora1 = audited(True)
    assert any(e["name"] == "fedllm.round" for e in tracer.events())
    tracer.reset()
    assert loss0 == loss1
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(lora0), jax.tree_util.tree_leaves(lora1)))
    assert base.compilations == on.compilations == 0
    assert (on.device_puts, on.device_gets) == (base.device_puts, base.device_gets)
