"""The rule by which ``round_attn_ms`` and ``attn_roofline`` pick the round's
attention out of ``round_fn``'s instruction -> module map (PR 40): everything
under ``layer_*/attention`` but the projections, whatever implements it.

On today's round (the blockwise ``jnp`` scan) it has to pick exactly what the
rule before it picked: the module's own instructions and the scan's einsum
scopes (``layer_*/attention/*->*``)."""

import json
import os

import numpy as np
import pytest

from conftest import BENCH

from readers import ops

#: the two metrics' ``args`` before PR 40
OLD = {"path": ["layer_*/attention", "layer_*/attention/*->*"]}
METRICS = ("round_attn_ms", "attn_roofline")
PROJECTIONS = ("wq", "wk", "wv", "wo")


def rule(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)["args"]


def picked(rows: dict, args: dict) -> set:
    return {instr for instr, row in rows.items() if ops.matches(row, args)}


def is_projection(path: str) -> bool:
    parts = path.split("/")
    return len(parts) > 2 and parts[1] == "attention" and parts[2] in PROJECTIONS


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path, counted", [
    ("layer_3/attention", True),
    ("layer_3/attention/...qd,...kd->...qk", True),
    ("layer_3/attention/flash_fwd", True),             # a Pallas kernel's own scope
    ("layer_3/attention/flash_bwd/dkv", True),
    ("layer_3/attention/wq", False),
    ("layer_3/attention/wo/base", False),
    ("layer_3/attention/wk/lora_a", False),
    ("layer_3/attention/wqkv", True),                  # not one of the four projections
    ("layer_3/mlp/w_up", False),
    ("layer_3/attn_norm", False),
    ("layer_3/attention._paged_attend", False),       # serving's method scope: not the round's
    ("", False),
])
def test_the_rule_on_paths(metric, path, counted):
    row = {"path": path, "phase": "backward", "kernel": "", "op": "fusion"}
    assert ops.matches(row, rule(metric)) is counted


@pytest.fixture(scope="module")
def api():
    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu.core.data.noniid_partition import partition
    from fedml_tpu.llm.fedllm import FedLLMAPI

    args = load_arguments()
    args.update(model="llama", dataset="shakespeare", seq_len=16,
                llm_dim=32, llm_n_layers=2, llm_n_heads=2, llm_n_kv_heads=2,
                llm_ffn_dim=64, llm_max_seq_len=16,
                client_num_in_total=4, client_num_per_round=2, comm_round=3,
                batch_size=2, learning_rate=3e-3, random_seed=9,
                llm_max_local_steps=2, lora_rank=2, partition_method="homo")
    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, _ = data_mod.load(args)
    dataset.train_x, dataset.train_y = dataset.train_x[:64], dataset.train_y[:64]
    dataset.test_x, dataset.test_y = dataset.test_x[:8], dataset.test_y[:8]
    dataset.client_idxs = partition(dataset.train_y[:, 0], 4, "homo", 0.5, 0)
    return FedLLMAPI(args, dataset)


def test_todays_round_reads_the_same_instructions_under_both_rules(api):
    assert np.isfinite(api.train_one_round(0)["train_loss"])
    rows = api.program_ops()["round_fn"]
    old = picked(rows, OLD)
    # the scan's products under their einsums' scopes, in all three phases
    assert {rows[i]["phase"] for i in old} == {"forward", "recompute", "backward"}
    assert any("->" in rows[i]["path"] for i in old)
    for metric in METRICS:
        assert picked(rows, rule(metric)) == old, metric
    # the projections (and their base and adapter children) are there, and in neither
    projections = {i for i, row in rows.items() if is_projection(row["path"])}
    assert {rows[i]["path"].split("/")[2] for i in projections} == set(PROJECTIONS)
    assert not projections & old
