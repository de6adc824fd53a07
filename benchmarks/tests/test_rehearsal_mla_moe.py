"""The new driver end to end at a tiny size on the CPU (``serve_mla_moe``): a
throw-away configuration and cell as new files in a temporary copy, a traced
run on the recorded trace, and the two planted faults, which have to read
``correct`` false."""

import json
import os

import numpy as np
import pytest

from conftest import BENCH, load_harness
from test_rehearsal import CONTRACT_KEYS, run_cell

TINY_CELL = {
    "name": "tiny.serve-mla", "config": "tiny-mla-moe", "traffic_name": "serve-closed",
    "driver": "serve_mla_moe", "chips": 1, "why": "throw-away cell of the tests",
    "engine": {"slots": 4, "buf_len": 96, "page_tokens": 8, "pool_pages": 0,
               "prefill_chunk_tokens": 32, "adapter_slots": 4},
    "traffic": {"callers": 6, "requests": 24, "block": 6,
                "prompt": {"lo": 4, "hi": 48}, "answer": {"lo": 3, "hi": 16},
                "adapters": {"count": 3, "power_a": 1.0},
                "stagger_first": 6, "ramp_seconds": 0.5},
    "trace_seconds": 1,
    "check": {"sample": 6, "near_tie_margin": 1e-6,
              "limits": {"served_gap": 1e-3, "served_gap_q99": 1e-3, "near_tie_share": 0.05, "unanswered": 0,
                         "short_answers": 0}},
}


@pytest.fixture
def mla_checkout(checkout):
    bench = checkout / "benchmarks"
    with open(os.path.join(BENCH, "tests", "tiny_mla_moe.json")) as f:
        (bench / "configs" / "tiny-mla-moe.json").write_text(f.read())
    (bench / "workloads" / "tiny.serve-mla.json").write_text(json.dumps(TINY_CELL))
    return checkout


def test_serve_mla_moe_end_to_end(mla_checkout, capsys):
    line, out = run_cell(load_harness(mla_checkout), capsys, "tiny.serve-mla")
    assert list(line) == CONTRACT_KEYS + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"served_gap", "served_gap_q99", "near_tie_share", "unanswered", "short_answers"}
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = [json.loads(l)["note"] for l in out.out.splitlines() if l.startswith('{"note"')]
    kv = next(n["kv"] for n in notes if "ticks_in_window" in n)
    assert kv["kv_bytes_per_token"] == 3 * 128 * 4 and kv["expert_pairs"] > 0
    assert any(n.get("experts_hit_mean", 0) > 0 for n in notes)


def test_traced_run_reads_the_new_layer_metrics(mla_checkout, capsys, monkeypatch):
    bench = mla_checkout / "benchmarks"
    for path in (bench / "layer_metrics").glob("*.axk1.json"):
        m = json.loads(path.read_text())
        m["workloads"] = m["workloads"] + ["tiny.serve-mla"]
        path.write_text(json.dumps(m))
    harness = load_harness(mla_checkout)
    from jax.profiler import ProfileData
    from readers import xplane
    with open(os.path.join(BENCH, "tests", "small_trace.textproto")) as f:
        text = "\n".join(line.split("#")[0] for line in f.read().splitlines())
    monkeypatch.setattr(xplane.Trace, "from_dir", classmethod(
        lambda cls, d: cls(ProfileData.from_text_proto(text))))
    line, _ = run_cell(harness, capsys, "tiny.serve-mla", trace=1)
    assert line["correct"] is True, line["checks"]
    got = set(line["metrics"])
    # the recorded trace has no such program: the rooflines find nothing and are left out
    assert {"serve_step_mfu.axk1", "tokens_per_tick.axk1", "expert_pairs_per_tick.axk1",
            "expert_load_max.axk1", "device_idle_pct.axk1"} <= got
    assert not {"decode_tick_roofline.axk1", "prefill_chunk_roofline.axk1"} & got
    assert 0 < line["metrics"]["serve_step_mfu.axk1"]["value"] < 100
    assert line["metrics"]["expert_pairs_per_tick.axk1"]["value"] > 0


def test_a_program_without_the_architecture_fails_at_once(mla_checkout, capsys, monkeypatch):
    """The parent's ``config_from_args`` reads no published config: the cell
    then says so and prints no result."""
    from fedml_tpu.llm import model as M
    monkeypatch.setattr(M, "config_from_published", lambda published: {})
    harness = load_harness(mla_checkout)
    from conftest import fake_devices
    rc = harness.main(["--workload", "tiny.serve-mla", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], find=fake_devices)
    out = capsys.readouterr()
    assert rc == 2 and "latent attention" in out.err and out.out.strip() == ""


# -- the timed path broken underneath: `correct` has to come out false ----------

def test_fault_an_experts_output_dropped(mla_checkout, capsys, monkeypatch):
    import jax.numpy as jnp
    from fedml_tpu.llm import moe
    real = moe.expert_ffn
    monkeypatch.setattr(moe, "expert_ffn", lambda x, gates, experts, *rest: real(
        x, jnp.where(experts < 4, 0.0, gates), experts, *rest))
    line, _ = run_cell(load_harness(mla_checkout), capsys, "tiny.serve-mla")
    assert line["correct"] is False
    assert line["checks"]["served_gap"]["value"] > line["checks"]["served_gap"]["limit"]


def test_fault_a_page_of_latent_overwritten(mla_checkout, capsys, monkeypatch):
    import jax
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    real = ContinuousBatchingEngine._dispatch

    def scribbled(self, live):
        pages = [int(self._btabs[i, 0]) for i in live]
        self._pool = jax.tree_util.tree_map(lambda p: p.at[np.asarray(pages)].set(0), self._pool)
        return real(self, live)

    monkeypatch.setattr(ContinuousBatchingEngine, "_dispatch", scribbled)
    line, _ = run_cell(load_harness(mla_checkout), capsys, "tiny.serve-mla")
    assert line["correct"] is False
    assert line["checks"]["served_gap"]["value"] > line["checks"]["served_gap"]["limit"]
