"""The ``serve_cohere2_moe`` driver end to end at a tiny size on the CPU: a
throw-away configuration and cell of two classes of length as new files in a
temporary copy, a traced run on the recorded trace, a program that lacks the
architecture, and the two planted faults, which have to read ``correct``
false."""

import json
import os

import pytest

from conftest import BENCH, load_harness
from test_rehearsal import CONTRACT_KEYS, run_cell

TINY_CELL = {
    "name": "tiny.serve-mixed", "config": "tiny-cohere2-moe", "traffic_name": "serve-mixed",
    "driver": "serve_cohere2_moe", "chips": 1, "why": "throw-away cell of the tests",
    "engine": {"slots": 4, "buf_len": 160, "page_tokens": 4, "pool_pages": 0, "window_pool_pages": 41,
               "prefill_chunk_tokens": 16, "adapter_slots": 4},
    "traffic": {"callers": 6, "requests": 24, "block": 8,
                "classes": [{"name": "short", "per_block": 6, "prompt": {"lo": 4, "hi": 30}},
                            {"name": "long", "per_block": 2, "prompt": {"lo": 90, "hi": 130}}],
                "answer": {"lo": 3, "hi": 16}, "adapters": {"count": 3, "power_a": 1.0},
                "stagger_first": 6, "ramp_seconds": 0.5},
    "trace_seconds": 1,
    "check": {"sample": 6, "sample_long": 2, "answer_tail": 16, "near_tie_margin": 1e-6,
              "limits": {"served_gap": 1e-3, "served_gap_q99": 1e-3, "near_tie_share": 0.05, "unanswered": 0,
                         "short_answers": 0}},
}


@pytest.fixture
def mixed_checkout(checkout):
    bench = checkout / "benchmarks"
    with open(os.path.join(BENCH, "tests", "tiny_cohere2_moe.json")) as f:
        (bench / "configs" / "tiny-cohere2-moe.json").write_text(f.read())
    (bench / "workloads" / "tiny.serve-mixed.json").write_text(json.dumps(TINY_CELL))
    return checkout


def notes_of(out):
    return [json.loads(l)["note"] for l in out.out.splitlines() if l.startswith('{"note"')]


def test_serve_cohere2_moe_end_to_end(mixed_checkout, capsys):
    line, out = run_cell(load_harness(mixed_checkout), capsys, "tiny.serve-mixed")
    assert list(line) == CONTRACT_KEYS + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"served_gap", "served_gap_q99", "near_tie_share", "unanswered", "short_answers"}
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = notes_of(out)
    window = next(n for n in notes if "ticks_in_window" in n)
    kv = window["kv"]
    # four layers of 2 x 2 x 16 float32 numbers a token; a ring of (24 + 16) / 4 + 1 entries
    assert kv["kv_bytes_per_token"] == 4 * 2 * 2 * 16 * 4 and kv["window_blocks"] == 11
    assert kv["window_pool_pages"] == 41 and kv["window_pages_freed"] > 0 and kv["expert_pairs"] > 0
    assert window["requests_by_class"].keys() == {"short", "long"}
    assert 0 < window["live_window_tokens_mean"] <= window["live_kv_tokens_mean"]
    checked = next(n for n in notes if "checked_by_class" in n)
    assert checked["checked_by_class"].get("long", 0) >= 1 and checked["checked_requests"] == 6


def test_traced_run_reads_the_new_layer_metrics(mixed_checkout, capsys, monkeypatch):
    bench = mixed_checkout / "benchmarks"
    for path in (bench / "layer_metrics").glob("*.cmda.json"):
        m = json.loads(path.read_text())
        m["workloads"] = m["workloads"] + ["tiny.serve-mixed"]
        path.write_text(json.dumps(m))
    harness = load_harness(mixed_checkout)
    from jax.profiler import ProfileData
    from readers import xplane
    with open(os.path.join(BENCH, "tests", "small_trace.textproto")) as f:
        text = "\n".join(line.split("#")[0] for line in f.read().splitlines())
    monkeypatch.setattr(xplane.Trace, "from_dir", classmethod(
        lambda cls, d: cls(ProfileData.from_text_proto(text))))
    line, _ = run_cell(harness, capsys, "tiny.serve-mixed", trace=1)
    assert line["correct"] is True, line["checks"]
    got = set(line["metrics"])
    # the recorded trace has no such program: the rooflines find nothing and are left out
    assert {"serve_step_mfu.cmda", "tokens_per_tick.cmda", "tick_ahead_share.cmda", "device_idle_pct.cmda",
            "window_pages_freed_per_tick.cmda", "window_live_share.cmda", "expert_pairs_per_tick.cmda",
            "expert_load_max.cmda"} <= got
    assert not {"decode_tick_roofline.cmda", "prefill_chunk_roofline.cmda"} & got
    assert 0 < line["metrics"]["serve_step_mfu.cmda"]["value"] < 100
    assert line["metrics"]["window_pages_freed_per_tick.cmda"]["value"] > 0
    assert 0 < line["metrics"]["window_live_share.cmda"]["value"] < 1


def test_the_span_ratio_reader_finds_nothing_in_an_older_program(mixed_checkout):
    """A program whose ticks carry no such arguments (the parent's): None, and
    the metric is left out of the line."""
    load_harness(mixed_checkout)
    import types
    from fedml_tpu import obs
    from readers import span_ratio
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        tracer = obs.get_tracer()
        with tracer.span("serve.tick", cat="engine", live=3):
            pass
        run = types.SimpleNamespace(window=(0.0, 1e18))
        args = {"span": "serve.tick", "over": "live_window_tokens", "under": "live_full_tokens"}
        assert span_ratio.read(args, run) is None
        with tracer.span("serve.tick", cat="engine") as tick:
            tick.set(live_window_tokens=30, live_full_tokens=120)
        assert span_ratio.read(args, run) == 0.25
    finally:
        obs.configure(enabled=False)


def test_a_program_without_the_architecture_fails_at_once(mixed_checkout, capsys, monkeypatch):
    """The parent's ``config_from_published`` passes the family's keys over:
    the cell then says so and prints no result."""
    from fedml_tpu.llm import model as M
    real = M.config_from_published
    monkeypatch.setattr(M, "config_from_published", lambda published: {
        k: v for k, v in real(dict(published, model_type="llama", layer_types=None)).items()
        if k not in ("parallel_block", "head_dim", "tie_embeddings", "logit_scale")})
    harness = load_harness(mixed_checkout)
    from conftest import fake_devices
    rc = harness.main(["--workload", "tiny.serve-mixed", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], find=fake_devices)
    out = capsys.readouterr()
    assert rc == 2 and "sliding-window" in out.err and out.out.strip() == ""


# -- the timed path broken underneath: `correct` has to come out false ----------

def test_fault_the_window_mask_dropped_in_one_layer(mixed_checkout, capsys, monkeypatch):
    """Layer 1 keeps its rotary embedding and loses its window: it attends to
    everything before, through the full layers' table."""
    from fedml_tpu.llm.model import LlamaConfig
    window, rope = LlamaConfig.layer_window, LlamaConfig.layer_rope
    monkeypatch.setattr(LlamaConfig, "layer_window", lambda self, i: 0 if i == 1 else window(self, i))
    monkeypatch.setattr(LlamaConfig, "layer_rope", lambda self, i: i == 1 or rope(self, i))
    line, _ = run_cell(load_harness(mixed_checkout), capsys, "tiny.serve-mixed")
    assert line["correct"] is False
    assert line["checks"]["served_gap"]["value"] > line["checks"]["served_gap"]["limit"]


def test_fault_a_freed_page_handed_out_inside_a_window(mixed_checkout, capsys, monkeypatch):
    """The engine believes the window two pages shorter than it is: pages
    still inside it go back to the free list and to their next holder."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    real = ContinuousBatchingEngine._slide_window
    monkeypatch.setattr(ContinuousBatchingEngine, "_slide_window",
                        lambda self, i, s, lo: real(self, i, s, lo + 8))
    line, _ = run_cell(load_harness(mixed_checkout), capsys, "tiny.serve-mixed")
    assert line["correct"] is False
    assert line["checks"]["served_gap"]["value"] > line["checks"]["served_gap"]["limit"]


# -- the calibration judges by the cell's own limits ---------------------------------

def test_calibration_puts_every_reading_through_the_cells_limits(mixed_checkout, capsys, monkeypatch, tmp_path):
    """The program's window reads ``correct`` true and the planted fault's false
    through ``check``'s limits and the harness's comparison; the control's
    numbers are judged the same way, and a kept file is judged again by
    ``--replay`` without a device."""
    import importlib.util
    import sys
    from conftest import fake_devices
    harness = load_harness(mixed_checkout)
    monkeypatch.setattr(harness, "find_devices", fake_devices)
    for name in ("calibrate", "calibrate_cohere2_moe"):
        sys.modules.pop(name, None)
    spec = importlib.util.spec_from_file_location(
        "calibrate_cohere2_moe", str(mixed_checkout / "benchmarks" / "calibrate_cohere2_moe.py"))
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    kept = tmp_path / "positions.jsonl"

    def lines(*argv):
        monkeypatch.setattr(sys, "argv", ["calibrate_cohere2_moe.py", "tiny.serve-mixed", *argv])
        assert cal.main() == 0
        return [json.loads(l) for l in capsys.readouterr().out.splitlines()
                if l.startswith("{") and not l.startswith('{"note"')]

    program, fault = lines("--seeds", "3000000007", "--seconds", "2", "--control", "1", "--fault", "1",
                           "--out", str(kept))
    limits = TINY_CELL["check"]["limits"]
    assert program["correct"] is True and set(program["checks"]) == set(limits)
    assert {k: c["limit"] for k, c in program["control_checks"].items()} == limits
    assert program["control_correct"] == all(c["value"] <= c["limit"] for c in program["control_checks"].values())
    assert fault["fault"] and fault["correct"] is False
    assert fault["checks"]["served_gap"]["value"] > limits["served_gap"]
    again, = lines("--replay", str(kept))
    assert again["correct"] is True and again["control_correct"] == program["control_correct"]
    assert again["checks"]["served_gap"]["value"] == pytest.approx(program["checks"]["served_gap"]["value"], abs=1e-6)
