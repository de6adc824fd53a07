"""The ``serve_lfm2_moe`` driver end to end at a tiny size on the CPU: a
throw-away configuration and cell as new files in a temporary copy, a traced
run on the recorded trace, a program that lacks the architecture, and the
three planted faults, which have to read ``correct`` false."""

import json
import os

import pytest

from conftest import BENCH, load_harness
from test_rehearsal import CONTRACT_KEYS, run_cell

TINY_CELL = {
    "name": "tiny.serve-chat", "config": "tiny-lfm2-moe", "traffic_name": "serve-chat",
    "driver": "serve_lfm2_moe", "chips": 1, "why": "throw-away cell of the tests",
    "engine": {"slots": 4, "buf_len": 96, "page_tokens": 4, "pool_pages": 0,
               "prefill_chunk_tokens": 16, "adapter_slots": 4},
    "traffic": {"callers": 6, "requests": 24, "block": 6,
                "prompt": {"lo": 20, "hi": 60}, "answer": {"lo": 6, "hi": 16},
                "adapters": {"count": 3, "power_a": 1.0}, "stagger_first": 6, "ramp_seconds": 0.5},
    "trace_seconds": 1,
    "check": {"sample": 6, "answer_tail": 16, "near_tie_margin": 1e-6,
              "limits": {"served_gap_q99": 1e-3, "served_gap_q90": 1e-3,
                         "near_tie_share": 0.05, "unanswered": 0, "short_answers": 0}},
}


@pytest.fixture
def chat_checkout(checkout):
    bench = checkout / "benchmarks"
    with open(os.path.join(BENCH, "tests", "tiny_lfm2_moe.json")) as f:
        (bench / "configs" / "tiny-lfm2-moe.json").write_text(f.read())
    (bench / "workloads" / "tiny.serve-chat.json").write_text(json.dumps(TINY_CELL))
    return checkout


def notes_of(out):
    return [json.loads(l)["note"] for l in out.out.splitlines() if l.startswith('{"note"')]


def test_serve_lfm2_moe_end_to_end(chat_checkout, capsys):
    line, out = run_cell(load_harness(chat_checkout), capsys, "tiny.serve-chat")
    assert list(line) == CONTRACT_KEYS + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"served_gap_q99", "served_gap_q90", "near_tie_share",
                                   "unanswered", "short_answers"}
    # the widest gap is read and held to nothing
    assert next(n for n in notes_of(out) if "checked_tokens" in n)["served_gap"] < 1e-3
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    window = next(n for n in notes_of(out) if "ticks_in_window" in n)
    kv = window["kv"]
    # one attention layer of 2 x 2 x 16 float32 numbers a token; four convolution
    # layers of (4 slots + the trash row) x 2 rows x 64 float32 numbers
    assert kv["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert kv["state_rows"] == 5 and kv["state_bytes"] == window["state_bytes"] == 4 * 5 * 2 * 64 * 4
    assert kv["expert_pairs"] > 0 and window["experts_hit_mean"] > 0


def test_traced_run_reads_the_new_layer_metrics(chat_checkout, capsys, monkeypatch):
    bench = chat_checkout / "benchmarks"
    for path in (bench / "layer_metrics").glob("*.lfm2.json"):
        m = json.loads(path.read_text())
        m["workloads"] = m["workloads"] + ["tiny.serve-chat"]
        path.write_text(json.dumps(m))
    harness = load_harness(chat_checkout)
    from jax.profiler import ProfileData
    from readers import xplane
    with open(os.path.join(BENCH, "tests", "small_trace.textproto")) as f:
        text = "\n".join(line.split("#")[0] for line in f.read().splitlines())
    monkeypatch.setattr(xplane.Trace, "from_dir", classmethod(
        lambda cls, d: cls(ProfileData.from_text_proto(text))))
    line, _ = run_cell(harness, capsys, "tiny.serve-chat", trace=1)
    assert line["correct"] is True, line["checks"]
    got = set(line["metrics"])
    # the recorded trace has no such program: the rooflines find nothing and are left out
    assert {"serve_step_mfu.lfm2", "tokens_per_tick.lfm2", "tick_ahead_share.lfm2", "device_idle_pct.lfm2",
            "expert_pairs_per_tick.lfm2", "expert_load_max.lfm2", "state_carried_chunk_share.lfm2",
            "state_held_lanes_per_tick.lfm2"} <= got
    assert not {"decode_tick_roofline.lfm2", "prefill_chunk_roofline.lfm2"} & got
    assert 0 < line["metrics"]["serve_step_mfu.lfm2"]["value"] < 100
    # prompts of 20-60 tokens in chunks of 16: two to four chunks, all but the first carry
    assert 0.4 < line["metrics"]["state_carried_chunk_share.lfm2"]["value"] < 0.8
    assert line["metrics"]["state_held_lanes_per_tick.lfm2"]["value"] >= 0


def test_the_new_readings_find_nothing_in_an_older_program(chat_checkout):
    """A program whose chunks and ticks carry no such arguments (the parent's):
    None, and the metrics are left out of the line."""
    load_harness(chat_checkout)
    import types
    from fedml_tpu import obs
    from readers import span_ratio, spans
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        tracer = obs.get_tracer()
        with tracer.span("serve.chunk", cat="engine", slot=0):
            pass
        with tracer.span("serve.tick", cat="engine", live=3):
            pass
        run = types.SimpleNamespace(window=(0.0, 1e18), host_spans=[], trace=None, clock_offset_ns=None,
                                    note=lambda **kw: None)
        ratio = {"span": "serve.chunk", "over": "state_carried", "under": "state_rows"}
        held = {"kind": "arg_mean", "span": "serve.tick", "arg": "state_held"}
        assert span_ratio.read(ratio, run) is None and spans.read(held, run) is None
        for carried in (0, 1):
            with tracer.span("serve.chunk", cat="engine") as chunk:
                chunk.set(state_rows=1, state_carried=carried)
        assert span_ratio.read(ratio, run) == 0.5
    finally:
        obs.configure(enabled=False)


def test_a_program_without_the_architecture_fails_at_once(chat_checkout, capsys, monkeypatch):
    """The parent's ``LlamaConfig`` knows no ``"conv"`` layer and raises on it:
    the cell then says so and prints no result."""
    from fedml_tpu.llm import model as M

    def parents(published):
        raise ValueError("layer_types: one of 'full_attention' and 'sliding_attention'")

    monkeypatch.setattr(M, "config_from_published", parents)
    harness = load_harness(chat_checkout)
    from conftest import fake_devices
    rc = harness.main(["--workload", "tiny.serve-chat", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], find=fake_devices)
    out = capsys.readouterr()
    assert rc == 2 and "cannot run this configuration" in out.err and out.out.strip() == ""


# -- the timed path broken underneath: `correct` has to come out false ----------

def _faults(checkout):
    import importlib.util
    import sys
    for name in ("calibrate", "calibrate_cohere2_moe", "calibrate_lfm2_moe"):
        sys.modules.pop(name, None)
    spec = importlib.util.spec_from_file_location(
        "calibrate_lfm2_moe", str(checkout / "benchmarks" / "calibrate_lfm2_moe.py"))
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    return cal


@pytest.mark.parametrize("fault", [
    "the final chunk's state not handed to the first tick",
    "a tick that does not write its lanes' rows back",
    "the bias left out of the selection"])
def test_planted_fault_reads_not_correct(chat_checkout, capsys, fault):
    harness = load_harness(chat_checkout)
    with _faults(chat_checkout).FAULTS[fault]():
        line, _ = run_cell(harness, capsys, "tiny.serve-chat")
    assert line["correct"] is False
    gap = line["checks"]["served_gap_q99"]      # by a gap, not by a count: the answers came whole
    assert gap["value"] > gap["limit"] and line["checks"]["unanswered"]["value"] == 0


# -- the calibration judges by the cell's own limits ---------------------------------

def test_calibration_puts_every_reading_through_the_cells_limits(chat_checkout, capsys, monkeypatch, tmp_path):
    import sys
    from conftest import fake_devices
    harness = load_harness(chat_checkout)
    monkeypatch.setattr(harness, "find_devices", fake_devices)
    cal = _faults(chat_checkout)
    kept = tmp_path / "positions.jsonl"

    def lines(*argv):
        monkeypatch.setattr(sys, "argv", ["calibrate_lfm2_moe.py", "tiny.serve-chat", *argv])
        assert cal.main() == 0
        return [json.loads(l) for l in capsys.readouterr().out.splitlines()
                if l.startswith("{") and not l.startswith('{"note"')]

    program, *faults, variant = lines("--seeds", "3000000007", "--seconds", "2", "--control", "1", "--witness", "1",
                                      "--fault", "1", "--variant", "1", "--out", str(kept))
    limits = TINY_CELL["check"]["limits"]
    assert program["correct"] is True and set(program["checks"]) == set(limits)
    assert {k: c["limit"] for k, c in program["control_checks"].items()} == limits
    assert program["control_correct"] is False
    assert len(program["bias_changed_share"]) == 4 and max(program["bias_changed_share"]) > 0
    assert [f["fault"] for f in faults] == list(cal.FAULTS) and not any(f["correct"] for f in faults)
    # the reference in bfloat16 departs from itself in float32, by less than in int8; a sound program
    # with the router's product in float32 (on the CPU: what it was) reads what the program read
    assert 0 < program["witness_checks"]["served_gap_q99"]["value"] < program["control_checks"]["served_gap_q99"]["value"]
    assert program["differ_share"] == 0 < program["witness_differ_share"]
    assert variant["variant"] == "the router's product in float32" and variant["correct"] is True
    again, = lines("--replay", str(kept))
    assert again["correct"] is True and again["control_correct"] is False
    assert again["checks"]["served_gap_q99"]["value"] == pytest.approx(
        program["checks"]["served_gap_q99"]["value"], abs=1e-6)
