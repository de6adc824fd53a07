"""The ``serve_gigachat35`` driver end to end at a tiny size on the CPU: a
throw-away configuration and cell as new files in a temporary copy, a traced
run on the recorded trace, a program that lacks the architecture, and the two
planted faults, which have to read ``correct`` false."""

import json
import os

import pytest

from conftest import BENCH, load_harness
from test_rehearsal import CONTRACT_KEYS, run_cell

TINY_CELL = {
    "name": "tiny.serve-reason", "config": "tiny-gigachat35", "traffic_name": "serve-reason",
    "driver": "serve_gigachat35", "chips": 1, "why": "throw-away cell of the tests",
    "engine": {"slots": 4, "buf_len": 128, "page_tokens": 4, "pool_pages": 0,
               "prefill_chunk_tokens": 32, "adapter_slots": 4},
    "traffic": {"callers": 6, "requests": 24, "block": 6,
                "prompt": {"lo": 40, "hi": 80}, "answer": {"lo": 10, "hi": 30},
                "adapters": {"count": 3, "power_a": 1.0}, "stagger_first": 6, "ramp_seconds": 0.5},
    "trace_seconds": 1,
    "check": {"sample": 6, "answer_tail": 30, "near_tie_margin": 1e-6, "state_slots": 3,
              "limits": {"served_gap_q99": 1e-3, "served_gap_q90": 1e-3, "state_gap": 1e-4,
                         "near_tie_share": 0.05, "unanswered": 0, "short_answers": 0}},
}


@pytest.fixture
def reason_checkout(checkout):
    bench = checkout / "benchmarks"
    with open(os.path.join(BENCH, "tests", "tiny_gigachat35.json")) as f:
        (bench / "configs" / "tiny-gigachat35.json").write_text(f.read())
    (bench / "workloads" / "tiny.serve-reason.json").write_text(json.dumps(TINY_CELL))
    return checkout


def notes_of(out):
    return [json.loads(l)["note"] for l in out.out.splitlines() if l.startswith('{"note"')]


def test_serve_gigachat35_end_to_end(reason_checkout, capsys):
    line, out = run_cell(load_harness(reason_checkout), capsys, "tiny.serve-reason")
    assert list(line) == CONTRACT_KEYS + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"served_gap_q99", "served_gap_q90", "near_tie_share",
                                   "unanswered", "short_answers", "state_gap"}
    print("sound state_gap", line["checks"]["state_gap"]["value"])
    assert line["checks"]["state_gap"]["value"] < 1e-5
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    window = next(n for n in notes_of(out) if "ticks_in_window" in n)
    kv = window["kv"]
    # one latent layer (16 + 8 numbers, padded to 128, float32); four linear layers of
    # (4 slots + the trash row) x (4 heads x 8 x 8 float32 + 3 rows x 64 float32)
    assert kv["kv_bytes_per_token"] == 128 * 4
    assert kv["state_rows"] == 5 and kv["state_bytes"] == 4 * 5 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    assert kv["expert_pairs"] > 0 and window["experts_hit_mean"] > 0
    assert 0 < window["live_lanes_mean"] <= 4 and "cut_at_close" in window
    checked = next(n for n in notes_of(out) if "checked_tokens" in n)
    assert checked["served_gap"] < 1e-3 and checked["checked_tokens"] > 0
    assert checked["state_slots_read"] == 3 and min(checked["state_positions"]) >= 40


def test_traced_run_reads_the_new_layer_metrics(reason_checkout, capsys, monkeypatch):
    bench = reason_checkout / "benchmarks"
    for path in (bench / "layer_metrics").glob("*.gc35.json"):
        m = json.loads(path.read_text())
        m["workloads"] = m["workloads"] + ["tiny.serve-reason"]
        path.write_text(json.dumps(m))
    harness = load_harness(reason_checkout)
    from jax.profiler import ProfileData
    from readers import xplane
    with open(os.path.join(BENCH, "tests", "small_trace.textproto")) as f:
        text = "\n".join(line.split("#")[0] for line in f.read().splitlines())
    monkeypatch.setattr(xplane.Trace, "from_dir", classmethod(
        lambda cls, d: cls(ProfileData.from_text_proto(text))))
    line, _ = run_cell(harness, capsys, "tiny.serve-reason", trace=1)
    assert line["correct"] is True, line["checks"]
    got = set(line["metrics"])
    # the recorded trace has no such program and no map: the rooflines and the module
    # times find nothing and are left out; the spans' readers and the host's read
    assert {"serve_step_mfu.gc35", "device_idle_pct.gc35", "state_carried_chunk_share.gc35",
            "expert_pairs_per_tick.gc35", "expert_load_max.gc35", "expert_tiles_per_tick.gc35",
            "state_held_lanes_per_tick.gc35", "tick_ahead_share.gc35", "tokens_per_tick.gc35",
            "prefill_slots_per_iter.gc35", "iter_host_ms.gc35", "chunk_host_ms.gc35",
            "tick_readback_idle_ms.gc35"} <= got
    assert not {"decode_tick_roofline.gc35", "prefill_chunk_roofline.gc35", "tick_linear_ms.gc35",
                "linear_state_roofline.gc35", "attn_read_roofline.gc35", "chunk_experts_ms.gc35",
                "chunk_expert_gather_ms.gc35"} & got
    assert 0 < line["metrics"]["serve_step_mfu.gc35"]["value"] < 100
    # prompts of 40-80 tokens in chunks of 32: two or three chunks, all but the first carry
    assert 0.4 < line["metrics"]["state_carried_chunk_share.gc35"]["value"] < 0.7


def test_a_program_without_the_architecture_fails_at_once(reason_checkout, capsys, monkeypatch):
    """The parent's ``config_from_published`` passes the family's keys over and
    builds latent layers only: the cell says so and prints no result."""
    from fedml_tpu.llm import model as M
    real = M.config_from_published

    def parents(published):
        keep = {k: v for k, v in published.items()
                if not (k.startswith("linear_") or k in (
                    "full_attention_layers", "gated_attention", "swiglu_limit", "norm_type",
                    "layernorm_type", "layernorm_gating_weight", "num_nextn_predict_layers"))}
        return real(keep)

    monkeypatch.setattr(M, "config_from_published", parents)
    harness = load_harness(reason_checkout)
    from conftest import fake_devices
    rc = harness.main(["--workload", "tiny.serve-reason", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], find=fake_devices)
    out = capsys.readouterr()
    assert rc == 2 and "cannot run this configuration" in out.err and out.out.strip() == ""


# -- the timed path broken underneath: `correct` has to come out false ----------

def _faults(checkout):
    import importlib.util
    import sys
    for name in ("calibrate", "calibrate_cohere2_moe", "calibrate_lfm2_moe", "calibrate_gigachat35"):
        sys.modules.pop(name, None)
    spec = importlib.util.spec_from_file_location(
        "calibrate_gigachat35", str(checkout / "benchmarks" / "calibrate_gigachat35.py"))
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    return cal


@pytest.mark.parametrize("fault", ["the matrix state kept in bfloat16 between steps",
                                   "the last chunk's state lost on its way to the first tick"])
def test_planted_fault_reads_not_correct(reason_checkout, capsys, fault):
    harness = load_harness(reason_checkout)
    with _faults(reason_checkout).FAULTS[fault]():
        line, _ = run_cell(harness, capsys, "tiny.serve-reason")
    assert line["correct"] is False
    checks = line["checks"]
    print("checks", fault, {k: v["value"] for k, v in checks.items()})
    assert checks["unanswered"]["value"] == 0       # by a gap, not by a count
    if fault.startswith("the matrix state"):        # the state itself, far off
        assert checks["state_gap"]["value"] > 10 * checks["state_gap"]["limit"]
    else:
        assert checks["served_gap_q99"]["value"] > checks["served_gap_q99"]["limit"]


def test_calibration_puts_every_reading_through_the_cells_limits(reason_checkout, capsys, monkeypatch, tmp_path):
    import sys
    from conftest import fake_devices
    harness = load_harness(reason_checkout)
    monkeypatch.setattr(harness, "find_devices", fake_devices)
    cal = _faults(reason_checkout)
    kept = tmp_path / "positions.jsonl"

    def lines(*argv):
        monkeypatch.setattr(sys, "argv", ["calibrate_gigachat35.py", "tiny.serve-reason", *argv])
        assert cal.main() == 0
        return [json.loads(l) for l in capsys.readouterr().out.splitlines()
                if l.startswith("{") and not l.startswith('{"note"')]

    program, *faults = lines("--seeds", "3000000007", "--seconds", "2", "--control", "1", "--witness", "1",
                             "--fault", "1", "--out", str(kept))
    limits = TINY_CELL["check"]["limits"]
    assert program["correct"] is True and set(program["checks"]) == set(limits)
    assert program["control_correct"] is False
    assert [f["fault"] for f in faults] == list(cal.FAULTS) and not any(f["correct"] for f in faults)
    again, = lines("--replay", str(kept))
    assert again["correct"] is True and again["control_correct"] is False
