"""The whole command at a tiny size on the CPU, with the harness's look for a
chip lifted here in the test (``run.py`` has no option for it): each driver end
to end, the traced path on the recorded trace, the planted faults, and the
layout of ``BENCHMARK.json``.  The throw-away configuration and cells are new
files in a temporary copy: nothing that is there is edited."""

import copy
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, TINY_CONFIG, TINY_SERVE, fake_devices, load_harness

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cell(harness, capsys, workload, seed=3000000007, trace=0):
    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds", "2",
                       "--trace", str(trace)], find=fake_devices)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    return line, out


def test_fedround_end_to_end(checkout, capsys):
    harness = load_harness(checkout)
    line, out = run_cell(harness, capsys, "tiny.fedround")
    assert list(line) == CONTRACT_KEYS + ["checks"]         # the checks come last
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert line["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # each number compared stands beside its limit at the end of standard error
    tail = out.err.strip().splitlines()[-len(line["checks"]):]
    assert all(re.match(r"check \w+: .* \(limit .*\)", t) for t in tail), tail


def test_serve_end_to_end_and_new_cell_needs_new_files_only(checkout, capsys):
    # a cell of another geometry and mix, added as one more new file
    cell = copy.deepcopy(TINY_SERVE)
    cell["name"] = "tiny.small-pool"
    cell["engine"].update(slots=3, pool_pages=19)
    cell["traffic"].update(callers=4, stagger_first=0)
    (checkout / "benchmarks" / "workloads" / "tiny.small-pool.json").write_text(json.dumps(cell))
    harness = load_harness(checkout)
    for name in ("tiny.serve", "tiny.small-pool"):
        line, _ = run_cell(harness, capsys, name)
        assert list(line) == CONTRACT_KEYS + ["checks"]
        assert line["correct"] is True, line["checks"]
        assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
        assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_run_reads_layer_metrics_from_new_files(checkout, capsys, monkeypatch):
    bench = checkout / "benchmarks"
    for name, reader, args, unit in (
            ("tiny_idle", "xplane", {"kind": "idle_pct"}, "%"),
            ("tiny_host", "xplane", {"kind": "span_minus_busy_ms", "span": "serve.decode"}, "ms"),
            ("tiny_nothing", "xplane", {"kind": "span_minus_busy_ms", "span": "no.such.span"}, "ms")):
        (bench / "layer_metrics" / f"{name}.json").write_text(json.dumps({
            "name": name, "unit": unit, "better": "lower", "source": "device_trace",
            "layer": "test", "moves": "serve_tokens_per_s", "workloads": ["tiny.serve"],
            "reader": reader, "args": args}))
    harness = load_harness(checkout)
    from jax.profiler import ProfileData
    from readers import xplane
    with open(os.path.join(BENCH, "tests", "small_trace.textproto")) as f:
        text = "\n".join(line.split("#")[0] for line in f.read().splitlines())
    # the CPU leaves no device plane: the recorded trace stands in for it
    monkeypatch.setattr(xplane.Trace, "from_dir", classmethod(
        lambda cls, d: cls(ProfileData.from_text_proto(text))))
    line, _ = run_cell(harness, capsys, "tiny.serve", trace=1)
    assert list(line) == CONTRACT_KEYS + ["breakdown", "checks"]
    assert set(line["metrics"]) == {"tiny_idle", "tiny_host"}    # nothing to read: left out
    # the recorded trace's few busy microseconds against the run's own window
    assert 99.99 < line["metrics"]["tiny_idle"]["value"] < 100.0
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert not os.path.exists(checkout / ".bench_trace" / "tiny.serve")


def test_same_seed_same_traffic():
    import traffic
    from drivers import fedround
    mix = TINY_SERVE["traffic"]
    first = lambda seed, n=48: [traffic.Requests(mix, 256, seed)[i] for i in range(n)]
    a = first(2**31 + 11)
    assert a == first(2**31 + 11)
    b = first(5)
    assert a != b
    # another seed: the same sizes and adapters in another order (past the
    # callers the loop starts with, whose answers are cut to a part)
    for what in (lambda r: len(r["prompt_ids"]), lambda r: r["adapter"]):
        assert sorted(map(what, a[:24])) == sorted(map(what, b[:24]))
    assert sorted(r["max_tokens"] for r in a[24:]) == sorted(r["max_tokens"] for r in b[24:])
    assert all(r["max_tokens"] <= 16 for r in a[:6])
    # every block of six holds one size of each sixth of the range
    for rs in (a, b):
        for g in range(0, 24, 6):
            assert sorted((len(r["prompt_ids"]) - 4) * 6 // 45 for r in rs[g:g + 6]) == list(range(6))
    # the set comes round again with token ids of its own: no prompt is sent twice
    assert [len(r["prompt_ids"]) for r in a[24:]] == [len(r["prompt_ids"]) for r in a[:24]]
    assert len({tuple(r["prompt_ids"]) for r in a}) == len(a)

    class Data:
        pass
    t = {"clients_total": 8, "client_rows": [2, 3], "seq_len": 16}
    d1, d2 = Data(), Data()
    fedround._dataset_from_seed(d1, t, 256, 99)
    fedround._dataset_from_seed(d2, t, 256, 99)
    assert (d1.train_x == d2.train_x).all() and (d1.train_y[:, :-1] == d1.train_x[:, 1:]).all()
    assert len({row.tobytes() for row in d1.train_x}) == len(d1.train_x)   # rows all differ


# -- the timed path broken underneath: `correct` has to come out false ----------

def test_fault_state_unchanged(checkout, capsys, monkeypatch):
    from fedml_tpu.llm.fedllm import FedLLMAPI
    real = FedLLMAPI.train_one_round

    def unchanged(self, r):
        before = self.global_lora
        out = real(self, r)
        self.global_lora = before
        return out

    monkeypatch.setattr(FedLLMAPI, "train_one_round", unchanged)
    line, _ = run_cell(load_harness(checkout), capsys, "tiny.fedround")
    assert line["correct"] is False
    assert line["checks"]["dnorm_r1"]["value"] == pytest.approx(1.0, abs=1e-6)


def test_fault_half_of_the_batch_left_out(checkout, capsys, monkeypatch):
    from fedml_tpu.llm.fedllm import FedLLMAPI
    real = FedLLMAPI._build_round_fn

    def build(self):
        f = real(self)
        return lambda base, lora, x, y, *rest: f(base, lora, x[:, :, :1], y[:, :, :1], *rest)

    monkeypatch.setattr(FedLLMAPI, "_build_round_fn", build)
    line, _ = run_cell(load_harness(checkout), capsys, "tiny.fedround")
    assert line["correct"] is False
    failing = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert any(k.startswith("dnorm") for k in failing), line["checks"]


def test_fault_token_altered_where_it_is_produced(checkout, capsys, monkeypatch):
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    real = ContinuousBatchingEngine._emit
    monkeypatch.setattr(ContinuousBatchingEngine, "_emit",
                        lambda self, i, tok: real(self, i, (int(tok) + 1) % 256))
    line, _ = run_cell(load_harness(checkout), capsys, "tiny.serve")
    assert line["correct"] is False
    assert line["checks"]["served_gap"]["value"] > line["checks"]["served_gap"]["limit"]


def test_control_in_lower_precision_is_not_correct(checkout):
    """The reference in int8 in the program's place fails the limits that the
    float32 program of the tiny configuration meets."""
    load_harness(checkout)
    from conftest import TINY_FEDROUND
    from drivers import fedround, serve
    cfg, t = TINY_CONFIG, TINY_FEDROUND["traffic"]
    rng = np.random.default_rng(4)
    tok = rng.integers(1, 256, size=(2, 2, 2, 2, 33), dtype=np.int32)   # rounds, clients, steps, batch
    staged = [(tok[r, ..., :-1], tok[r, ..., 1:], np.ones((2, 2), np.float32),
               np.array([2.0, 3.0], np.float32)) for r in range(2)]
    want = fedround.follow(cfg, t, 17, staged, 2)
    low = fedround.follow(cfg, t, 17, staged, 2, quant="int8")
    numbers = fedround.compare(low, want, 2)
    limits = TINY_FEDROUND["check"]["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers
    # a served request: the token int8 puts first lies below the reference's best
    base, adapters = serve.reference_weights(cfg, 17, ["a00"])
    rec = {"prompt_ids": [int(x) for x in rng.integers(1, 256, size=40)],
           "tokens": [int(x) for x in rng.integers(1, 256, size=12)], "adapter": "a00"}
    gaps = serve.forced(cfg, base, adapters, rec, 96, quant="int8")
    assert gaps["control_gap"] > TINY_SERVE["check"]["limits"]["served_gap"]


# -- the files ---------------------------------------------------------------------

def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert list(b) == ["command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"]
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert name.match(c["name"]) and c["file"].startswith("benchmarks/")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in b["end_to_end"])
    cells = set()
    for w in b["workloads"]:
        with open(os.path.join(BENCH, "workloads", f"{w['name']}.json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and w["config"] in configs
        assert cell["chips"] == w["chips"] == 1 and cell["why"] == w["why"]
        assert cell["traffic_name"] == w["traffic"] and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "drivers", f"{cell['driver']}.py"))
        cells.add(w["name"])
    listed = set()
    for m in b["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics", f"{m['name']}.json")) as f:
            held = json.load(f)
        assert {k: held[k] for k in m} == m
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
        assert os.path.isfile(os.path.join(BENCH, "readers", f"{held['reader']}.py"))
        listed |= set(m["workloads"])
    assert listed == cells                 # every cell reports a per-layer metric
    for cell in cells:                     # and a share of the whole step, named mfu
        assert any("mfu" in m["name"] and cell in m["workloads"] for m in b["per_layer"])


def test_no_result_where_the_program_is_missing(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's paths."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "benchmarks/run.py", "--workload",
                        "fedlora-round.mistral-7b-d12", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
