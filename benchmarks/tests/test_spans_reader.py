"""``readers/spans.py`` on hand-made tracer events (every expected number is
worked out from the times written here), and the six metrics that read the
program's spans, through their own files, on throw-away cells of both drivers
at tiny size against the recorded trace."""

import json
import os
import types

import pytest

from conftest import BENCH, fake_devices, load_harness
from readers import spans

ORIGIN = 100.0          # the tracer's origin on time.perf_counter, seconds
TID = 7


def B(name, ts, tid=TID, **args):
    return {"name": name, "ph": "B", "ts": ts, "tid": tid, "cat": "engine",
            "args": {"span_id": f"{name}@{ts}", **args}}


def E(name, ts, tid=TID, **args):
    ev = {"name": name, "ph": "E", "ts": ts, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def tick(ts, iter_, tokens, readback_us):
    """One iteration from ``ts`` (µs): iter [ts, ts+1000], tick [ts+100,
    ts+900] with dispatch [ts+200, ts+300] and a read-back from ts+300."""
    it, tk = f"serve.iter@{ts}", f"serve.tick@{ts + 100}"
    return [B("serve.iter", ts, iter=iter_),
            B("serve.tick", ts + 100, parent=it, live=4),
            B("serve.tick.dispatch", ts + 200, parent=tk), E("serve.tick.dispatch", ts + 300),
            B("serve.tick.readback", ts + 300, parent=tk),
            E("serve.tick.readback", ts + 300 + readback_us),
            E("serve.tick", ts + 900, tokens=tokens), E("serve.iter", ts + 1000)]


#: three iterations at 0, 2000 and 4000 µs; a counter; a request's lifetime
#: on a synthetic lane; an iteration the tracer had to close itself
EVENTS = (tick(0, 1, 4, 100) + tick(2000, 2, 3, 500) + tick(4000, 3, 2, 200) + [
    {"name": "serve.queue_depth", "ph": "C", "ts": 4500, "tid": TID, "args": {"value": 0}},
    B("serve.request", 0, tid=-16), E("serve.request", 4800, tid=-16),
    B("serve.iter", 6000, iter=4),
    {"name": "serve.iter", "ph": "E", "ts": 9000, "tid": TID, "args": {"synthesized_end": True}}])
EVENTS.sort(key=lambda e: e["ts"])


class Tracer:
    origin_s = ORIGIN

    def __init__(self, events):
        self._events = events

    def events(self):
        return self._events


@pytest.fixture
def run(monkeypatch):
    import fedml_tpu.obs
    monkeypatch.setattr(fedml_tpu.obs, "get_tracer", lambda: Tracer(EVENTS))
    noted = {}
    return types.SimpleNamespace(window=(ORIGIN, ORIGIN + 0.0045), trace=None,
                                 clock_offset_ns=None, noted=noted,
                                 note=lambda **kw: noted.update(kw))


def test_pairs_spans_on_the_tracers_clock():
    rows = spans.paired(EVENTS, ORIGIN)
    assert len(rows) == 3 * 4 + 1           # the self-closed iteration is left out
    first = next(r for r in rows if r["name"] == "serve.tick")
    assert first["t0"] == pytest.approx(ORIGIN + 100e-6)
    assert first["t1"] == pytest.approx(ORIGIN + 900e-6)
    assert first["args"] == {"live": 4, "tokens": 4}        # both events' args
    assert first["parent"] == "serve.iter@0" and first["id"] == "serve.tick@100"


def test_arg_mean_keeps_the_spans_that_end_inside_the_window(run):
    args = {"kind": "arg_mean", "span": "serve.tick", "arg": "tokens"}
    # the third tick ends at 4900 µs, past the window's close at 4500
    assert spans.read(args, run) == pytest.approx((4 + 3) / 2)
    run.window = (ORIGIN, ORIGIN + 1.0)
    assert spans.read(args, run) == pytest.approx((4 + 3 + 2) / 3)
    assert spans.read({**args, "arg": "absent"}, run) is None
    assert spans.read({**args, "span": "no.such.span"}, run) is None


def test_mean_ms_sums_the_named_spans_per_parent(run):
    run.window = (ORIGIN, ORIGIN + 1.0)
    args = {"kind": "mean_ms", "per": "serve.iter",
            "names": ["serve.tick.dispatch", "serve.tick.readback"]}
    # per iteration 100 µs of dispatch and 100, 500, 200 µs of read-back
    assert spans.read(args, run) == pytest.approx((300 + 800) / 3 / 1e3)
    assert spans.read({**args, "names": ["absent"]}, run) is None
    assert spans.read({**args, "per": "absent"}, run) is None


def test_nothing_to_read_gives_none_and_no_note(run, monkeypatch):
    import fedml_tpu.obs
    args = {"kind": "arg_mean", "span": "serve.tick", "arg": "tokens",
            "notes": {"longest": {"span": "serve.tick.readback", "index": "iter"}}}
    run.window = (ORIGIN + 50.0, ORIGIN + 51.0)               # no span ends there
    assert spans.read(args, run) is None and run.noted == {}
    monkeypatch.setattr(fedml_tpu.obs, "get_tracer", lambda: Tracer([]))
    assert spans.read(args, run) is None
    # a program from before the tracer had a public origin has no such spans
    monkeypatch.setattr(fedml_tpu.obs, "get_tracer", lambda: object())
    assert spans.read(args, run) is None and run.noted == {}


def test_longest_names_the_iteration(run):
    run.window = (ORIGIN, ORIGIN + 1.0)
    spans.read({"kind": "arg_mean", "span": "serve.tick", "arg": "tokens",
                "notes": {"longest": {"span": "serve.tick.readback", "index": "iter"}}}, run)
    assert run.noted["longest"] == {"span": "serve.tick.readback",
                                    "ms": pytest.approx(0.5), "iter": 2}


def test_clock_check_counts_an_event_outside_its_span(run):
    off = 5000.0           # trace time minus perf_counter time, ns
    at = lambda us: (ORIGIN + us / 1e6) * 1e9 + off
    host = [("PjitFunction(paged_step_mt)", at(50), at(90)),        # before the first span: not judged
            ("PjitFunction(paged_step_mt)", at(210), at(290)),      # inside the first
            ("PjitFunction(paged_step_mt)", at(2299), at(2400)),    # inside the second
            ("PjitFunction(paged_step_mt)", at(3000), at(3100)),    # 700 µs past the second
            ("PjitFunction(paged_step_mt)", at(9000), at(9100)),    # after the last span: not judged
            ("PjitFunction(gather_row)", at(1500), at(1600))]       # another program
    run.window = (ORIGIN, ORIGIN + 1.0)
    run.clock_offset_ns = off
    run.trace = types.SimpleNamespace(host_events=host, devices={},
                                      gaps=lambda: [])
    args = {"kind": "arg_mean", "span": "serve.tick", "arg": "tokens",
            "notes": {"clock_check": {"events": "*paged_step*", "span": "serve.tick.dispatch"}}}
    spans.read(args, run)
    assert run.noted["clock_check"] == {"events": 3, "outside": 1,
                                        "worst_us": pytest.approx(700.0, abs=1e-3)}
    # with the anchor off by 200 µs the spans lie at 400, 2400 and 4400 µs:
    # the event at 210 comes before the first, the other two begin outside
    run.clock_offset_ns = off + 200e3
    spans.read(args, run)
    assert run.noted["clock_check"] == {"events": 2, "outside": 2,
                                        "worst_us": pytest.approx(500.0, abs=1e-3)}


def test_idle_gaps_are_split_among_the_innermost_spans_of_the_thread(run):
    at = lambda us: (ORIGIN + us / 1e6) * 1e9
    # gaps: inside the first read-back; from the first tick's last 50 µs over
    # the end of its iteration to 200 µs past it (only the request's lifetime
    # is open there, on its synthetic lane: another thread); from the second
    # read-back's last 40 µs into the tick after it
    gaps = [(at(320), at(380)), (at(850), at(1200)), (at(2760), at(2830))]
    run.window = (ORIGIN, ORIGIN + 1.0)
    run.clock_offset_ns = 0.0
    run.trace = types.SimpleNamespace(host_events=[], devices={"d": {}}, gaps=lambda: gaps)
    spans.read({"kind": "arg_mean", "span": "serve.tick", "arg": "tokens",
                "notes": {"idle_by_span": "serve.iter"}}, run)
    rows = dict(map(tuple, run.noted["idle_by_span"]))
    assert rows == {"serve.tick.readback": pytest.approx(100e-6),
                    "serve.tick": pytest.approx(80e-6),
                    "serve.iter": pytest.approx(100e-6),
                    "(no span)": pytest.approx(200e-6)}
    assert sum(rows.values()) == pytest.approx(sum(b - a for a, b in gaps) / 1e9)
    # one thread's spans cut into stretches that do not overlap
    cut = spans.stretches(spans.paired(tick(0, 1, 4, 100), 0.0))
    assert [(round(a * 1e6), round(b * 1e6), n) for a, b, n in cut] == [
        (0, 100, "serve.iter"), (100, 200, "serve.tick"), (200, 300, "serve.tick.dispatch"),
        (300, 400, "serve.tick.readback"), (400, 900, "serve.tick"), (900, 1000, "serve.iter")]


def test_span_ms_gives_each_name_its_length_and_its_self_time(run):
    run.window = (ORIGIN, ORIGIN + 1.0)
    spans.read({"kind": "arg_mean", "span": "serve.tick", "arg": "tokens",
                "notes": {"span_ms": True}}, run)
    rows = {name: (n, mean, own) for name, n, mean, own in run.noted["span_ms"]}
    assert set(rows) == {"serve.iter", "serve.tick", "serve.tick.dispatch",
                         "serve.tick.readback"}       # the request's lane is left out
    assert rows["serve.iter"] == (3, pytest.approx(1.0), pytest.approx(0.2))
    # a tick of 800 µs holds 100 µs of dispatch and 100, 500, 200 µs of read-back
    assert rows["serve.tick"] == (3, pytest.approx(0.8), pytest.approx((700 - 800 / 3) / 1e3))
    assert rows["serve.tick.readback"] == (3, pytest.approx(0.8 / 3), pytest.approx(0.8 / 3))
    assert run.noted["span_ms"][0][0] == "serve.tick"          # the most self time first


# -- the six metric files on throw-away cells ----------------------------------------

NEW = {"tiny.serve": ["iter_host_ms.sat", "tick_readback_idle_ms.sat",
                      "chunk_host_ms.sat", "tokens_per_tick.sat"],
       "tiny.fedround": ["round_driver_host_ms", "round_prepare_ms"]}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_new_metric_files_read_a_value_on_a_throw_away_cell(
        checkout, capsys, monkeypatch, cell):
    bench = checkout / "benchmarks"
    for name in NEW[cell]:
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
            m = json.load(f)
        m.update(name=f"tiny.{name}", workloads=[cell])       # one more new file
        (bench / "layer_metrics" / f"tiny.{name}.json").write_text(json.dumps(m))
    harness = load_harness(checkout)
    from jax.profiler import ProfileData
    from readers import xplane
    with open(os.path.join(BENCH, "tests", "small_trace.textproto")) as f:
        text = "\n".join(line.split("#")[0] for line in f.read().splitlines())
    # the CPU leaves no device plane: the recorded trace stands in for it
    monkeypatch.setattr(xplane.Trace, "from_dir", classmethod(
        lambda cls, d: cls(ProfileData.from_text_proto(text))))
    rc = harness.main(["--workload", cell, "--seed", "3000000011", "--seconds", "2",
                       "--trace", "1"], find=fake_devices)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    lines = [json.loads(l) for l in out.out.strip().splitlines() if l.startswith("{")]
    line = lines[-1]
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {f"tiny.{name}" for name in NEW[cell]}
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(v > 0 for v in values.values()), values
    notes = {k: v for l in lines[:-1] for k, v in l.get("note", {}).items()}
    assert notes["clock_check"]["outside"] == 0
    if cell == "tiny.serve":
        assert 0 < values["tiny.tokens_per_tick.sat"] <= 4              # the cell's slots
        assert values["tiny.tick_readback_idle_ms.sat"] < values["tiny.iter_host_ms.sat"]
        assert notes["longest"]["span"] == "serve.tick.readback" and notes["longest"]["iter"] > 0
        # every idle second of the trace passed inside a span of the engine's thread or outside them
        idle = dict(map(tuple, notes["idle_by_span"]))
        assert sum(idle.values()) == pytest.approx(
            line["device"]["window_s"] - line["device"]["busy_s"], rel=1e-6)
        assert all(k == "(no span)" or k.startswith("serve.") for k in idle)
        assert not {"serve.request", "serve.queue", "serve.decode"} & set(idle)
    else:
        # the three phases before the dispatch are a part of the whole round
        assert values["tiny.round_prepare_ms"] < values["tiny.round_driver_host_ms"]
