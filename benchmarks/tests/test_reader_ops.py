"""``readers/ops.py`` on a trace written by hand (two programs that share
instruction names, a ``while`` over its body, an operation outside every
program, one in no map) and ``rooflines/kernels.py`` against hand counts."""

import json
import os
import types

import pytest

from conftest import BENCH

from readers import ops, xplane
from rooflines import kernels

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def cell(name):
    with open(os.path.join(BENCH, "workloads", f"{name}.json")) as f:
        return json.load(f)


# -- a trace by hand ---------------------------------------------------------------

def event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines.items()])


def hlo(name, op="fusion"):
    return f"%{name} = f32[8]{{0:T(128)}} {op}(%p.1)"


def trace():
    """Window 1,000..11,000 ns.  ``step`` runs twice (2,000..4,000 and
    6,000..8,000), ``chunk`` once (4,000..6,000), one ``step`` before the
    window (0..900) and a program that registered nothing (9,000..9,500)."""
    modules = [event("jit_paged_step_mt(11)", 0, 900),
               event("jit_paged_step_mt(11)", 2000, 2000), event("jit_paged_chunk(12)", 4000, 2000),
               event("jit_paged_step_mt(11)", 6000, 2000), event("jit_convert_element_type(3)", 9000, 500)]
    step = lambda t: [event(hlo("fusion.12"), t, 500),                     # attention: 500
                      event(hlo("while.1", "while"), t + 600, 1000),       # self 1000 - 400 - 300
                      event(hlo("fusion.7"), t + 700, 400),                # the body's: experts
                      event(hlo("copy-done.3", "copy-done"), t + 1200, 300)]   # the body's: no path
    chunk = [event(hlo("fusion.12"), 4000, 1500),                          # the same name: experts here
             event(hlo("fusion.99"), 5600, 200)]                           # in no map
    outside = [event(hlo("fusion.12"), 100, 700),                          # a run before the window
               event(hlo("convert.1", "convert"), 9000, 500),              # a program with no map
               event(hlo("fusion.5"), 10000, 250)]                         # in no program at all
    return xplane.Trace(types.SimpleNamespace(planes=[
        plane("/host:CPU", python=[event(xplane.WINDOW, 1000, 10000)]),
        plane("/device:TPU:0", **{"XLA Modules": modules,
                                  "XLA Ops": outside[:1] + step(2000) + chunk + step(6000) + outside[1:]})]))


def row(path, kernel="", phase="forward", op="fusion"):
    return {"path": path, "phase": phase, "kernel": kernel, "op": op}


MAPS = {
    "paged_step_mt": {"fusion.12": row("layer_3/attention._paged_attend"),
                      "while.1": row("", op="while"),
                      "fusion.7": row("layer_5/moe_mlp/gated_matmul", "gated_matmul", op="custom-call"),
                      "copy-done.3": row("", op="copy-done"), "fusion.99": row("lm_head")},
    "paged_chunk": {"fusion.12": row("layer_5/moe_mlp/gated_matmul", "gated_matmul", "recompute")},
}


def test_events_are_attributed_by_the_program_that_contains_them():
    found = ops.join(trace(), MAPS.get)
    p = found["programs"]
    assert set(p) == {"paged_step_mt", "paged_chunk", "convert_element_type"}
    assert p["paged_step_mt"]["runs"] == 2 and p["paged_step_mt"]["module_ns"] == 4000
    # a while's self time is its length less its body's operations
    assert p["paged_step_mt"]["ops"] == {"fusion.12": 1000, "while.1": 600, "fusion.7": 800,
                                         "copy-done.3": 600}
    # fusion.12 of the chunk is another instruction than the step's
    assert p["paged_chunk"]["ops"] == {"fusion.12": 1500} and p["paged_chunk"]["runs"] == 1
    assert p["convert_element_type"]["map"] is None
    # in the window: 3000 + 1700 + 500 + 250; unmatched: fusion.99, convert.1, fusion.5
    assert found["unmatched_pct"] == pytest.approx(100 * (200 + 500 + 250) / 5450)


def test_module_ms_and_the_table():
    found = ops.join(trace(), MAPS.get)
    ms = lambda **args: ops.matched_ms(found, args)
    assert ms(program="paged_step*", path=["layer_*/attention._paged_attend*"]) == pytest.approx(500e-6)
    assert ms(program="paged_step*", kernel=["gated_matmul", "grouped_matmul"]) == pytest.approx(400e-6)
    assert ms(program="paged_chunk", kernel=["gated_matmul"]) == pytest.approx(1500e-6)
    assert ms(program="paged_chunk", phase=["recompute"]) == pytest.approx(1500e-6)
    assert ms(program="paged_step*", path=[""], kernel=[""]) == pytest.approx(600e-6)
    assert ms(program="paged_step*", path=["layer_*"], not_path=["*/moe_mlp*"]) == pytest.approx(500e-6)
    assert ms(program="paged_step*", path=["lm_head"]) is None          # in the map, never ran
    assert ms(program="round_fn", path=["*"]) is None
    table = ops.by_module(found["programs"])
    assert set(table) == {"paged_step_mt", "paged_chunk"}
    step = table["paged_step_mt"]
    assert step["runs"] == 2 and step["module_ms"] == pytest.approx(2000e-6)
    assert step["groups"] == pytest.approx({"(no path)": 600e-6, "layer_*/attention._paged_attend": 500e-6,
                                            "layer_*/moe_mlp/gated_matmul": 400e-6})
    assert step["ops_ms"] == pytest.approx(1500e-6)
    assert list(table["paged_chunk"]["groups"]) == ["layer_*/moe_mlp/gated_matmul [recompute]"]


def run_of(notes):
    return types.SimpleNamespace(trace=trace(), cfg={}, cell={}, counters={"experts_hit_mean": 2.0},
                                 peak=PEAK, note=lambda **kw: notes.append(kw))


def test_read_joins_once_a_run_and_notes_the_table(monkeypatch):
    from fedml_tpu.obs import programs
    asked = []
    monkeypatch.setattr(programs, "op_modules", lambda name: asked.append(name) or MAPS.get(name))
    monkeypatch.setattr(kernels, "expert_gmm_mla_moe", lambda cfg, cell, counters, peak: 100e-9)
    notes = []
    run = run_of(notes)
    args = {"kind": "module_ms", "program": "paged_step*", "kernel": ["gated_matmul"]}
    assert ops.read(args, run) == pytest.approx(400e-6)
    share = ops.read({**args, "kind": "kernel_roofline_pct", "roofline": "kernels.expert_gmm_mla_moe"}, run)
    assert share == pytest.approx(25.0)                       # 100 ns over 400 ns
    assert sorted(asked) == ["convert_element_type", "paged_chunk", "paged_step_mt"]
    assert len(notes) == 1 and set(notes[0]) == {"by_module", "unmatched_pct", "map_s", "join_s"}
    with pytest.raises(ValueError, match="unknown kind"):
        ops.read({**args, "kind": "nothing"}, run)


def test_a_program_that_publishes_no_map_gives_none_and_no_note(monkeypatch):
    from fedml_tpu.obs import programs
    monkeypatch.setattr(programs, "op_modules", lambda name: None)
    notes = []
    run = run_of(notes)
    args = {"kind": "module_ms", "program": "paged_step*", "kernel": ["gated_matmul"]}
    assert ops.read(args, run) is None and ops.read(args, run) is None and not notes
    # nor where the program's package has no such module at all (the parent's checkout)
    import sys
    monkeypatch.setitem(sys.modules, "fedml_tpu.obs.programs", None)
    run = run_of(notes)
    assert ops.read(args, run) is None and not notes


@pytest.mark.parametrize("name,want", [
    ("%fusion.9 = f32[32,128]{1,0:T(8,128)} fusion(%p.0), kind=kOutput", "fusion.9"),
    ("%latent_attention.14 = bf16[1,64,512,512]{3,2,1,0} custom-call(%a)", "latent_attention.14"),
    ("copy-done.3", "copy-done.3"),
])
def test_instruction_of_a_trace_event(name, want):
    assert ops.instruction(name) == want


@pytest.mark.parametrize("name,want", [("jit_paged_step_mt(8375309)", "paged_step_mt"),
                                       ("jit_round_fn", "round_fn"), ("paged_chunk(7)", "paged_chunk")])
def test_program_of_a_module_event(name, want):
    assert ops.program_name(name) == want


# -- the kernels' least seconds against hand counts ----------------------------------

def test_latent_read_at_the_latent_cells_sizes():
    cfg = config("a.x-k1-ep16-d7")
    # a row of latent is 512 + 64 numbers of bf16 = 1,152 B a token and layer, 7 layers
    assert kernels.mla_moe.latent_bytes_per_token(cfg) == 7 * 1152
    # PERF.md section 5: 117.5k live tokens x 1,280 B (the pool's row of 640, padded to whole
    # lanes) = 150 MB a layer = 184 us at 819 GB/s; without the padding 135 MB = 165 us
    assert 117_500 * 1280 / 819e9 == pytest.approx(184e-6, rel=0.01)
    least = kernels.attn_read_mla_moe(cfg, {}, {"live_kv_tokens_mean": 117_500}, PEAK)
    assert least == pytest.approx(7 * 117_500 * 1152 / 819e9)
    assert least / 7 == pytest.approx(165e-6, rel=0.01)
    assert kernels.attn_read_mla_moe(cfg, {}, {}, PEAK) == 0.0


@pytest.mark.parametrize("family,name,layers,params", [
    # 3 matrices of hidden x expert width, bf16: a.x-k1 7168 x 2048 over 6 sparse layers,
    # Command A+ 4096 x 4096 over 4, LFM2 2048 x 1792 over 12
    ("mla_moe", "a.x-k1-ep16-d7", 6, 3 * 7168 * 2048),
    ("cohere2_moe", "command-a-plus-ep8-d4", 4, 3 * 4096 * 4096),
    ("lfm2_moe", "lfm2-8b-a1b-d13", 12, 3 * 2048 * 1792),
])
def test_hit_experts_bytes(family, name, layers, params):
    fn = getattr(kernels, f"expert_gmm_{family}")
    got = fn(config(name), {}, {"experts_hit_mean": 10.0}, PEAK)
    assert got == pytest.approx(2 * layers * 10.0 * params / 819e9)
    assert fn(config(name), {}, {}, PEAK) == 0.0


def test_hit_experts_at_the_latent_cells_reading():
    # 10.8 of 12 held experts hit a layer and tick: 5.7 GB of weights, 7.0 ms at the bandwidth
    got = kernels.expert_gmm_mla_moe(config("a.x-k1-ep16-d7"), {}, {"experts_hit_mean": 10.8}, PEAK)
    assert got == pytest.approx(6.97e-3, rel=0.01)


def test_live_keys_and_values_by_pool_kind():
    cfg = config("command-a-plus-ep8-d4")
    # K and V of 8 heads of 128 in bf16: 4,096 B a token and layer; 1 full and 3 window layers
    counters = {"live_kv_tokens_mean": 180_000, "live_window_tokens_mean": 90_000}
    got = kernels.attn_read_cohere2_moe(cfg, {}, counters, PEAK)
    assert got == pytest.approx(4096 * (180_000 + 3 * 90_000) / 819e9)
    assert kernels.attn_read_cohere2_moe(cfg, {}, {"live_kv_tokens_mean": 1.0}, PEAK) == 0.0


def test_round_attention_flops():
    cfg, c = config("mistral-7b-v0.3-d12"), cell("fedlora-round.mistral-7b-d12")
    # 4 clients x 2 steps x batch 2 = 16 sequences of 1,024; forward 2 * 32 heads * 128 * 1024^2
    # a layer, three times that with the backward pass, 12 layers: 4.95 TFLOP a round
    flops = 16 * 12 * 3 * 2 * 32 * 128 * 1024 * 1024
    assert kernels.round_attention(cfg, c, {}, PEAK) == pytest.approx(flops / 197e12)
    assert flops == pytest.approx(4.95e12, rel=0.01)


def test_every_ops_metric_names_a_function_that_exists():
    for path in sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))):
        with open(os.path.join(BENCH, "layer_metrics", path)) as f:
            m = json.load(f)
        if m["reader"] != "ops":
            continue
        assert m["args"]["kind"] in ("module_ms", "kernel_roofline_pct") and m["args"]["program"]
        assert set(m["args"]) <= {"kind", "program", "path", "not_path", "kernel", "phase", "roofline"}
        if m["args"]["kind"] == "kernel_roofline_pct":
            assert m["unit"] == "%" and m["name"].split(".")[0].endswith("_roofline")
            assert callable(xplane._roofline(m["args"]["roofline"]))
