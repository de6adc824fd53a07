"""Device time by module, the program's side (``fedml_tpu/obs/programs.py``,
ISSUE 39): the ``op_name`` parser on canned optimized-HLO text, the registry
(weak, filled at a program's first launch, nothing compiled until a map is
asked for), and ``program_ops()`` of a tiny engine and a tiny ``FedLLMAPI``."""

import gc
import json
import os
import sys
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.obs import programs

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from readers import ops as bench_ops  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# An optimized module as the chip's compiler prints it, cut to what the parser
# reads: a fused computation and its fusion, a reducer, a Pallas custom call,
# a ``while`` with its body and condition, a backward and a rematerialised
# instruction, a clone the compiler made to rematerialise, and a copy with no
# metadata.
HLO = r'''HloModule jit_paged_step_mt, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%region_0.1 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[]{:T(128)} parameter(0)
  %b.1 = f32[]{:T(128)} parameter(1)
  ROOT %add.9 = f32[]{:T(128)} add(%a.1, %b.1), metadata={op_name="jit(paged_step_mt)/LlamaLM/final_norm/reduce_sum"}
}

%fused_computation.2 (param_0.8: f32[17,8,16], param_1.1: s32[64]) -> f32[64,8,16] {
  %param_0.8 = f32[17,8,16]{2,1,0:T(8,128)} parameter(0)
  %param_1.1 = s32[64]{0:T(128)} parameter(1)
  %mul.3 = f32[17,8,16]{2,1,0:T(8,128)} multiply(%param_0.8, %param_0.8), metadata={op_name="jit(paged_step_mt)/while/body/closed_call/LlamaLM/checkpoint/layer_3/attn_norm/mul"}
  ROOT %gather.1 = f32[64,8,16]{2,1,0:T(8,128)} gather(%mul.3, %param_1.1), offset_dims={1,2}, metadata={op_name="jit(paged_step_mt)/while/body/closed_call/LlamaLM/checkpoint/layer_3/attention/attention._paged_attend/jit(take_along_axis)/gather" stack_frame_id=11}
}

%body.5 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.1 = (s32[]{:T(128)}, f32[8]{0:T(128)}) parameter(0)
  %get-tuple-element.7 = f32[8]{0:T(128)} get-tuple-element(%arg.1), index=1
  %fusion.77 = f32[8]{0:T(128)} fusion(%get-tuple-element.7), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp(LlamaLM))/jvp(LlamaLM)/checkpoint/rematted_computation/layer_0/mlp/w_gate/base/dot_general"}
  %dot.4 = f32[8]{0:T(128)} dot(%fusion.77, %fusion.77), metadata={op_name="jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp(LlamaLM))/jvp(LlamaLM)/checkpoint/layer_0/attention/wq/base/dot_general"}
  %exp.2.remat = f32[8]{0:T(128)} exponential(%dot.4), metadata={op_name="jit(round_fn)/vmap()/while/body/closed_call/jvp(LlamaLM)/layer_0/attention/exp"}
  ROOT %tuple.3 = (s32[]{:T(128)}, f32[8]{0:T(128)}) tuple(%get-tuple-element.7, %exp.2.remat)
}

%fused_computation.9 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0:T(128)} parameter(0)
  ROOT %neg.1 = f32[8]{0:T(128)} negate(%param_0.2)
}

%cond.6 (arg.2: (s32[], f32[8])) -> pred[] {
  %arg.2 = (s32[]{:T(128)}, f32[8]{0:T(128)}) parameter(0)
  ROOT %compare.1 = pred[]{:T(512)} compare(%arg.2, %arg.2), direction=LT, metadata={op_name="jit(round_fn)/vmap()/while/cond/lt"}
}

ENTRY %main.89 (params__x.1: f32[8], bank.1: f32[17,8,16], rows.1: s32[64]) -> f32[8] {
  %params__x.1 = f32[8]{0:T(128)} parameter(0)
  %bank.1 = f32[17,8,16]{2,1,0:T(8,128)} parameter(1)
  %rows.1 = s32[64]{0:T(128)} parameter(2)
  %fusion.12 = f32[64,8,16]{2,1,0:T(8,128)} fusion(%bank.1, %rows.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(paged_step_mt)/while/body/closed_call/LlamaLM/checkpoint/layer_3/attn_norm/mul"}
  %latent_attention.4 = bf16[64,1,64,512]{3,2,1,0:T(8,128)(2,1)} custom-call(%fusion.12, %rows.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[64,8,16]{2,1,0}, s32[64]{0}}, metadata={op_name="jit(paged_step_mt)/while/body/closed_call/LlamaLM/checkpoint/layer_3/attention/attention._paged_attend/cond/branch_0_fun/jit(latent_attention)/latent_attention/pallas_call" stack_frame_id=98}, backend_config={"custom_call_config":{"body":"TUzvUg"}}
  %gated_matmul.2 = bf16[512,2048]{1,0:T(8,128)(2,1)S(1)} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(paged_step_mt)/while/body/closed_call/LlamaLM/checkpoint/layer_5/moe_mlp/jit(swiglu)/cond/branch_0_fun/gated_matmul/pallas_call"}
  %custom-call.22 = s32[64]{0:T(128)} custom-call(%rows.1), custom_call_target="AssumeGatherIndicesInBound"
  %reduce.3 = f32[]{:T(128)} reduce(%params__x.1, %params__x.1), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(paged_step_mt)/LlamaLM/lm_head/reduce_sum"}
  %copy-start.1 = (f32[8]{0:T(128)S(1)}, f32[8]{0:T(128)}, u32[]{:S(2)}) copy-start(%params__x.1)
  %copy-done.1 = f32[8]{0:T(128)S(1)} copy-done(%copy-start.1)
  %tuple.1 = (s32[]{:T(128)}, f32[8]{0:T(128)}) tuple(%custom-call.22, %copy-done.1)
  %while.1 = (s32[]{:T(128)}, f32[8]{0:T(128)}) while(%tuple.1), condition=%cond.6, body=%body.5, metadata={op_name="jit(round_fn)/vmap()/while"}
  ROOT %get-tuple-element.9 = f32[8]{0:T(128)} get-tuple-element(%while.1), index=1
}
'''


@pytest.mark.parametrize("op_name,path,phase", [
    ("jit(paged_step_mt)/while/body/closed_call/LlamaLM/checkpoint/layer_3/attention/"
     "attention._paged_attend/cond/branch_0_fun/jit(latent_attention)/latent_attention/pallas_call",
     "layer_3/attention._paged_attend/latent_attention", "forward"),
    ("jit(paged_step_mt)/while/body/closed_call/LlamaLM/checkpoint/layer_5/moe_mlp/jit(swiglu)/"
     "cond/branch_0_fun/jit(floor_divide)/rem", "layer_5/moe_mlp", "forward"),
    ("jit(paged_step_mt)/LlamaLM/lm_head/dot_general", "lm_head", "forward"),
    ("jit(paged_step_mt)/LlamaLM/layer_0/attention/bhsn,rhn->bhsr/dot_general",
     "layer_0/attention/bhsn,rhn->bhsr", "forward"),
    ("jit(round_fn)/vmap()/while/body/closed_call/jvp(LlamaLM)/layer_0/attention/wq/base/dot_general",
     "layer_0/attention/wq/base", "forward"),
    ("jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp(LlamaLM))/jvp(LlamaLM)/checkpoint/"
     "layer_1/mlp/w_gate/base/transpose", "layer_1/mlp/w_gate/base", "backward"),
    ("jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp(LlamaLM))/jvp(LlamaLM)/checkpoint/"
     "rematted_computation/layer_1/attention/while/body/closed_call/mul", "layer_1/attention", "recompute"),
    ("jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp(LlamaLM))/lm_head/dot_general",
     "lm_head", "backward"),
    ("jit(paged_step_mt)/gather", "", "forward"),
    # no module: the outermost jitted function below the program's own, in brackets
    ("jit(paged_step_mt)/while/body/closed_call/vmap(jit(_gumbel))/jit(_uniform)/vmap()/add",
     "(_gumbel)", "forward"),
    ("jit(paged_step_mt)/while/body/closed_call/vmap()/vmap(jit(_threefry_split))/"
     "ContinuousBatchingEngine.__init__.<locals>.paged_tick.<locals>.body/add", "(_threefry_split)", "forward"),
    ("jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp(jit(take_along_axis)))/scatter-add",
     "(take_along_axis)", "backward"),
    ("jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp())/while/body/closed_call/dot_general",
     "", "backward"),
    ("reduce_sum", "", "forward"),
], ids=["kernel", "under-jit-and-cond", "head", "einsum-scope", "jvp", "transpose", "remat",
        "backward-head", "no-module", "function", "function-under-a-qualname", "function-backward",
        "custom-vjp", "bare-primitive"])
def test_op_name_gives_path_and_phase(op_name, path, phase):
    assert programs.parse_op_name(op_name) == {"path": path, "phase": phase}


@pytest.mark.parametrize("name,want", [
    # a fusion counts where its root lies, not where its own metadata points
    ("fusion.12", {"path": "layer_3/attention._paged_attend", "phase": "forward",
                   "kernel": "", "op": "fusion"}),
    ("latent_attention.4", {"path": "layer_3/attention._paged_attend/latent_attention",
                            "phase": "forward", "kernel": "latent_attention", "op": "custom-call"}),
    ("gated_matmul.2", {"path": "layer_5/moe_mlp/gated_matmul", "phase": "forward",
                        "kernel": "gated_matmul", "op": "custom-call"}),
    # another custom call is no kernel; with no metadata it keeps its place in the map
    ("custom-call.22", {"path": "", "phase": "forward", "kernel": "", "op": "custom-call"}),
    ("copy-done.1", {"path": "", "phase": "forward", "kernel": "", "op": "copy-done"}),
    ("reduce.3", {"path": "lm_head", "phase": "forward", "kernel": "", "op": "reduce"}),
    # a while's body is read: its instructions show in a trace under their own names;
    # a fusion whose root has no metadata falls back on its own
    ("fusion.77", {"path": "layer_0/mlp/w_gate/base", "phase": "recompute", "kernel": "",
                   "op": "fusion"}),
    ("dot.4", {"path": "layer_0/attention/wq/base", "phase": "backward", "kernel": "", "op": "dot"}),
    ("exp.2.remat", {"path": "layer_0/attention", "phase": "recompute", "kernel": "",
                     "op": "exponential"}),
    ("compare.1", {"path": "", "phase": "forward", "kernel": "", "op": "compare"}),
    ("while.1", {"path": "", "phase": "forward", "kernel": "", "op": "while"}),
], ids=lambda v: v if isinstance(v, str) else "")
def test_canned_module_maps_every_traceable_instruction(name, want):
    assert programs.parse_hlo(HLO)[name] == want


def test_canned_module_leaves_out_what_no_trace_shows():
    ops = programs.parse_hlo(HLO)
    # the insides of fusions and of reducers are not device operations
    assert not {"mul.3", "gather.1", "neg.1", "add.9", "a.1"} & set(ops)
    assert {"params__x.1", "tuple.1", "get-tuple-element.9", "arg.1"} <= set(ops)


# -- the registry ----------------------------------------------------------------

def test_registry_keeps_shapes_and_no_array():
    fn = jax.jit(lambda x, y, k=None: x * 2 + y)
    x = jnp.arange(8.0)
    handle = programs.register("twice_test", fn, (x, np.float32(1.0)), {"k": None})
    try:
        assert "twice_test" in programs.registered()
        kept = jax.tree_util.tree_leaves((handle.args, handle.kwargs))
        assert all(isinstance(k, jax.ShapeDtypeStruct) for k in kept) and len(kept) == 2
        assert kept[0].shape == (8,) and kept[0].sharding == x.sharding
        ops = programs.op_modules("twice_test")
        assert ops and all(set(row) == {"path", "phase", "kernel", "op"} for row in ops.values())
        assert programs.op_modules(handle) == ops
    finally:
        programs.unregister(handle)
    assert "twice_test" not in programs.registered()
    assert programs.op_modules("twice_test") is None and programs.op_modules(handle) is None


def test_registry_forgets_a_collected_callable():
    fn = jax.jit(lambda x: x + 1)
    handle = programs.register("gone_test", fn, (jnp.zeros(3),))
    del fn
    gc.collect()
    assert "gone_test" not in programs.registered()
    assert programs.op_modules(handle) is None
    programs.unregister(handle, None)          # idempotent; None is skipped


# -- a tiny engine ------------------------------------------------------------------

def _tiny_model(lora_rank=0):
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
                      max_seq_len=64, dtype=jnp.float32, lora_rank=lora_rank, lora_alpha=2.0)
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, variables


def _compile_counter():
    seen = []

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


@pytest.mark.parametrize("adapters", [0, 2], ids=["one-model", "bank"])
def test_engine_registers_at_first_launch_and_compiles_only_when_asked(adapters):
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    model, variables = _tiny_model(lora_rank=2 if adapters else 0)
    eng = ContinuousBatchingEngine(model, variables["params"], slots=2, buf_len=64, kv_page_tokens=4,
                                   prefill_chunk_tokens=8, adapter_slots=adapters)
    step = "paged_step_mt" if adapters else "paged_step"
    try:
        # nothing launched yet (but the bank's gather, which shaped the pool)
        assert all(v is None for k, v in eng.program_ops().items() if k != "gather_row")
        ids = [int(t) for t in np.random.default_rng(5).integers(1, 97, size=21)]
        first = eng.generate(ids, max_new_tokens=6)
        assert {step, "paged_chunk", "slot_rows"} <= set(programs.registered())
        compiles = _compile_counter()
        assert eng.generate(ids, max_new_tokens=6) == first
        assert not compiles, "a launch after the first compiled something"
        ops = eng.program_ops()
        assert compiles, "the maps are made by lowering and compiling, when asked"
        assert set(ops) == {step, "paged_chunk", "slot_rows"} | ({"gather_row"} if adapters else set())
        for name in (step, "paged_chunk"):
            paths = {row["path"] for row in ops[name].values()}
            assert all(isinstance(p, str) for p in paths)
            assert any(p.startswith("layer_0/") for p in paths)
            assert any(p.startswith("layer_1/attention._paged_decode_attend") for p in paths), paths
            assert {row["phase"] for row in ops[name].values()} == {"forward"}
        # no module in the slot rows' program: no path, or a jitted function's name in brackets
        assert {row["path"] for row in ops["slot_rows"].values()} <= {"", "(_where)"}
        if adapters:
            assert ops["gather_row"]
        # the maps changed nothing of the programs that run
        compiles.clear()
        assert eng.generate(ids, max_new_tokens=6) == first and not compiles
    finally:
        eng.stop()
    # a stopped engine is out of the registry though it is still referenced
    assert not {step, "paged_chunk", "slot_rows", "gather_row"} & set(programs.registered())
    assert all(v is None for v in eng.program_ops().values())


def test_program_ops_is_refused_on_the_engines_thread():
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    model, variables = _tiny_model()
    eng = ContinuousBatchingEngine(model, variables["params"], slots=2, buf_len=64, kv_page_tokens=4,
                                   prefill_chunk_tokens=8)
    try:
        eng._thread, own = threading.current_thread(), eng._thread
        with pytest.raises(RuntimeError, match="engine's thread"):
            eng.program_ops()
        eng._thread = own
    finally:
        eng.stop()


def test_registry_keeps_no_engine_alive():
    """What ``check`` relies on: after ``stop()`` and the last reference,
    ``gc.collect()`` frees the engine, its parameters and its pool; the
    registry held none of them."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    model, variables = _tiny_model(lora_rank=2)
    eng = ContinuousBatchingEngine(model, variables["params"], slots=2, buf_len=64, kv_page_tokens=4,
                                   prefill_chunk_tokens=8, adapter_slots=2)
    eng.generate([3, 5, 7, 11, 13, 17, 19, 23, 29], max_new_tokens=4)
    assert eng.program_ops()["paged_chunk"]            # also after a map was made
    pool = jax.tree_util.tree_leaves(eng._pool)
    dead = [weakref.ref(eng), weakref.ref(eng._step), weakref.ref(eng.registry)] \
        + [weakref.ref(p) for p in pool]
    mine = {"paged_step_mt", "paged_chunk", "slot_rows", "gather_row"}
    # not stopped through ``stop()``'s unregister alone: drop the thread, then every reference
    eng.stop()
    del eng, pool
    gc.collect()
    assert all(r() is None for r in dead)
    assert not mine & set(programs.registered())
    assert not any(name in programs._PROGRAMS for name in mine)


# -- a tiny FedLLMAPI ---------------------------------------------------------------

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


def test_round_program_registers_at_the_first_round_and_maps_three_phases(api):
    assert api.program_ops() == {"round_fn": None}
    api.train_one_round(0)
    assert "round_fn" in programs.registered()
    compiles = _compile_counter()
    loss = api.train_one_round(1)["train_loss"]
    assert np.isfinite(loss) and not compiles
    ops = api.program_ops()["round_fn"]
    assert compiles
    rows = list(ops.values())
    assert all(isinstance(r["path"], str) for r in rows)
    by_phase = {phase: {r["path"] for r in rows if r["phase"] == phase}
                for phase in ("forward", "recompute", "backward")}
    # remat=full: every block's forward is computed again inside the backward pass
    for phase, paths in by_phase.items():
        assert "layer_0/attention" in paths, (phase, sorted(paths))
        assert any(p.startswith("layer_1/mlp") for p in paths), (phase, sorted(paths))
    assert "lm_head" in by_phase["forward"] | by_phase["backward"]
    # the round after the map was made runs the program that was there
    compiles.clear()
    api.train_one_round(2)
    assert not compiles


# -- the rule by which the benchmark reads the round's attention ---------------------
# ``round_attn_ms`` and ``attn_roofline`` pick the round's attention out of
# ``round_fn``'s map: everything under ``layer_*/attention`` but the projections,
# whatever implements it.  On the round as it runs here (the blockwise ``jnp``
# scan) that is exactly what the rule before it picked: the module's own
# instructions and the scan's einsum scopes.

#: the two metrics' ``args`` before the rule counted a kernel's own scope
OLD_RULE = {"path": ["layer_*/attention", "layer_*/attention/*->*"]}
ROUND_ATTN_METRICS = ("round_attn_ms", "attn_roofline")
PROJECTIONS = ("wq", "wk", "wv", "wo")


def _rule(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)["args"]


def _picked(rows: dict, args: dict) -> set:
    return {instr for instr, row in rows.items() if bench_ops.matches(row, args)}


def _is_projection(path: str) -> bool:
    parts = path.split("/")
    return len(parts) > 2 and parts[1] == "attention" and parts[2] in PROJECTIONS


@pytest.mark.parametrize("metric", ROUND_ATTN_METRICS)
@pytest.mark.parametrize("path, counted", [
    ("layer_3/attention", True),
    ("layer_3/attention/...qd,...kd->...qk", True),
    ("layer_3/attention/flash_fwd", True),             # the Pallas kernels' own scopes
    ("layer_3/attention/flash_dq", True),
    ("layer_3/attention/flash_dkv", True),
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
    assert bench_ops.matches(row, _rule(metric)) is counted


def test_todays_round_reads_the_same_instructions_under_both_rules(api):
    assert np.isfinite(api.train_one_round(0)["train_loss"])
    rows = api.program_ops()["round_fn"]
    old = _picked(rows, OLD_RULE)
    # the scan's products under their einsums' scopes, in all three phases
    assert {rows[i]["phase"] for i in old} == {"forward", "recompute", "backward"}
    assert any("->" in rows[i]["path"] for i in old)
    for metric in ROUND_ATTN_METRICS:
        assert _picked(rows, _rule(metric)) == old, metric
    # the projections (and their base and adapter children) are there, and in neither
    projections = {i for i, row in rows.items() if _is_projection(row["path"])}
    assert {rows[i]["path"].split("/")[2] for i in projections} == set(PROJECTIONS)
    assert not projections & old
