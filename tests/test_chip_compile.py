"""The main path's kernels, compiled for a TPU v5e that is described and not
attached (section 2 of the on-chip-measurement guide): what the chip's
compiler refuses costs no chip time.  Nothing runs, so these say nothing
about results or speed.

The topology is described inside a module-scoped fixture — never while a
module is imported — and every such compile lives in THIS file: the process
that describes the topology loads the TPU's library and keeps it.
"""

import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from fedml_tpu.ops import attention as A

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# (batch, q heads, kv heads, seq, head_dim): the tile table's first shape, a
# GQA variant of it, the 1.075B flagship's attention as chip_smoke.py's
# kernel phase and a two-client cohort trace it, two long-context GQA/MHA
# shapes at head_dim 128, and the round of fedlora-round.mistral-7b-d12 (4
# clients x batch 2, 32 q heads on 8 kv heads of 128) at its entered tile
SHAPES = [
    (4, 12, 12, 1024, 64),
    (2, 32, 4, 1024, 64),
    (2, 16, 8, 256, 128),
    (2, 16, 8, 2048, 128),
    (1, 32, 32, 2048, 128),
    (8, 32, 8, 1024, 128),
]
_ids = ["b{}_h{}_kv{}_s{}_d{}".format(*s) for s in SHAPES]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(shape, sharding):
    b, h, h_kv, s, d = shape
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, h_kv, s, d), jnp.bfloat16,
                              sharding=sharding)
    return q, kv


@pytest.mark.parametrize("shape", SHAPES, ids=_ids)
def test_flash_forward_compiles_for_v5e(one_chip, shape):
    q, kv = _qkv(shape, one_chip)
    fwd = jax.jit(lambda q, k, v: A.flash_attention_fwd_pallas(
        q, k, v, True, None, return_lse=True))
    compiled = fwd.lower(q, kv, kv).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1 and "%flash_fwd" in hlo


@pytest.mark.parametrize("shape", SHAPES, ids=_ids)
def test_flash_backward_compiles_for_v5e(one_chip, shape):
    b, h, _, s, _ = shape
    q, kv = _qkv(shape, one_chip)
    lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one_chip)
    bwd = jax.jit(lambda q, k, v, out, lse, do: A.flash_attention_bwd_pallas(
        q, k, v, out, lse, do, True, None))
    compiled = bwd.lower(q, kv, kv, q, lse, q).compile()
    # the dq pass and the dk/dv pass, under the kernels' names
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert "%flash_dq" in hlo and "%flash_dkv" in hlo


def test_scatter_merge_compiles_on_four_chip_mesh(topo):
    """The mesh engine's default (scatter) round program, lowered for a
    4-device ``Mesh`` over the described chips: the program asks for the
    reduce-scatter merge, the chip's compiler accepts it, and it fits."""
    import fedml_tpu
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu.core.mesh import make_mesh
    from fedml_tpu.simulation.mesh.engine import (MeshFedAvgAPI,
                                                  make_mesh_round_fn)

    args = load_arguments()
    args.update(dataset="synthetic", num_classes=10, input_shape=(28, 28, 1),
                train_size=1024, test_size=256, model="lr",
                client_num_in_total=16, client_num_per_round=8, comm_round=2,
                batch_size=16, learning_rate=0.1, random_seed=0,
                backend="mesh", async_staging=False)
    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    # a CPU twin supplies the trainer, the scatter-layout state and one
    # round's staged arguments; only their shapes cross over
    cpu = MeshFedAvgAPI(args, None, dataset, model,
                        mesh=make_mesh(client=4, devices=jax.devices()[:4]))
    assert cpu.update_sharding == "scatter"
    _, cpu_args, _ = cpu.round_program(0)

    mesh = make_mesh(client=4, devices=topo.devices)
    round_fn = make_mesh_round_fn(
        cpu.trainer, cpu.server_opt, mesh, gather=cpu._gather,
        sharded_data=cpu._sharded_data, update_sharding="scatter",
        state_template=cpu.state,
        collective_precision=cpu.collective_precision,
        quant_block=cpu.quant_block)
    # (the round key is an uncommitted one-device array: replicated)
    described = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(
                mesh, getattr(a.sharding, "spec", PartitionSpec()))),
        cpu_args)
    lowered = round_fn.lower(*described)
    assert "stablehlo.reduce_scatter" in lowered.as_text()
    compiled = lowered.compile()
    # at this size the chip's compiler serves the scatter with all-reduces
    # and permutes; what matters is that the collectives are there
    assert "all-reduce" in compiled.as_text()
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes)
    assert per_chip < 16e9


# -- the expert layer's grouped matmuls at the latent cell's shapes ----------

@pytest.mark.parametrize("rows", [512, 4096], ids=["tick", "chunk"])
@pytest.mark.parametrize("call", ["gate", "down", "gate_up", "swiglu"])
def test_grouped_matmul_compiles_for_v5e(one_chip, call, rows):
    """``bf16[512|4096, 7168] x bf16[12, 7168, 2048]`` and back: a tick's and
    a prefill chunk's (token, expert) pairs against the 12 held experts of
    ``a.x-k1-ep16-d7``.  ``swiglu`` is what ``expert_ffn`` calls: lowered for
    the chip it is the two kernels and no ``ragged_dot``."""
    from fedml_tpu.ops import grouped_matmul as gm

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x, act = shape(rows, 7168), shape(rows, 2048)
    wide, narrow = shape(12, 7168, 2048), shape(12, 2048, 7168)
    sizes = shape(12, dtype=jnp.int32)
    fn, args, kernels = {
        "gate": (gm.grouped_matmul, (x, wide, sizes), 1),
        "down": (gm.grouped_matmul, (act, narrow, sizes), 1),
        "gate_up": (gm.gated_matmul, (x, wide, wide, sizes), 1),
        "swiglu": (gm.swiglu, (x, wide, wide, narrow, sizes), 2),
    }[call]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count("tpu_custom_call") == kernels and "ragged-dot" not in hlo


# -- the paged read's walk at the mixed cell's shapes ---------------------------

@pytest.mark.parametrize("table", ["full", "ring"])
@pytest.mark.parametrize("shape", ["tick", "chunk"])
def test_paged_attention_compiles_for_v5e(one_chip, shape, table):
    """``ops/paged_attention.py`` at the two shapes and both kinds of table of
    ``serve-mixed-12k.command-a-plus-ep8-d4``: a tick's 48 lanes of one query
    and a chunk's 1,024, 128 query heads on 8 kv heads of 128, over the full
    layers' table of 946 entries (a pool of 14,401 pages of 16 tokens) and the
    window layers' ring of 321 (7,521 pages, window 4,096).  What
    ``_walk_pages`` lowers to for the chip at these operands is this call
    and no loop."""
    from fedml_tpu.llm import model as M
    from fedml_tpu.ops import paged_attention as pa

    def described(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    b, s = {"tick": (48, 1), "chunk": (1, 1024)}[shape]
    entries, pages, window = {"full": (946, 14401, 0),
                              "ring": (321, 7521, 4096)}[table]
    pool = described(pages, 16, 8, 128)
    operands = (described(b, 8, 16, s, 128), pool, pool,
                described(b, entries, dtype=jnp.int32),
                described(b, s, dtype=jnp.int32))
    assert pa.kernel_can_run(*operands[:4])
    walk = jax.jit(lambda *a: M._walk_pages(
        *a, window, table == "ring", 128 ** -0.5, jnp.bfloat16))
    compiled = walk.lower(*operands).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1 and "%paged_attention" in hlo
    assert not re.search(r" while\(", hlo)
    # the pools are read where they lie: no temporary of a pool's size
    assert compiled.memory_analysis().temp_size_in_bytes < pool.size


# -- the latent pool's paged read at the latent cell's shapes -------------------

@pytest.mark.parametrize("shape", ["tick", "chunk"])
def test_latent_attention_compiles_for_v5e(one_chip, shape):
    """``ops/latent_attention.py`` at the two shapes of
    ``serve-saturated-2k.a.x-k1-ep16-d7``: a tick's 64 lanes of one query and
    a chunk's 512, 64 heads against a pool of 10,241 pages of 16 rows of 640
    (rank 512) through tables of 213 entries.  What ``mla._read_pool`` lowers
    to for the chip at these operands is this call between its two small
    products, and no window gathered."""
    from fedml_tpu.llm import mla
    from fedml_tpu.llm.model import LlamaConfig

    def described(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = LlamaConfig(vocab_size=512, dim=7168, n_layers=1, n_heads=64,
                      n_kv_heads=64, ffn_dim=18432, dtype=jnp.bfloat16,
                      q_lora_rank=1536, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, kv_page_tokens=16, kv_pool_pages=10241)
    b, s = {"tick": (64, 1), "chunk": (1, 512)}[shape]
    pool = described(10241, 16, 640)
    operands = (described(b, 64, s, 128), described(b, 64, s, 64), pool,
                described(b, 213, dtype=jnp.int32),
                described(512, 64, 256), described(b, s, dtype=jnp.int32))
    read = jax.jit(lambda *a: mla._read_pool(cfg, *a, 0.1))
    compiled = read.lower(*operands).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1 and "%latent_attention" in hlo
    assert not re.search(r" while\(", hlo)
    # the pool is read where it lies: no window of it, no temporary its size
    assert f"bf16[{b},3408,640]" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < pool.size


# -- the paged decode programs never move a whole page pool -----------------

@pytest.fixture(scope="module")
def paged_programs(one_chip):
    """The multi-adapter tick and the prefill chunk program at the serving
    cell's pool and slot shapes (``benchmarks/workloads/serve-saturated.
    internlm2-1.8b.json``: 32 slots, 1,281 pages of 16 tokens, 8 kv heads of
    128, chunk 128, 17 bank rows), two layers deep — what a pool costs is
    per layer — compiled for the described chip from abstract arguments."""
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.batching import ContinuousBatchingEngine

    cfg = LlamaConfig(vocab_size=512, dim=2048, n_layers=2, n_heads=16,
                      n_kv_heads=8, ffn_dim=8192, max_seq_len=1040,
                      rope_theta=1e6, norm_eps=1e-5, dtype=jnp.bfloat16,
                      lora_rank=16, lora_alpha=16.0)
    model = LlamaLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        model, params, slots=32, buf_len=1040, adapter_slots=17,
        kv_page_tokens=16, kv_pool_pages=1281, prefill_chunk_tokens=128)
    return _engine_programs(eng, one_chip)


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (?P<type>.*?) (?P<op>[a-z][\w\-]*)\(")
_HLO_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def _pool_sized_instructions(hlo: str, pool_elems: int):
    """(opcode, line) of every instruction, in any computation of the module,
    whose result holds an array of the pool's element count."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        for dims in _HLO_ARRAY.findall(m.group("type")):
            if math.prod(int(d) for d in dims.split(",")) == pool_elems:
                found.append((m.group("op"), line.strip()))
                break
    return found


def _engine_programs(eng, one_chip):
    """The engine's step programs compiled for the described chip from
    abstract arguments under the options the engine gives the chip's compiler
    (this process's own backend is the CPU, so the jits carry none), and the
    shapes of what they donate: the pools, then the slot state the tick
    carries from launch to launch."""
    from fedml_tpu.serving.batching import PAGED_TPU_COMPILER_OPTIONS
    try:
        pools = jax.tree_util.tree_leaves(eng._pool) \
            + jax.tree_util.tree_leaves(eng._dev)
        out = {}
        for name, fn, args, _ in eng.step_programs():
            described = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            out[name] = fn.lower(*described).compile(
                compiler_options=PAGED_TPU_COMPILER_OPTIONS)
        return out, [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools]
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def latent_programs(one_chip):
    """The same two programs of a latent-attention, sparse-expert model at
    the widths and the engine geometry of ``benchmarks/workloads/
    serve-saturated-2k.a.x-k1-ep16-d7.json`` (64 slots, 10,241 pages of 16
    tokens of 640 numbers: the 576 of the latent in whole lane tiles, chunk
    512, 17 bank rows; 12 of 192 experts held), one dense and one sparse
    layer deep.  The weights are shapes only: at
    these widths two layers are 2.3 GB."""
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM, YarnScaling
    from fedml_tpu.serving.batching import ContinuousBatchingEngine

    cfg = LlamaConfig(
        vocab_size=512, dim=7168, n_layers=2, n_heads=64, n_kv_heads=64,
        ffn_dim=18432, max_seq_len=2896, rope_theta=1e4, norm_eps=1e-6,
        dtype=jnp.bfloat16, lora_rank=16, lora_alpha=16.0,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=YarnScaling(32, 4096, 32, 1, 1, 1),
        n_experts=192, moe_top_k=8, moe_ffn_dim=2048, first_dense_layers=1,
        n_shared_experts=1, moe_scoring="sigmoid", moe_n_group=8,
        moe_topk_group=4, moe_routed_scale=2.5, experts_held=(0, 12))
    model = LlamaLM(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        model, params, slots=64, buf_len=2896, adapter_slots=17,
        kv_page_tokens=16, kv_pool_pages=10241, prefill_chunk_tokens=512)
    return _engine_programs(eng, one_chip)


@pytest.fixture(scope="module")
def mixed_programs(one_chip):
    """The same two programs of a model with window and full layers in one
    stack at the widths and the engine geometry of ``benchmarks/workloads/
    serve-mixed-12k.command-a-plus-ep8-d4.json`` (48 slots, a buffer of
    14,112, chunk 1,024, 17 bank rows; 128 query and 8 kv heads of 128, a
    window of 4,096, a parallel block, 16 of 128 experts held beside 4
    shared ones, a tied head), one window and one full layer deep, with a
    third of the cell's pages in either pool (what a pool costs is per
    page).  The weights are shapes only."""
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.batching import ContinuousBatchingEngine

    cfg = LlamaConfig(
        vocab_size=512, dim=4096, n_layers=2, n_heads=128, n_kv_heads=8,
        head_dim=128, ffn_dim=4096, max_seq_len=14112, rope_theta=5e4,
        norm_eps=1e-5, dtype=jnp.bfloat16, lora_rank=16, lora_alpha=16.0,
        layer_types=("sliding_attention", "full_attention"),
        sliding_window=4096, rope_full_layers=False, parallel_block=True,
        norm_kind="layer", tie_embeddings=True, n_experts=128, moe_top_k=8,
        n_shared_experts=4, shared_expert_scale=0.25, moe_scoring="sigmoid",
        experts_held=(0, 16))
    model = LlamaLM(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        model, params, slots=48, buf_len=14112, adapter_slots=17,
        kv_page_tokens=16, kv_pool_pages=4801, kv_window_pool_pages=2507,
        prefill_chunk_tokens=1024)
    assert eng.window_blocks == 321 and eng.max_blocks == 946
    return _engine_programs(eng, one_chip)


@pytest.fixture(scope="module")
def stateful_programs(one_chip):
    """The same two programs of a model whose layers mix by the gated short
    convolution or by attention, at the widths and the engine geometry of
    ``benchmarks/workloads/serve-chat-512.lfm2-8b-a1b-d13.json`` (64 slots, a
    buffer of 736, chunk 256, 2,721 pages of 16 tokens, 17 bank rows; 32 query
    and 8 kv heads of 64 with norms on q and k, 32 experts of 1,792 chosen 4 a
    token under a selection bias, a tied head), one convolution and one
    attention layer deep, both sparse.  The weights are shapes only."""
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.batching import ContinuousBatchingEngine

    cfg = LlamaConfig(
        vocab_size=512, dim=2048, n_layers=2, n_heads=32, n_kv_heads=8,
        ffn_dim=7168, max_seq_len=736, rope_theta=1e6, norm_eps=1e-5,
        dtype=jnp.bfloat16, lora_rank=16, lora_alpha=16.0,
        layer_types=("conv", "full_attention"), conv_kernel=3, qk_norm=True,
        tie_embeddings=True, n_experts=32, moe_top_k=4, moe_ffn_dim=1792,
        moe_scoring="sigmoid", moe_select_bias=True)
    model = LlamaLM(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        model, params, slots=64, buf_len=736, adapter_slots=17,
        kv_page_tokens=16, kv_pool_pages=2721, prefill_chunk_tokens=256)
    assert eng.max_blocks == 62 and eng._state_bytes == 65 * 2 * 2048 * 2
    return _engine_programs(eng, one_chip)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_stateful_program_moves_neither_a_pool_nor_the_state(
        stateful_programs, program):
    """The rule below, for state beside pages: the two pools (K and V of the
    attention layer) and the convolution layer's rows of state (a row a slot
    and the trash row) are each updated where they lie by one scatter, alone
    in its fusion, donated and aliased, and otherwise only named: nothing of
    a pool's or of the state's size is copied, transposed or re-laid out.
    Heads 64 wide, 4 query rows a kv head, are not the paged-attention
    kernel's (``ops/paged_attention.py::kernel_can_run``): the read is the
    gather, out of a pool whose rows are flat (with a trailing axis of 64
    the compiler padded every page to twice its size and copied each pool
    four times a program).  The experts run as the two grouped-matmul kernels at 2048 and
    1792."""
    compiled, donated = stateful_programs
    pools = [p for p in donated if p.ndim == 3 and p.shape[0] == 2721]
    rows = [p for p in donated if p.ndim == 3 and p.shape[0] == 65]
    state = [p for p in donated if p.ndim < 3]
    # a page's row is the 8 kv heads of 64 side by side: whole lane tiles
    assert [p.shape for p in pools] == [(2721, 16, 512)] * 2
    assert [p.shape for p in rows] == [(65, 2, 2048)] and len(state) == 7
    compiled = compiled[program]
    hlo = compiled.as_text()
    assert "ragged-dot" not in hlo and hlo.count("tpu_custom_call") >= 2
    assert "%gated_matmul" in hlo and "%grouped_matmul" in hlo
    assert "%paged_attention" not in hlo
    naming = {"parameter", "get-tuple-element", "tuple", "bitcast"}
    instrs = _pool_sized_instructions(hlo, pools[0].size)
    moving = [(op, line[:200]) for op, line in instrs
              if op not in naming | {"scatter", "fusion"}]
    assert not moving, moving
    scatters = [line for op, line in instrs if op == "scatter"]
    assert len(scatters) == 2 and all(
        line.startswith("ROOT ") for line in scatters), scatters
    assert sum(op == "fusion" for op, _ in instrs) == 2
    # the state, half a megabyte a layer: written where it lies by the tick's
    # one scatter of its lanes' rows, or by the chunk's one row.  The
    # compiler may fetch it whole into fast memory and put it back, as it
    # does a weight matrix (a start and a done each way); nothing re-lays it
    # out
    instrs = _pool_sized_instructions(hlo, rows[0].size)
    moving = [(op, line[:200]) for op, line in instrs if op not in naming | {
        "scatter", "dynamic-update-slice", "fusion", "copy-start",
        "copy-done"}]
    assert not moving, moving
    assert sum(op in ("scatter", "dynamic-update-slice")
               for op, _ in instrs) == 1
    aliases = re.search(r"input_output_alias=\{(.*?) \}, ", hlo).group(1)
    assert aliases.count("-alias)") == len(pools) + len(rows) + len(state)
    held_bytes = sum(p.size * p.dtype.itemsize for p in pools + rows)
    assert compiled.memory_analysis().alias_size_in_bytes >= held_bytes
    assert "slice-start" not in hlo and "copy-start" in hlo


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_two_pool_program_never_moves_a_whole_pool(mixed_programs, program):
    """The rule below, for a pool per kind of layer: each of the four pools
    (K and V of the window layer's, K and V of the full layer's) is updated
    where it lies by one scatter, alone in its fusion, and is otherwise only
    named: the walk over a block table is the paged-attention kernel
    (``ops/paged_attention.py``), one custom call a layer that takes the
    pools as operands and reads its pages from HBM itself, so no loop is
    left in either program and nothing of a pool's size is gathered.  The
    slot state has the window tables beside the seven vectors of a one-pool
    engine.  The experts run as the two kernels."""
    compiled, donated = mixed_programs
    pools = [p for p in donated if p.ndim >= 3]
    state = [p for p in donated if p.ndim < 3]
    assert sorted({p.shape for p in pools}) == [(2507, 16, 8, 128),
                                                (4801, 16, 8, 128)]
    assert len(pools) == 4 and len(state) == 8
    assert {p.shape for p in state if p.ndim == 2 and p.dtype == jnp.int32} \
        == {(48, 946), (48, 321)}
    compiled = compiled[program]
    hlo = compiled.as_text()
    assert "ragged-dot" not in hlo and hlo.count("tpu_custom_call") >= 2
    assert "%gated_matmul" in hlo and "%grouped_matmul" in hlo
    # the window layer's read and the full layer's, under the kernel's name
    assert len(re.findall(r"%paged_attention[.\d]* = ", hlo)) == 2
    assert not re.search(r" while\(", hlo)
    naming = {"parameter", "get-tuple-element", "tuple", "bitcast"}
    scatters = fusions = 0
    for elems in {p.size for p in pools}:
        instrs = _pool_sized_instructions(hlo, elems)
        moving = [(op, line[:200]) for op, line in instrs
                  if op not in naming | {"scatter", "fusion"}]
        assert not moving, moving
        assert all(line.startswith("ROOT ") for op, line in instrs
                   if op == "scatter")
        scatters += sum(op == "scatter" for op, _ in instrs)
        fusions += sum(op == "fusion" for op, _ in instrs)
    assert scatters == fusions == len(pools)
    aliases = re.search(r"input_output_alias=\{(.*?) \}, ", hlo).group(1)
    assert aliases.count("-alias)") == len(pools) + len(state), aliases
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    assert "slice-start" not in hlo and "copy-start" in hlo


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
@pytest.mark.parametrize("model", ["dense", "latent"])
def test_paged_program_never_moves_a_whole_pool(request, model, program):
    """docs/SERVING.md, Memory plane: the write of the new tokens' K/V (or
    latent row) is an in-place update of the donated pool.  In the optimized
    module a pool is the result of a parameter, of the scatter that updates
    it (one per pool, alone in its fusion) and of the plumbing that names it,
    and of nothing that reads or writes all of it: no copy, transpose,
    relayout or prefetch.  Every donated argument is aliased to its output:
    the pools, and the slot state's vectors (tokens, positions, steps left,
    keys, temperatures, adapter rows, block tables) that the tick and the
    final chunk update where they lie.  Weights and banks are prefetched
    whole: a slice is a start, a done and a trace event of its own, and
    four a prefetch doubled the programs' operations."""
    compiled, donated = request.getfixturevalue(
        {"dense": "paged_programs", "latent": "latent_programs"}[model])
    pools = [p for p in donated if p.ndim >= 3]
    state = [p for p in donated if p.ndim < 3]
    assert len(state) == 7 and all(p.shape[0] == state[0].shape[0] for p in state)
    compiled = compiled[program]
    hlo = compiled.as_text()
    assert len({p.shape for p in pools}) == 1
    if model == "latent":
        assert pools[0].shape == (10241, 16, 640) and len(pools) == 2
        # the experts run as the two grouped-matmul kernels over the sorted
        # pairs (ops/grouped_matmul.py): the program the chip runs holds no
        # grouped matmul of the compiler's own
        assert "ragged-dot" not in hlo
        assert hlo.count("tpu_custom_call") >= 2
        # under the kernels' own names, which is what a device trace shows
        assert "%gated_matmul" in hlo and "%grouped_matmul" in hlo
        # the read is ``ops/latent_attention.py``'s kernel, once a layer,
        # over each lane's own pages: no window is gathered (a tick's 64
        # lanes, a chunk's one) and none multiplied out to the heads' keys
        assert len(re.findall(r"%latent_attention[.\d]* = ", hlo)) == 2
        assert not re.search(r"bf16\[(64|1),3408,640\]", hlo)
        assert "[1,64,3408,192]" not in hlo
    # neither reads through the walk: the dense model's tables are gathered
    # whole, the latent model reads through ``llm/mla.py``
    assert "%paged_attention" not in hlo
    instrs = _pool_sized_instructions(hlo, pools[0].size)
    naming = {"parameter", "get-tuple-element", "tuple", "bitcast"}
    moving = [(op, line[:200]) for op, line in instrs
              if op not in naming | {"scatter", "fusion"}]
    assert not moving, moving
    # the update: one scatter per pool, each the root of its own fusion
    scatters = [line for op, line in instrs if op == "scatter"]
    fusions = [line for op, line in instrs if op == "fusion"]
    assert len(scatters) == len(pools), scatters
    assert all(line.startswith("ROOT ") for line in scatters)
    assert len(fusions) == len(pools), [f[:200] for f in fusions]
    # donated and aliased: the update writes the argument's own buffer
    aliases = re.search(r"input_output_alias=\{(.*?) \}, ", hlo).group(1)
    assert aliases.count("-alias)") == len(pools) + len(state), aliases
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    # a prefetch is one start and one done: no weight or bank comes in slices
    assert "slice-start" not in hlo and "copy-start" in hlo


@pytest.fixture(scope="module")
def linear_programs(one_chip):
    """The same two programs of a model whose layers mix by the gated delta
    rule or by gated latent attention, at the widths of ``benchmarks/configs/
    gigachat3.5-ep16-d5.json`` (32 key and 64 value heads of 128, four taps;
    latent attention of ``a.x-k1-ep16-d7``'s widths with a gate; 16 of 256
    experts of 2,048 held, clamped at 10; sandwich gated norms), one linear
    and one latent layer deep, both sparse, on 8 slots, a buffer of 1,040,
    chunk 512 and 401 pages (what the state costs is per slot and layer).
    The weights are shapes only."""
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM, YarnScaling
    from fedml_tpu.serving.batching import ContinuousBatchingEngine

    cfg = LlamaConfig(
        vocab_size=512, dim=7168, n_layers=2, n_heads=64, n_kv_heads=64,
        ffn_dim=18432, max_seq_len=1040, rope_theta=1e5, norm_eps=1e-6,
        dtype=jnp.bfloat16, lora_rank=16, lora_alpha=16.0,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=YarnScaling(8, 32768, 32, 1, 1, 1),
        layer_types=("linear_attention", "full_attention"),
        linear_key_heads=32, linear_value_heads=64, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel=4, gated_attention=True,
        sandwich_norm=True, norm_kind="gated", swiglu_limit=10.0,
        n_experts=256, moe_top_k=8, moe_ffn_dim=2048, n_shared_experts=1,
        moe_scoring="sigmoid", moe_select_bias=True, moe_routed_scale=2.5,
        experts_held=(0, 16))
    model = LlamaLM(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        model, params, slots=8, buf_len=1040, adapter_slots=17,
        kv_page_tokens=16, kv_pool_pages=401, prefill_chunk_tokens=512)
    assert eng._state_bytes == 9 * (64 * 128 * 128 * 4 + 3 * 16384 * 2)
    return _engine_programs(eng, one_chip)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_linear_state_is_written_where_it_lies(linear_programs, program):
    """The matrix state (a float32 row of 64 x 128 x 128 a slot and the trash
    row) and the convolution's rows are each updated in place by one scatter
    or update, donated and aliased, and nothing else of their size is copied
    or re-laid out; the latent pool likewise.  The experts run as the two
    grouped-matmul kernels (the clamp inside them), the latent read as its
    kernel, and the core's instructions lie under ``layer_0/linear_attn/core``
    in the program's map."""
    from fedml_tpu.obs import programs
    compiled, donated = linear_programs
    states = [p for p in donated if p.shape[:1] == (9,) and p.ndim == 4]
    convs = [p for p in donated if p.shape == (9, 3, 16384)]
    pools = [p for p in donated if p.shape[:1] == (401,)]
    assert [p.dtype for p in states] == [jnp.float32] and len(convs) == 1 and len(pools) == 1
    hlo = compiled[program].as_text()
    assert "ragged-dot" not in hlo and "%gated_matmul" in hlo and "%grouped_matmul" in hlo
    assert len(re.findall(r"%latent_attention[.\d]* = ", hlo)) == 1
    naming = {"parameter", "get-tuple-element", "tuple", "bitcast"}
    for leaf in states + convs + pools:
        instrs = _pool_sized_instructions(hlo, leaf.size)
        # the tick's gather of its lanes' rows may be the compiler's loop of
        # row reads, which passes the buffer through untouched (a ``while``)
        moving = [(op, line[:200]) for op, line in instrs if op not in naming | {
            "scatter", "dynamic-update-slice", "fusion", "copy-start", "copy-done", "while"}]
        assert not moving, (leaf.shape, moving)
    aliases = re.search(r"input_output_alias=\{(.*?) \}, ", hlo).group(1)
    assert aliases.count("-alias)") == len(donated)
    held = sum(p.size * p.dtype.itemsize for p in states + convs + pools)
    assert compiled[program].memory_analysis().alias_size_in_bytes >= held
    paths = {row["path"] for row in programs.parse_hlo(hlo).values()}
    assert any(p.startswith("layer_0/linear_attn/core") for p in paths), sorted(paths)[:40]


def test_the_tick_reads_its_lanes_rows_under_the_core(linear_programs):
    """The tick's loop of row reads that gathers its 8 lanes' matrix state
    (``f32[8,64,128,128]``) is the core's own: every fusion of that size lies
    under ``layer_0/linear_attn/core`` in the program's map, so the device
    time of the gather is counted with the rule's."""
    from fedml_tpu.obs import programs
    compiled, _ = linear_programs
    hlo = compiled["decode_step"].as_text()
    rows = programs.parse_hlo(hlo)
    fusions = [line.split(" = ")[0].split()[-1].lstrip("%")
               for op, line in _pool_sized_instructions(hlo, 8 * 64 * 128 * 128) if op == "fusion"]
    assert any("dynamic-slice" in name for name in fusions), fusions
    assert all(rows[name]["path"].startswith("layer_0/linear_attn/core") for name in fusions), \
        {name: rows[name]["path"] for name in fusions}


# -- the instruction -> module map of the programs the chip runs ----------------

@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
@pytest.mark.parametrize("model", ["latent", "mixed"])
def test_kernels_map_to_their_name_and_module(request, model, program):
    """``obs/programs.py`` on the text the chip's compiler gives: every Pallas
    custom call of the two paged programs maps to its kernel's ``name=`` and to
    the path of the module that called it (the attention's paged method, the
    expert layer), one a layer, and everything else that can show in a trace
    has a row with a path or ``""``."""
    from fedml_tpu.obs import programs
    compiled, _ = request.getfixturevalue(f"{model}_programs")
    hlo = compiled[program].as_text()
    ops = programs.parse_hlo(hlo)
    calls = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"",
                       hlo, flags=re.M)
    assert calls and all(ops[c]["kernel"] for c in calls)
    kernels = {}
    for c in calls:
        kernels.setdefault(ops[c]["kernel"], []).append(ops[c]["path"])
    read, method = {"latent": ("latent_attention", "_paged_attend"),
                    "mixed": ("paged_attention", "_paged_decode_attend")}[model]
    assert set(kernels) == {read, "gated_matmul", "grouped_matmul"}
    assert sorted(kernels[read]) == [f"layer_{i}/attention.{method}/{read}" for i in (0, 1)]
    sparse = (1,) if model == "latent" else (0, 1)      # the latent model's layer 0 is dense
    for name in ("gated_matmul", "grouped_matmul"):
        assert sorted(kernels[name]) == [f"layer_{i}/moe_mlp/{name}" for i in sparse]
    # the instruction is called what the kernel is, which is what a trace shows
    assert all(c.split(".")[0] == ops[c]["kernel"] for c in calls)
    assert {row["phase"] for row in ops.values()} == {"forward"}
    paths = {row["path"] for row in ops.values()}
    assert {"", "lm_head" if model == "latent" else "tok_embed", "final_norm",
            "layer_0/attn_norm", "layer_1/moe_mlp"} <= paths, sorted(paths)
    # the entry's instructions are all there: what the trace names is in the map
    entry = hlo[hlo.index("\nENTRY "):]
    named = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = ", entry, flags=re.M)
    assert len(named) > 100 and set(named) <= set(ops)


# -- the round's flash kernels in the map, and the rule that counts them --------

ROUND_ATTN_METRICS = ("round_attn_ms", "attn_roofline")
_TPU_CALL = re.compile(
    r"^\s+(?:ROOT )?%([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"", re.M)


@pytest.fixture(scope="module")
def round_like_rows(one_chip):
    """The Pallas calls' rows in the map of a round-shaped program: a ``vmap``
    over two clients of a scan over two local steps of ``jax.grad`` of a
    two-layer LoRA ``LlamaLM``'s loss under ``remat=full`` (the round's
    nesting, which puts ``vmap()`` in a segment of its own), heads of 128,
    grouped KV (4 q heads on 2), seq 256, bf16.  The gate
    ``_use_pallas`` is patched to ``True``: off a chip it is ``False``, and
    seq 256 has no entry in the tile table."""
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.obs import programs

    cfg = LlamaConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_dim=1024, max_seq_len=256, dtype=jnp.bfloat16, lora_rank=4,
                      lora_alpha=4.0, remat="full", attn_impl="flash")
    model = LlamaLM(cfg)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    def loss(lora, params, tokens):
        logits = model.apply({"params": params, "lora": lora}, tokens, train=True)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    def local_steps(lora, params, tokens):
        def step(lora, batch):
            grads = jax.grad(loss)(lora, params, batch)
            return jax.tree_util.tree_map(lambda w, g: w - 1e-3 * g.astype(w.dtype), lora, grads), None
        return jax.lax.scan(step, lora, tokens)[0]

    clients = jax.vmap(local_steps, in_axes=(0, None, 0))

    def described(tree, lead=()):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(lead + a.shape, a.dtype, sharding=one_chip), tree)

    tokens = jax.ShapeDtypeStruct((2, 2, 2, 256), jnp.int32, sharding=one_chip)
    gate = A._use_pallas
    A._use_pallas = lambda s_k, d: True
    try:
        compiled = jax.jit(clients).lower(described(variables["lora"], (2,)),
                                          described(variables["params"]), tokens).compile()
    finally:
        A._use_pallas = gate
    hlo = compiled.as_text()
    rows = programs.parse_hlo(hlo)
    return {c: rows[c] for c in _TPU_CALL.findall(hlo)}


def test_every_flash_call_lies_under_its_layers_attention_in_three_phases(round_like_rows):
    """Each kernel sits at ``layer_i/attention/<name>`` under its own
    ``name=``: per layer one ``flash_fwd`` in the forward, one again in the
    recomputed forward (``remat=full``), and the ``flash_dq`` and
    ``flash_dkv`` passes in the backward, not a second forward under
    ``vjp``."""
    calls = list(round_like_rows.values())
    assert calls and all(row["kernel"] in ("flash_fwd", "flash_dq", "flash_dkv")
                         for row in calls), calls
    assert all(re.fullmatch(rf"layer_[01]/attention/{row['kernel']}", row["path"])
               for row in calls), calls
    for i in (0, 1):
        mine = [row for row in calls if row["path"].startswith(f"layer_{i}/")]
        by_phase = {phase: sorted(row["kernel"] for row in mine if row["phase"] == phase)
                    for phase in ("forward", "recompute", "backward")}
        assert by_phase == {"forward": ["flash_fwd"], "recompute": ["flash_fwd"],
                            "backward": ["flash_dkv", "flash_dq"]}, (i, by_phase)
    # the instruction is called what the kernel is, which is what a trace shows
    assert all(c.split(".")[0] == row["kernel"] for c, row in round_like_rows.items())


@pytest.mark.parametrize("metric", ROUND_ATTN_METRICS)
def test_the_rule_counts_every_flash_call(round_like_rows, metric):
    from readers import ops
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        args = json.load(f)["args"]
    missed = [(c, row) for c, row in round_like_rows.items() if not ops.matches(row, args)]
    assert not missed
