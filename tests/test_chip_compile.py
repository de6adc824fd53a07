"""The main path's kernels, compiled for a TPU v5e that is described and not
attached (section 2 of the on-chip-measurement guide): what the chip's
compiler refuses costs no chip time.  Nothing runs, so these say nothing
about results or speed.

The topology is described inside a module-scoped fixture — never while a
module is imported — and every such compile lives in THIS file: the process
that describes the topology loads the TPU's library and keeps it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from fedml_tpu.ops import attention as A

# (batch, q heads, kv heads, seq, head_dim): the tile table's one shape, a
# GQA variant of it, the 1.075B flagship's attention as chip_smoke.py's
# kernel phase and a two-client cohort trace it, and two long-context GQA/MHA
# shapes at head_dim 128
SHAPES = [
    (4, 12, 12, 1024, 64),
    (2, 32, 4, 1024, 64),
    (2, 16, 8, 256, 128),
    (2, 16, 8, 2048, 128),
    (1, 32, 32, 2048, 128),
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
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", SHAPES, ids=_ids)
def test_flash_backward_compiles_for_v5e(one_chip, shape):
    b, h, _, s, _ = shape
    q, kv = _qkv(shape, one_chip)
    lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one_chip)
    bwd = jax.jit(lambda q, k, v, out, lse, do: A.flash_attention_bwd_pallas(
        q, k, v, out, lse, do, True, None))
    compiled = bwd.lower(q, kv, kv, q, lse, q).compile()
    # the dq pass and the dk/dv pass
    assert compiled.as_text().count("tpu_custom_call") >= 2


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
