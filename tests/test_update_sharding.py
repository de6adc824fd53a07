"""Cross-replica sharded server update (``update_sharding="scatter"``):
reduce-scatter merge + shard-resident server optimizer state must reproduce
the replicated path bit-for-tolerance for EVERY stateful algorithm, survive
checkpoint round-trips, and count only real clients in padded cohorts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import load_arguments
from fedml_tpu.core import tree as tree_util
from fedml_tpu.core.mesh import CLIENT_AXIS


def args_for(rounds=3, **over):
    args = load_arguments()
    args.update(
        dataset="synthetic", num_classes=10, input_shape=(28, 28, 1),
        train_size=1024, test_size=256, model="lr",
        client_num_in_total=16, client_num_per_round=8, comm_round=rounds,
        epochs=1, batch_size=16, learning_rate=0.1, random_seed=7,
        backend="mesh", frequency_of_the_test=10 ** 9,
    )
    args.update(**over)
    return args


def run_mesh(rounds=3, **over):
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    args = fedml_tpu.init(args_for(rounds=rounds, **over))
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    api = MeshFedAvgAPI(args, None, dataset, model)
    metrics = [api.train_one_round(r) for r in range(rounds)]
    return api, [round(float(m["train_loss"]), 6) for m in metrics]


def assert_tree_close(a, b, atol=2e-5, rtol=1e-4, msg=""):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=rtol, err_msg=msg)


STATEFUL_ALGS = ["FedAvg", "FedOpt", "SCAFFOLD", "FedDyn", "FedNova", "Mime"]


@pytest.mark.parametrize("opt", STATEFUL_ALGS)
def test_scatter_matches_replicated(opt):
    """ISSUE 1 acceptance: scatter-mode final global_params match
    replicated-mode within 2e-5 after >=3 rounds on the 8-device mesh, for
    every algorithm family (stateless pass-through, optax server step, and
    every shard-resident state transition)."""
    assert jax.device_count() == 8
    rep, rep_losses = run_mesh(federated_optimizer=opt,
                               update_sharding="replicated")
    sc, sc_losses = run_mesh(federated_optimizer=opt,
                             update_sharding="scatter")
    assert rep.update_sharding == "replicated"
    assert sc.update_sharding == "scatter"
    assert rep_losses == sc_losses, (opt, rep_losses, sc_losses)
    assert_tree_close(rep.state.global_params, sc.state.global_params,
                      msg=f"{opt} params diverged")
    # the aux server state must agree too: the replicated pytree flattens to
    # the scatter path's (unpadded prefix of the) flat shard-resident vector
    n_shards = sc.n_shards
    for field in ("c_server", "h", "momentum"):
        rep_v, sc_v = getattr(rep.state, field), getattr(sc.state, field)
        assert (rep_v is None) == (sc_v is None), field
        if rep_v is None:
            continue
        flat_rep = np.asarray(tree_util.tree_flatten_1d(rep_v))
        flat_sc = np.asarray(sc_v)[: flat_rep.shape[0]]
        np.testing.assert_allclose(flat_rep, flat_sc, atol=2e-5, rtol=1e-4,
                                   err_msg=field)
    if opt == "FedOpt":
        # Adam moments shard-resident: same treedef, flat leaves
        rep_leaves = jax.tree_util.tree_leaves(rep.state.opt_state)
        sc_leaves = jax.tree_util.tree_leaves(sc.state.opt_state)
        assert len(rep_leaves) > 0 and len(sc_leaves) > 0


@pytest.mark.parametrize("opt", ["SCAFFOLD", "FedDyn"])
def test_scatter_parity_with_padded_cohort(opt):
    """6 sampled clients on 8 shards -> 2 zero-weight pad rows.  SCAFFOLD's
    and FedDyn's |S|/N fraction must count the 6 real clients in BOTH modes
    (regression for the pad-dependent n_sampled drift)."""
    rep, rep_losses = run_mesh(client_num_per_round=6,
                               federated_optimizer=opt,
                               update_sharding="replicated")
    sc, sc_losses = run_mesh(client_num_per_round=6,
                             federated_optimizer=opt,
                             update_sharding="scatter")
    assert rep_losses == sc_losses, (opt, rep_losses, sc_losses)
    assert_tree_close(rep.state.global_params, sc.state.global_params)


def test_compute_aggregates_counts_real_clients_only():
    """sp-path regression (agg_operator): a deliberately padded cohort's
    zero-weight rows must not inflate n_sampled — pre-fix it returned
    weights.shape[0] (8), drifting SCAFFOLD/FedDyn's |S|/N by 33%."""
    from fedml_tpu.ml.aggregator.agg_operator import ServerOptimizer

    args = load_arguments()
    args.update(federated_optimizer="FedAvg", client_num_in_total=16)
    opt = ServerOptimizer(args)
    stacked = {"w": jnp.ones((8, 3))}
    weights = jnp.asarray([2.0, 1.0, 3.0, 1.0, 2.0, 1.0, 0.0, 0.0])
    agg = opt.compute_aggregates(
        opt.init({"w": jnp.zeros((3,))}), stacked, weights)
    assert float(agg["n_sampled"]) == 6.0


def test_scatter_matches_sp_engine():
    """Three-way parity: sp == mesh-replicated == mesh-scatter (tentpole
    acceptance).  Covers the full seed-matched curve, not just final
    params."""
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    args = fedml_tpu.init(args_for())
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    sp = FedAvgAPI(args, None, dataset, model)
    sp_losses = [round(float(sp.train_one_round(r)["train_loss"]), 6)
                 for r in range(3)]
    sc, sc_losses = run_mesh(update_sharding="scatter")
    # psum_scatter sums in another order than sp's one reduction: an ULP,
    # which may straddle a rounding boundary of the sixth decimal
    assert sp_losses == pytest.approx(sc_losses, abs=2e-6), (sp_losses,
                                                             sc_losses)
    assert_tree_close(sp.state.global_params, sc.state.global_params)


def test_sharded_state_layout():
    """The scatter state's aux fields really are client-axis sharded flat
    vectors (not replicated pytrees), and global_params stays replicated."""
    from jax.sharding import PartitionSpec as P

    api, _ = run_mesh(rounds=1, federated_optimizer="FedOpt",
                      update_sharding="scatter")
    flat_len = tree_util.padded_flat_size(api.state.global_params,
                                          api.n_shards)
    moments = [l for l in jax.tree_util.tree_leaves(api.state.opt_state)
               if np.ndim(l) >= 1]
    assert moments, "FedOpt must keep Adam moments"
    for leaf in moments:
        assert leaf.shape == (flat_len,)
        assert leaf.sharding.spec == P(CLIENT_AXIS), leaf.sharding
    for leaf in jax.tree_util.tree_leaves(api.state.global_params):
        assert leaf.sharding.spec == P(), leaf.sharding


def test_sharded_opt_state_checkpoint_roundtrip(tmp_path):
    """Shard-resident opt_state must survive checkpoint save/restore with
    identical values and continue training to the same curve as an
    uninterrupted run."""
    ck = str(tmp_path / "ck")
    api, _ = run_mesh(rounds=2, federated_optimizer="FedOpt",
                      update_sharding="scatter", checkpoint_dir=ck,
                      checkpoint_freq=1)
    api.maybe_checkpoint(1)

    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    args = fedml_tpu.init(args_for(federated_optimizer="FedOpt",
                                   update_sharding="scatter",
                                   checkpoint_dir=ck, checkpoint_freq=1))
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    api2 = MeshFedAvgAPI(args, None, dataset, model)
    start = api2.maybe_resume()
    assert start == 2
    assert int(api2.state.round_idx) == int(api.state.round_idx)
    assert_tree_close(api.state.global_params, api2.state.global_params,
                      atol=0, rtol=0, msg="restored params differ")
    assert_tree_close(api.state.opt_state, api2.state.opt_state,
                      atol=0, rtol=0, msg="restored opt_state differs")
    # restored state keeps training on the same curve as the fresh run
    uninterrupted, _ = run_mesh(rounds=3, federated_optimizer="FedOpt",
                                update_sharding="scatter")
    api2.train_one_round(2)
    assert_tree_close(uninterrupted.state.global_params,
                      api2.state.global_params)


def test_async_staging_off_is_identical():
    """async_staging is a pure overlap optimization: disabling it must not
    change the curve."""
    on, on_losses = run_mesh(async_staging=True)
    off, off_losses = run_mesh(async_staging=False)
    assert on_losses == off_losses
    assert_tree_close(on.state.global_params, off.state.global_params,
                      atol=0, rtol=0)
