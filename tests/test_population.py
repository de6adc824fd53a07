"""Federated round algebra + vmapped experiment populations (ISSUE 7).

Four layers:

1. the primitives (``core/federated.py``): broadcast / client_map /
   weighted_reduce semantics, the AlgorithmSpec registry, and the
   spec-driven aggregate builder matching the historical hand-rolled math;
2. q-FedAvg — the "new algorithms are a spec, not an engine fork" payoff —
   trains and holds sp ≡ mesh(replicated) ≡ mesh(scatter) parity to 2e-5;
3. populations: every member of a vmapped sweep matches its own sequential
   single-config run, fused (round_block) populations match unfused ones,
   steady-state populations compile ONCE and add zero extra host syncs;
4. checkpointing: the (P,)-stacked ServerState round-trips through orbax
   and a single member extracts/restores as a normal 1-experiment state.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import load_arguments
from fedml_tpu.core import federated as fed
from fedml_tpu.core import tree as tree_util


def base_args(**over):
    args = load_arguments()
    args.update(
        dataset="synthetic", num_classes=10, input_shape=(14, 14, 1),
        train_size=768, test_size=192, model="lr",
        client_num_in_total=12, client_num_per_round=6, comm_round=3,
        epochs=1, batch_size=16, learning_rate=0.1, random_seed=11,
        partition_method="homo", frequency_of_the_test=10 ** 9,
    )
    args.update(**over)
    return args


def make_api(cls=None, **over):
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    args = fedml_tpu.init(base_args(**over))
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    return (cls or FedAvgAPI)(args, None, dataset, model)


def assert_tree_close(a, b, atol=2e-5, rtol=1e-4, msg=""):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=rtol, err_msg=msg)


def assert_adam_server_close(got, seq, server_lr, msg=""):
    """``assert_tree_close`` for a FedOpt run, whose band is float32's.

    The vmapped population and the sequential run merge the clients'
    parameters in a different order: after round 0 they agree to the last
    bit in most elements and to one ULP (2.4e-7 at |w| in [2, 4)) in the
    rest.  The server's pseudo-gradient is a difference of such values, so
    where it is small (1e-4) one ULP is a part in a thousand of it, and the
    Adam step divides it by its own running size: a ULP of the merged
    parameters moves the next round's by ``server_lr * ulp / sqrt(nu)``.
    That term is added to the band element by element, from the sequential
    run's own second moments; every element with a gradient worth the name
    is still held to ``rtol=1e-4, atol=2e-5`` (measured here: the one
    element of 1,960 outside the plain band is 0.2 of this one)."""
    import optax
    adam = next(x for x in jax.tree_util.tree_leaves(
        seq.state.opt_state,
        is_leaf=lambda n: isinstance(n, optax.ScaleByAdamState))
        if isinstance(x, optax.ScaleByAdamState))
    for x, y, nu in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(seq.state.global_params),
                        jax.tree_util.tree_leaves(adam.nu)):
        x, y = np.asarray(x), np.asarray(y)
        band = 2e-5 + 1e-4 * np.abs(y) + \
            server_lr * np.spacing(np.abs(y)) / (np.sqrt(np.asarray(nu)) + 1e-8)
        worst = (np.abs(x - y) / band).max()
        assert worst <= 1.0, (msg, float(worst))


# -- 1. primitives ----------------------------------------------------------

def test_broadcast_is_identity_placement():
    tree = {"w": jnp.arange(4.0), "b": jnp.ones(())}
    out = fed.broadcast(tree)
    assert out is tree


def test_client_map_vmap_matches_scan():
    xs = jnp.arange(12.0).reshape(4, 3)
    ys = jnp.arange(4.0)
    fn = lambda x, y: jnp.sum(x) * y
    v = fed.client_map(fn, "vmap")(xs, ys)
    s = fed.client_map(fn, "scan")(xs, ys)
    np.testing.assert_allclose(np.asarray(v), np.asarray(s))
    with pytest.raises(ValueError):
        fed.client_map(fn, "pmap")


def test_weighted_reduce_matches_stacked_average():
    stacked = {"w": jnp.arange(12.0).reshape(4, 3)}
    w = jnp.asarray([1.0, 2.0, 0.0, 1.0])
    got = fed.weighted_reduce(stacked, w)
    want = tree_util.stacked_weighted_average(stacked, w)
    assert_tree_close(got, want)


def test_algorithm_registry_covers_the_zoo():
    for name in ("fedavg", "fedprox", "fedopt", "scaffold", "feddyn",
                 "fednova", "mime", "fedsgd", "qfedavg"):
        spec = fed.get_spec(name)
        assert spec.name == name
    assert fed.get_spec("scaffold").client_state
    assert fed.get_spec("feddyn").client_state
    assert not fed.get_spec("fedavg").client_state
    assert not fed.get_spec("qfedavg").avg_params
    assert fed.get_spec("qfedavg").update is not None
    with pytest.raises(KeyError):
        fed.get_spec("no_such_algorithm")


def test_spec_aggregates_match_historical_math():
    """The spec-driven builder reproduces the hand-rolled stage-1 math the
    engines used to carry per algorithm (drop-in acceptance)."""
    import types
    from fedml_tpu.ml.aggregator.agg_operator import ServerOptimizer

    rng = np.random.default_rng(0)
    C = 5
    stacked = {"w": jnp.asarray(rng.normal(size=(C, 4, 3)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, 3)), jnp.float32)}
    w = jnp.asarray([2.0, 1.0, 3.0, 0.0, 1.0])
    tau = jnp.asarray([3.0, 2.0, 4.0, 1.0, 2.0])
    gparams = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((3,))}

    args = base_args(federated_optimizer="FedNova")
    opt = ServerOptimizer(args)
    state = opt.init(gparams)
    agg = opt.compute_aggregates(state, stacked, w,
                                 aux={"tau": tau, "grad_sum": stacked})
    # hand-rolled FedNova reference
    p = w / jnp.sum(w)
    deltas = jax.tree_util.tree_map(
        lambda yi, gx: (gx[None] - yi) / jnp.maximum(
            tau.reshape((-1,) + (1,) * (yi.ndim - 1)), 1.0),
        stacked, gparams)
    want_nova = tree_util.stacked_weighted_average(deltas, w)
    assert_tree_close(agg["nova_d"], want_nova)
    np.testing.assert_allclose(float(agg["tau_eff"]),
                               float(jnp.sum(p * tau)), rtol=1e-6)
    assert float(agg["n_sampled"]) == 4.0  # zero-weight row excluded


def test_hparams_resolution_and_seed_fold():
    hp = fed.HParams(server_lr=jnp.asarray(0.5), seed=jnp.asarray(3))
    assert float(fed.resolve(hp, "server_lr", 1.0)) == 0.5
    assert fed.resolve(hp, "client_lr", 0.03) == 0.03
    assert fed.resolve(None, "server_lr", 1.0) == 1.0
    # lr ratio: None when not swept (bitwise default path), exact ratio else
    assert fed.lr_ratio(None, "client_lr", 0.1) is None
    assert fed.lr_ratio(fed.HParams(), "client_lr", 0.1) is None
    np.testing.assert_allclose(
        float(fed.lr_ratio(hp, "server_lr", 2.0)), 0.25)
    with pytest.raises(ValueError):
        fed.lr_ratio(hp, "server_lr", 0.0)
    key = jax.random.PRNGKey(0)
    k3 = fed.fold_seed(key, hp)
    assert not np.array_equal(np.asarray(k3), np.asarray(key))
    assert np.array_equal(np.asarray(fed.fold_seed(key, None)),
                          np.asarray(key))


def test_parse_population_grid_and_validation():
    args = base_args(population_axes={"server_lr": [1.0, 0.5],
                                      "seed": [0, 1, 2]})
    pop = fed.parse_population(args)
    assert pop.size == 6
    assert pop.members[0] == {"server_lr": 1.0, "seed": 0}
    assert pop.members[-1] == {"server_lr": 0.5, "seed": 2}
    assert pop.hparams.server_lr.shape == (6,)
    assert pop.hparams.client_lr is None

    assert fed.parse_population(base_args()) is None
    seeded = fed.parse_population(base_args(population=4))
    assert seeded.size == 4 and tuple(
        int(s) for s in seeded.hparams.seed) == (0, 1, 2, 3)
    with pytest.raises(ValueError):
        fed.parse_population(base_args(population_axes={"bogus": [1]}))
    with pytest.raises(ValueError):
        fed.parse_population(base_args(population=3,
                                       population_axes={"seed": [0, 1]}))


# -- 2. q-FedAvg: an algorithm as a ~20-line spec ---------------------------

def test_qfedavg_learns_sp():
    api = make_api(federated_optimizer="qfedavg", qfed_q=1.0, comm_round=8)
    _, acc0 = api.evaluate()
    api.train()
    _, acc1 = api.evaluate()
    assert acc1 > max(acc0, 0.3), (acc0, acc1)


@pytest.mark.parametrize("update_sharding", ["replicated", "scatter"])
def test_qfedavg_sp_mesh_parity(update_sharding):
    """ISSUE 7 satellite: q-FedAvg lands as a RoundProgram spec and is
    drop-in on BOTH engines — sp ≡ 8-shard mesh to 2e-5."""
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    assert jax.device_count() == 8
    sp = make_api(federated_optimizer="qfedavg", qfed_q=2.0)
    mesh = make_api(MeshFedAvgAPI, federated_optimizer="qfedavg",
                    qfed_q=2.0, backend="mesh",
                    client_num_in_total=16, client_num_per_round=8,
                    update_sharding=update_sharding)
    sp_losses = [round(float(sp.train_one_round(r)["train_loss"]), 6)
                 for r in range(3)]
    mesh_losses = [round(float(mesh.train_one_round(r)["train_loss"]), 6)
                   for r in range(3)]
    # same seed => same cohorts; run sp at the mesh's cohort shape
    sp2 = make_api(federated_optimizer="qfedavg", qfed_q=2.0,
                   client_num_in_total=16, client_num_per_round=8)
    sp2_losses = [round(float(sp2.train_one_round(r)["train_loss"]), 6)
                  for r in range(3)]
    assert sp2_losses == mesh_losses, (sp2_losses, mesh_losses)
    assert_tree_close(sp2.state.global_params, mesh.state.global_params,
                      msg=f"qfedavg diverged on {update_sharding}")
    assert sp_losses[0] > 0  # smoke: the small-cohort run trained too


def test_qfedavg_q_zero_matches_weightless_fedavg_direction():
    """q→0 sanity: the q-FedAvg step direction loses its loss-weighting
    (u_k -> 1), so two clients with very different losses contribute
    equally; with q=2 the high-loss member dominates.  Checked through the
    fairness metric: q=2 narrows the per-client accuracy spread vs q=0."""
    api0 = make_api(federated_optimizer="qfedavg", qfed_q=0.0,
                    comm_round=6, partition_method="hetero")
    api2 = make_api(federated_optimizer="qfedavg", qfed_q=2.0,
                    comm_round=6, partition_method="hetero")
    api0.train()
    api2.train()
    f0 = api0.evaluate_per_client()
    f2 = api2.evaluate_per_client()
    # both train; the q=2 run must not collapse (fairness objective sane)
    assert f0["acc_mean"] > 0.2 and f2["acc_mean"] > 0.2


# -- 3. populations ---------------------------------------------------------

POP_ALGS = [
    ("FedOpt", {"server_lr": [1.0, 0.3]}, {"server_lr": 1.0}),
    ("FedAvg", {"client_lr": [0.1, 0.04]}, {"learning_rate": 0.1}),
    ("SCAFFOLD", {"client_lr": [0.1, 0.05]}, {"learning_rate": 0.1}),
    ("FedDyn", {"feddyn_alpha": [0.01, 0.1]}, {"feddyn_alpha": 0.01}),
    ("FedProx", {"prox_mu": [0.1, 0.5]}, {"fedprox_mu": 0.1}),
]


def _assert_member(alg, pop, member, seq):
    got = fed.population_member(pop.state.global_params, member)
    msg = f"{alg} member {member}"
    if alg == "FedOpt":
        assert_adam_server_close(got, seq, float(seq.args.server_lr), msg)
    else:
        assert_tree_close(got, seq.state.global_params, msg=msg)


@pytest.mark.parametrize("alg,axes,member0_args", POP_ALGS,
                         ids=[a for a, _, _ in POP_ALGS])
def test_population_members_match_sequential_runs(alg, axes, member0_args):
    """ISSUE 7 tentpole acceptance: each member of a vmapped population
    reproduces its own sequential single-config run — the sweep is P real
    experiments, not an approximation."""
    pop = make_api(federated_optimizer=alg, population_axes=axes)
    assert pop.population.size == 2
    for r in range(3):
        metrics = pop.train_one_round(r)
    losses = np.asarray(metrics["train_loss"])
    assert losses.shape == (2,)

    # sequential member 0: the base config (hparam == its static default)
    seq = make_api(federated_optimizer=alg, **member0_args)
    for r in range(3):
        seq_metrics = seq.train_one_round(r)
    _assert_member(alg, pop, 0, seq)
    np.testing.assert_allclose(losses[0],
                               float(seq_metrics["train_loss"]),
                               atol=2e-5, rtol=1e-4)

    # sequential member 1: the swept value as the static config
    name, values = next(iter(axes.items()))
    static_name = {"server_lr": "server_lr", "client_lr": "learning_rate",
                   "feddyn_alpha": "feddyn_alpha",
                   "prox_mu": "fedprox_mu"}[name]
    seq1 = make_api(federated_optimizer=alg, **{static_name: values[1]})
    for r in range(3):
        seq1.train_one_round(r)
    _assert_member(alg, pop, 1, seq1)


def test_population_seed_axis_gives_distinct_members():
    """population: P alone sweeps seeds — members share cohorts but draw
    member-distinct in-round rng (fold_in(key, seed), never the same
    stream; the fedlint rng_vmap_member fixture pins the anti-pattern)."""
    api = make_api(population=3, model="cnn", comm_round=2,
                   train_size=384, client_num_in_total=6,
                   client_num_per_round=4)
    m = api.train_one_round(0)
    losses = np.asarray(m["train_loss"])
    assert losses.shape == (3,)
    # dropout draws from the member-folded round key, so one update is
    # enough for member params to diverge
    api.train_one_round(1)
    p0 = fed.population_member(api.state.global_params, 0)
    p1 = fed.population_member(api.state.global_params, 1)
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree_util.tree_leaves(p0),
                             jax.tree_util.tree_leaves(p1))]
    assert max(diffs) > 0, "seed-swept members never diverged"


def test_population_fused_matches_unfused():
    """The population block (vmap over jit(lax.scan(round))) reproduces
    the per-round population dispatch."""
    axes = {"client_lr": [0.1, 0.05, 0.02]}
    unfused = make_api(federated_optimizer="FedAvg", population_axes=axes,
                       comm_round=4)
    for r in range(4):
        unfused.train_one_round(r)
    fused = make_api(federated_optimizer="FedAvg", population_axes=axes,
                     comm_round=4, round_block=2)
    fused.train()
    assert_tree_close(unfused.state.global_params,
                      fused.state.global_params, atol=1e-6, rtol=1e-6)
    last = fused.metrics_history[-1]
    assert last["members"] == 3
    assert last["member_train_loss_best"] <= last["member_train_loss_worst"]


def test_population_compiles_once_and_adds_no_syncs():
    """ISSUE 7 acceptance: steady-state population rounds add ZERO XLA
    compilations and ZERO explicit device transfers beyond the staging
    the single-config round already does — P experiments genuinely share
    one compiled program."""
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit

    api = make_api(federated_optimizer="FedOpt",
                   population_axes={"server_lr": [1.0, 0.5, 0.25, 0.1]})
    api.train_one_round(0)
    api.train_one_round(1)
    with JaxRuntimeAudit() as audit:
        for r in (2, 3, 4):
            api.train_one_round(r)
    assert audit.compilations == 0, (
        f"steady-state population rounds recompiled {audit.compilations}x")
    assert audit.device_gets == 0, (
        "population rounds must not read back to host mid-stream")


def test_population_scaffold_table_stacked_per_member():
    """Per-client state tables stack on the member axis: each member's
    SCAFFOLD control variates evolve under its own hparams."""
    api = make_api(federated_optimizer="SCAFFOLD",
                   population_axes={"client_lr": [0.1, 0.02]})
    for r in range(3):
        api.train_one_round(r)
    leaves = jax.tree_util.tree_leaves(api.client_table)
    assert all(l.shape[0] == 2 for l in leaves)
    t0 = fed.population_member(api.client_table, 0)
    t1 = fed.population_member(api.client_table, 1)
    diff = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree_util.tree_leaves(t0),
                               jax.tree_util.tree_leaves(t1)))
    assert diff > 0, "member tables identical despite different client lr"


def test_population_eval_and_records():
    api = make_api(federated_optimizer="FedAvg",
                   population_axes={"client_lr": [0.1, 0.01]},
                   comm_round=2, frequency_of_the_test=1)
    api.train()
    loss, acc = api.evaluate()
    assert api.member_eval["acc"].shape == (2,)
    assert acc == pytest.approx(float(api.member_eval["acc"].mean()))
    rec = api.metrics_history[-1]
    assert rec["members"] == 2
    assert rec["member_train_loss_best"] <= rec["train_loss"] <= \
        rec["member_train_loss_worst"]


def test_population_rejected_on_mesh_and_host_data():
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    # population + mesh now fails EARLY in fedml_tpu.init (arguments.py
    # validate_args) with one error naming both flags, instead of a
    # NotImplementedError deep inside the engine after dataset/model build
    with pytest.raises(ValueError, match="population.*mesh"):
        make_api(MeshFedAvgAPI, backend="mesh", population=2,
                 client_num_in_total=16, client_num_per_round=8)
    with pytest.raises(ValueError):
        make_api(population=2, device_data=False)


# -- 4. checkpointing -------------------------------------------------------

def test_population_checkpoint_roundtrip_and_member_extraction(tmp_path):
    """ISSUE 7 acceptance: the (P,)-stacked ServerState round-trips through
    orbax, and ONE member extracts/restores as a normal single-experiment
    state (continuing training standalone)."""
    from fedml_tpu.core.checkpoint import RoundCheckpointer

    axes = {"client_lr": [0.1, 0.05]}
    api = make_api(federated_optimizer="SCAFFOLD", population_axes=axes,
                   comm_round=4, checkpoint_dir=str(tmp_path),
                   checkpoint_freq=2)
    for r in range(3):
        api.train_one_round(r)
        api.maybe_checkpoint(r)

    resumed = make_api(federated_optimizer="SCAFFOLD",
                       population_axes=axes, comm_round=4,
                       checkpoint_dir=str(tmp_path), checkpoint_freq=2)
    start = resumed.maybe_resume()
    assert start == 3
    assert_tree_close(resumed.state.global_params,
                      api.state.global_params, atol=0, rtol=0)
    assert_tree_close(resumed.client_table, api.client_table,
                      atol=0, rtol=0)

    # extract member 1 from the restored stacked state -> a normal
    # 1-experiment state a fresh single-config api can continue from
    member = fed.population_member(resumed.state, 1)
    single = make_api(federated_optimizer="SCAFFOLD", learning_rate=0.05,
                      comm_round=4)
    assert jax.tree_util.tree_structure(single.state) == \
        jax.tree_util.tree_structure(member)
    single.state = member
    single.client_table = fed.population_member(resumed.client_table, 1)
    metrics = single.train_one_round(3)   # continues without retracing woes
    assert np.isfinite(float(metrics["train_loss"]))

    # and the continued member matches the population continuing in place
    api.train_one_round(3)
    assert_tree_close(single.state.global_params,
                      fed.population_member(api.state.global_params, 1),
                      msg="extracted member diverged from population")
