"""fedverify — AOT lowering-level contract checks (ISSUE 10).

Three tiers:

1. parser/check units — pure functions over synthetic HLO text and
   synthetic reports (no lowering, no jax programs);
2. the tier-1 GATE — every canonical program lowers, compiles on the
   8-virtual-device CPU host, and verifies with ZERO unsuppressed
   violations against the committed manifest
   (``tests/data/fedverify/contracts.json``) — the fedverify twin of the
   fedlint zero-errors gate;
3. mutation tests — each of the five contract families must FAIL when
   its invariant is broken: an injected re-replication (the PR 6 bug
   class), a dropped donation, byte-model drift, an HBM over-fit the
   estimator would have admitted, and an over-budget recompile surface.

Everything runs on CPU; no TPU needed (the point of the lowering-level
checker).
"""

import dataclasses
import os

import numpy as np
import pytest

import jax

from fedml_tpu.analysis import fedverify as fv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- 1. parser / check units ------------------------------------------------

def test_parse_replica_groups_explicit_and_iota():
    assert fv._parse_replica_groups("{{0,1,2,3,4,5,6,7}}") == \
        [[0, 1, 2, 3, 4, 5, 6, 7]]
    assert fv._parse_replica_groups("{{0,2},{1,3}}") == [[0, 2], [1, 3]]
    # iota v2 form: [n_groups, group]<=[dims] with optional transpose
    assert fv._parse_replica_groups("[1,8]<=[8]") == [list(range(8))]
    assert fv._parse_replica_groups("[4,2]<=[8]") == \
        [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert fv._parse_replica_groups("[2,4]<=[4,2]T(1,0)") == \
        [[0, 2, 4, 6], [1, 3, 5, 7]]


def test_classify_groups_axes():
    # (4, 2) client x model mesh: id = client * 2 + model
    assert fv.classify_groups([[0, 2, 4, 6], [1, 3, 5, 7]], (4, 2)) == \
        "client"
    assert fv.classify_groups([[0, 1], [2, 3], [4, 5], [6, 7]], (4, 2)) \
        == "model"
    assert fv.classify_groups([list(range(8))], (4, 2)) == "world"
    assert fv.classify_groups([list(range(8))], (8, 1)) == "client"
    assert fv.classify_groups([[0], [1]], (8, 1)) == "none"
    assert fv.classify_groups([], (8, 1)) == "none"


_HLO = """\
HloModule jit_round_fn, is_scheduled=true, input_output_alias={ {0}: \
(0, {}, may-alias), {15}: (10, {}, may-alias) }, \
entry_computation_layout={(s32[])->(s32[])}, num_partitions=8

ENTRY %main {
  %reduce-scatter.1 = f32[982]{0} reduce-scatter(f32[7856]{0} %fusion), \
channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %all-reduce.5 = f32[] all-reduce(f32[] %bitcast.22), channel_id=1, \
replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true
  %all-gather = f32[7856]{0} all-gather(f32[982]{0} %fusion.2), \
channel_id=11, replica_groups=[1,8]<=[8], dimensions={0}
  %collective-permute = f32[4]{0} collective-permute(f32[4]{0} %slice), \
channel_id=12, source_target_pairs={{0,1},{1,2}}
  %all-reduce-done = f32[] all-reduce-done(f32[] %all-reduce-start)
}
"""


def test_parse_collectives_census():
    ops = fv.parse_collectives(_HLO, (8, 1))
    kinds = sorted((o.kind, o.axis) for o in ops)
    assert kinds == [("all-gather", "client"), ("all-reduce", "client"),
                     ("collective-permute", "client"),
                     ("reduce-scatter", "client")]
    by_kind = {o.kind: o for o in ops}
    # reductions count operand bytes, gathers count result bytes
    assert by_kind["reduce-scatter"].nbytes == 7856 * 4
    assert by_kind["all-gather"].nbytes == 7856 * 4
    assert by_kind["all-reduce"].nbytes == 4
    assert by_kind["collective-permute"].nbytes == 16


def test_parse_collectives_operands_by_name():
    """The installed XLA prints operands by name only and combines
    reductions into one tuple-shaped op whose ``/*index=5*/`` markers
    carry an ``=``: bytes come from the result shapes."""
    hlo = (
        "  %all-reduce = (f32[10]{0}, f32[], f32[784,10]{1,0}, f32[], "
        "f32[], /*index=5*/f32[]) all-reduce(%a, %b, %c, %d, %e, "
        "/*index=5*/%f), channel_id=1, "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%region\n"
        "  %reduce_scatter.7 = f32[982]{0} reduce-scatter(%fusion), "
        "channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, "
        "dimensions={0}, to_apply=%region\n"
        "  %all-gather.2 = f32[7840]{0} all-gather(%fusion.3), "
        "channel_id=13, replica_groups=[1,8]<=[8], dimensions={0}\n")
    by_kind = {o.kind: o for o in fv.parse_collectives(hlo, (8, 1))}
    assert by_kind["all-reduce"].nbytes == (10 + 784 * 10 + 4) * 4
    assert by_kind["reduce-scatter"].nbytes == 982 * 8 * 4
    assert by_kind["all-gather"].nbytes == 7840 * 4
    assert by_kind["all-gather"].operand_bytes == 7840 * 4 // 8


def test_parse_io_aliases_nested_braces():
    # the alias map nests {} (the empty output-shape-index tuple): a
    # naive first-} regex sees only the first entry
    assert fv.parse_io_aliases(_HLO) == {0, 10}
    assert fv.parse_num_partitions(_HLO) == 8


_STABLEHLO = """\
module @jit_round_fn {
  func.func public @main(%arg0: tensor<i32> {jax.buffer_donor = true}, \
%arg1: tensor<10xf32> {jax.buffer_donor = true}, \
%arg2: tensor<8x2x16xi32>, %arg3: tensor<8xf32>) -> (tensor<i32>) {
  }
}
"""


def test_stablehlo_args_and_pruning_alignment():
    args = fv.parse_stablehlo_args(_STABLEHLO)
    assert [(s, d) for s, d, _ in args] == [
        ((), "i32"), ((10,), "f32"), ((8, 2, 16), "i32"), ((8,), "f32")]
    assert [donor for _, _, donor in args] == [True, True, False, False]
    # flat leaves include a key leaf jit PRUNED (dead rng): alignment
    # must skip it so later indices don't shift
    leaves = [((), "i32"), ((10,), "f32"), ((2,), "ui32"),
              ((8, 2, 16), "i32"), ((8,), "f32")]
    kept, undonated = fv.align_donated_args(leaves, {0, 1}, args)
    assert kept == {0, 1} and undonated == set()
    # the same leaves against a module with NO donor marks = the
    # donation was lost at the jit boundary
    stripped = [(s, d, False) for s, d, _ in args]
    kept, undonated = fv.align_donated_args(leaves, {0, 1}, stripped)
    assert undonated == {0, 1}


def _report(**over):
    base = dict(
        name="synthetic", mesh_shape=(8, 1), num_partitions=8,
        collectives=[
            fv.CollectiveOp("reduce-scatter", "client", 31424,
                            "f32[982]", 31424, 3928, ((0, 1),)),
            fv.CollectiveOp("all-gather", "client", 31424,
                            "f32[7856]", 3928, 31424, ((0, 1),)),
        ],
        requested_collectives={"reduce-scatter": 1},
        donated_params={0, 1}, undonated_params=set(),
        aliased_params={0, 1},
        sharding_violations=[], rereplicated=[], n_sharding_leaves=4,
        modeled_bytes={"client": 62848.0},
        memory={"argument": 800_000.0, "output": 30_000.0,
                "temp": 150_000.0, "alias": 30_000.0},
        estimate_bytes=1_200_000.0,
        signatures=["sig_a"], signature_budget=1,
    )
    base.update(over)
    return fv.ProgramReport(**base)


def _entry(rep, **over):
    e = rep.to_manifest_entry()
    e.update({"bytes_tolerance": fv.DEFAULT_BYTES_TOL,
              "model_ratio_band": list(fv.DEFAULT_RATIO_BAND),
              "hbm_budget_bytes": fv.DEFAULT_HBM_BUDGET,
              "signature_budget": rep.signature_budget})
    e.update(over)
    return e


def _rules(findings, unsuppressed_only=True):
    return sorted({f.rule for f in findings
                   if not (unsuppressed_only and f.suppressed)})


def test_run_checks_clean_report_is_clean():
    rep = _report()
    assert fv.run_checks(rep, _entry(rep)) == []


def test_census_tamper_fails():
    rep = _report()
    e = _entry(rep)
    e["collectives"] = {"reduce-scatter.client": 1}  # drop the gather
    assert "collective-census" in _rules(fv.run_checks(rep, e))
    e = _entry(rep)
    e["census_bytes"] = {"client": 10_000}           # bytes drifted
    assert "collective-census" in _rules(fv.run_checks(rep, e))


def test_byte_model_drift_fails():
    # the ObsCarry model shrinks 10x (someone "simplified" the wire
    # model): census/model ratio leaves the pinned band
    rep = _report(modeled_bytes={"client": 6_284.0})
    assert "byte-model-drift" in _rules(fv.run_checks(rep, _entry(rep)))
    # model prices zero traffic on an axis the module really uses
    rep = _report(modeled_bytes={})
    assert "byte-model-drift" in _rules(fv.run_checks(rep, _entry(rep)))


def test_hbm_overfit_mutant_fails():
    rep = _report()
    # estimator (mutated to under-price) admits the config under a
    # budget the lowering busts: measured 950KB > budget 900KB >= est
    rep2 = dataclasses.replace(rep, estimate_bytes=800_000.0)
    e = _entry(rep2, hbm_budget_bytes=900_000)
    fs = fv.run_checks(rep2, e)
    assert "hbm-fit" in _rules(fs)
    # and an estimator that no longer upper-bounds the lowering is
    # flagged even under a huge budget
    fs = fv.run_checks(rep2, _entry(rep2))
    assert "hbm-fit" in _rules(fs)


def test_recompile_surface_over_budget_fails():
    rep = _report(signatures=["sig_a", "sig_b", "sig_a", "sig_c"])
    fs = fv.run_checks(rep, _entry(rep))
    assert "recompile-surface" in _rules(fs)
    assert "presents 3 distinct" in \
        [f.message for f in fs if f.rule == "recompile-surface"][0]


def test_dropped_donation_synthetic_fails():
    rep = _report(aliased_params={0})          # XLA dropped leaf 1
    assert "donation-aliasing" in _rules(fv.run_checks(rep, _entry(rep)))
    rep = _report(undonated_params={1})        # lost at the jit boundary
    assert "donation-aliasing" in _rules(fv.run_checks(rep, _entry(rep)))


def test_manifest_suppressions_apply():
    rep = _report(signatures=["a", "b"])
    sup = [{"program": "synthetic", "rule": "recompile-surface",
            "reason": "hetero pow2 classes are the contract"}]
    fs = fv.run_checks(rep, _entry(rep), sup)
    assert all(f.suppressed for f in fs if f.rule == "recompile-surface")
    assert fv.exit_code(fs) == 0
    # a suppression for another program must not leak
    sup[0]["program"] = "other"
    fs = fv.run_checks(rep, _entry(rep), sup)
    assert fv.exit_code(fs) == 1


def test_missing_manifest_entry_warns():
    fs = fv.run_checks(_report(), None)
    assert _rules(fs) == ["manifest-missing"]
    assert all(f.severity == fv.WARNING for f in fs)


# -- 2. the tier-1 gate -----------------------------------------------------

@pytest.fixture(scope="module")
def verified():
    """Build + lower + check EVERY canonical program once per module."""
    findings, reports = fv.verify_programs()
    return findings, {r.name: r for r in reports}


def test_fedverify_zero_unsuppressed_violations(verified):
    """The enforced gate (ISSUE 10 acceptance): every canonical program
    — sp round, mesh 1-D/2-D x replicated/scatter, fused round_block=8,
    population P=4, and the serving batched step — lowers, compiles,
    and verifies clean against the committed manifest."""
    findings, reports = verified
    assert set(reports) == set(fv.PROGRAMS)
    active = [f for f in findings if not f.suppressed]
    assert active == [], "\n" + fv.render_findings(findings,
                                                   tool="fedverify")
    assert fv.exit_code(findings) == 0


def test_mesh1d_scatter_census_golden(verified):
    """Committed lowered-module golden for the minimal 8-shard scatter
    config: the facts that must survive any toolchain bump are pinned
    structurally (the full census lives in contracts.json — raw
    StableHLO text is version-fragile by design, docs/FEDVERIFY.md)."""
    _, reports = verified
    rep = reports["mesh1d_scatter"]
    counts = rep.collective_counts()
    # ONE reduce-scatter moves the merged numerator (the arXiv:2004.13336
    # cross-replica layout), everything client-axis on the 1-D mesh
    assert counts["reduce-scatter.client"] == 1
    assert all(k.endswith(".client") for k in counts)
    assert rep.num_partitions == 8
    # the whole donated ServerState aliased in-place
    assert rep.donated_params == rep.aliased_params
    assert rep.undonated_params == set()
    # census within the manifest pin
    entry = fv.load_manifest()["programs"]["mesh1d_scatter"]
    assert counts == entry["collectives"]
    # steady state: one staged-input signature
    assert len(set(rep.signatures)) == 1


def test_gate_covers_every_program_family(verified):
    _, reports = verified
    rep2d = reports["mesh2d_scatter"]
    assert rep2d.mesh_shape == (4, 2)
    # the 2-D module really reduces along BOTH axes
    axes = {o.axis for o in rep2d.collectives}
    assert "client" in axes and "model" in axes
    # sharding contracts were actually compared, not vacuously skipped
    assert rep2d.n_sharding_leaves >= 6
    # fused block: census covers 8 rounds (several x the single round's
    # client-axis bytes; exact counts are the manifest's pin)
    blk = reports["mesh_block8"]
    one = reports["mesh1d_scatter"]
    assert blk.collective_counts()["reduce-scatter.client"] >= 1  # scan
    assert blk.census_bytes()["client"] > \
        3 * one.census_bytes()["client"]
    # single-partition programs carry no collectives
    for name in ("sp_round", "population_p4", "serving_paged_decode_step"):
        assert reports[name].collectives == [], name
    # the prefill chunk really donates the page pools and the carried slot
    # state, and writes them in place
    chunk = reports["serving_paged_prefill_chunk"]
    assert len(chunk.donated_params) >= 4 and \
        chunk.donated_params <= chunk.aliased_params


def test_manifest_holds_exactly_the_two_serving_programs():
    """The engine has one KV cache, so it has two compiled citizens: the
    registry and the committed manifest name the tick and the prefill chunk
    over the page pool, and no program of a per-slot cache."""
    serving = ["serving_paged_decode_step", "serving_paged_prefill_chunk"]
    assert [n for n in fv.PROGRAMS if n.startswith("serving")] == serving
    pinned = fv.load_manifest()["programs"]
    assert sorted(n for n in pinned if n.startswith("serving")) == serving
    assert set(pinned) == set(fv.PROGRAMS)


# -- 3. lowering-level mutants ----------------------------------------------

def test_injected_rereplication_mutant_fails():
    """The PR 6 bug class, re-injected: the flat aux state leaves the
    round fully replicated instead of on its resting placement — the
    checker MUST flag it.  (Under jax 0.9.0 merely dropping the layout's
    pins no longer re-replicates: sharding propagation keeps the state
    where it came in.  So the mutant pins it replicated.)"""
    from fedml_tpu.simulation.mesh.layout import MeshLayout
    orig_cs = MeshLayout.constrain_state
    MeshLayout.constrain_state = \
        lambda self, state, scatter, quantized: self.replicate_leaves(state)
    try:
        rep = fv.build_mesh2d_scatter()
    finally:
        MeshLayout.constrain_state = orig_cs
    assert rep.rereplicated, "a replicated exit must be seen"
    assert any("opt_state" in p for p in rep.rereplicated)
    entry = fv.load_manifest()["programs"]["mesh2d_scatter"]
    rules = _rules(fv.run_checks(rep, entry))
    assert "silent-rereplication" in rules
    assert fv.exit_code(fv.run_checks(rep, entry)) == 1


def test_dropped_donation_mutant_fails():
    """The engine declares the state donated but the jit wrapper lost
    it (donate_argnums dropped): the lowered module carries no
    jax.buffer_donor marks and the checker fails."""
    from fedml_tpu.simulation.mesh.engine import make_mesh_round_fn
    api = fv._make_api(fv._canonical_args(
        backend="mesh", mesh_shape="8,1", update_sharding="scatter",
        federated_optimizer="FedOpt"))
    fn = make_mesh_round_fn(
        api.trainer, api.server_opt, api.mesh, gather=api._gather,
        sharded_data=api._sharded_data,
        update_sharding=api.update_sharding, state_template=api.state,
        donate=False,                      # <-- the mutation
        collective_precision=api.collective_precision,
        quant_block=api.quant_block)
    _, args, _ = api.round_program(0)
    rep = fv.lower_program("mutant_nodonate", fn, args, (0,),
                           mesh_shape=(8, 1))
    assert rep.undonated_params == rep.donated_params != set()
    entry = fv.load_manifest()["programs"]["mesh1d_scatter"]
    assert "donation-aliasing" in _rules(fv.run_checks(rep, entry))


def test_hetero_partition_busts_homo_signature_budget():
    """The recompile surface is real: a hetero (Dirichlet) partition
    presents multiple pow2 step classes to the jit cache, busting the
    homo budget of 1 — statically, from the staged signatures alone."""
    api = fv._make_api(fv._canonical_args(
        backend="mesh", mesh_shape="8,1", update_sharding="scatter",
        partition_method="hetero"))
    sigs = [api.round_signature(r) for r in range(6)]
    assert len(set(sigs)) > 1
    rep = _report(signatures=sigs)
    assert "recompile-surface" in _rules(fv.run_checks(rep, _entry(rep)))


def test_update_manifest_preserves_policy(tmp_path):
    """--update-manifest refreshes measured fields but keeps budgets,
    bands and suppressions — the policy half is the reviewed surface."""
    path = str(tmp_path / "contracts.json")
    rep = _report()
    fv.update_manifest([rep], path)
    m = fv.load_manifest(path)
    m["programs"]["synthetic"]["hbm_budget_bytes"] = 123
    m["suppressions"] = [{"program": "synthetic", "rule": "hbm-fit",
                          "reason": "test"}]
    import json
    with open(path, "w") as fh:
        json.dump(m, fh)
    rep2 = _report(memory={"argument": 1.0, "output": 1.0,
                           "temp": 1.0, "alias": 0.0})
    fv.update_manifest([rep2], path)
    m2 = fv.load_manifest(path)
    assert m2["programs"]["synthetic"]["hbm_budget_bytes"] == 123
    assert m2["programs"]["synthetic"]["per_chip_total"] == 3
    assert m2["suppressions"] == [{"program": "synthetic",
                                   "rule": "hbm-fit", "reason": "test"}]
