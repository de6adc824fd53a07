"""Pallas flash-attention forward+backward vs blockwise autodiff, run in
Pallas interpret mode so numerics are validated hermetically on the CPU
mesh (TPU timing/parity additionally covered by `bench.py --attn`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.attention import (blockwise_attention,
                                     flash_attention_bwd_pallas,
                                     flash_attention_fwd_pallas)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,block", [(128, 64), (96, 64)])
def test_flash_fwd_bwd_interpret_matches_blockwise(causal, s, block):
    b, h, d = 1, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))
    do = jax.random.normal(ks[3], (b, h, s, d))

    out, lse = flash_attention_fwd_pallas(
        q, k, v, causal, block_q=block, block_k=block, return_lse=True,
        interpret=True)
    ref = blockwise_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)
    # lse sanity: exp(lse) = sum exp(scores) row-normalizer
    assert np.isfinite(np.asarray(lse)).all()

    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, do, causal, block_q=block, block_k=block,
        interpret=True)
    _, vjp = jax.vjp(lambda q, k, v: blockwise_attention(q, k, v,
                                                         causal=causal),
                     q, k, v)
    rq, rk, rv = vjp(do)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=5e-5,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=5e-5,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=5e-5,
                               rtol=1e-3)


def test_gqa_grouped_paths_match_repeated():
    """Grouped-query attention without KV materialization: blockwise
    broadcast view and Pallas index-mapped heads (fwd + bwd) must match the
    repeat-KV reference exactly."""
    b, h, hkv, s, d = 2, 8, 2, 96, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    do = jax.random.normal(ks[3], (b, h, s, d))
    rep = h // hkv
    kr, vr = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)

    ref = blockwise_attention(q, kr, vr, causal=True)
    got = blockwise_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)

    out, lse = flash_attention_fwd_pallas(q, k, v, True, block_q=64,
                                          block_k=64, return_lse=True,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)

    dq, dk, dv = flash_attention_bwd_pallas(q, k, v, out, lse, do, True,
                                            block_q=64, block_k=64,
                                            interpret=True)
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(
            q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1), causal=True),
        q, k, v)
    rq, rk, rv = vjp(do)
    for a, r in ((dq, rq), (dk, rk), (dv, rv)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=5e-5,
                                   rtol=1e-3)


def _eqns(jaxpr, kernels=True):
    """Every eqn of a (nested) jaxpr; ``kernels=False`` stays out of the
    bodies of Pallas kernels."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not kernels:
            continue
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):          # ClosedJaxpr
                yield from _eqns(v.jaxpr, kernels)
            elif hasattr(v, "eqns"):         # Jaxpr
                yield from _eqns(v, kernels)


def _walk_dots(jaxpr, out):
    """Collect every dot_general eqn in a (nested) jaxpr."""
    out.extend(e for e in _eqns(jaxpr) if e.primitive.name == "dot_general")
    return out


def test_bf16_score_dots_accumulate_f32():
    """Round-3 TPU regression: with bf16
    inputs, the attention dots must request f32 accumulation
    (preferred_element_type) — a bf16-rounded score matrix through the
    transposed scan produced NaN gradients on real TPU v5e while CPU bf16
    stayed clean, so the jaxpr is pinned instead of the numerics."""
    b, h, s, d = 1, 2, 128, 32
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: blockwise_attention(q, k, v, True, block_k=64))(
            q, q, q)
    dots = _walk_dots(jaxpr.jaxpr, [])
    bf16_in = [e for e in dots
               if any(v.aval.dtype == jnp.bfloat16 for v in e.invars)]
    assert bf16_in, "expected bf16-input dots in blockwise attention"
    for eqn in bf16_in:
        assert eqn.outvars[0].aval.dtype == jnp.float32, (
            "bf16 attention dot lost its f32 accumulation "
            f"(got {eqn.outvars[0].aval.dtype})")


def test_bf16_grads_finite_at_bisect_shape():
    """The offending shape from the round-2/3 TPU NaN (B2 H8 S512 D64,
    causal, multi-block).  On TPU this NaNed before the f32-accumulation
    fix; everywhere it pins the fixed code path end-to-end."""
    b, h, s, d = 2, 8, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
    g = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            blockwise_attention(q, k, v, True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)
    gn = float(np.asarray(jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(g)))))
    assert np.isfinite(gn), f"bf16 blockwise grads not finite: {gn}"


# -- tools/tpu_flash_tune.py: what the sweep times ------------------------------

@pytest.fixture(scope="module")
def tool():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "tpu_flash_tune.py")
    spec = importlib.util.spec_from_file_location("tpu_flash_tune", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _live_eqns(fn, x):
    """Every eqn left, at any depth but inside a kernel, once the jaxpr of
    ``fn(x)`` has lost what nothing uses (what ``jit`` does before it
    lowers): a pass whose result is dropped is not here."""
    from jax._src.interpreters import partial_eval as pe

    closed = jax.make_jaxpr(fn)(x)
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    return list(_eqns(jaxpr, kernels=False))


def _tune_operands():
    b, h, hkv, s, d = 1, 4, 2, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    g = jax.random.normal(ks[3], (b, h, s, d), jnp.bfloat16)
    return q, k, v, g


def test_tune_times_the_forward_and_both_backward_passes(tool):
    """The chain a tile is timed by keeps the forward, the dq pass and the
    dK/dV pass live: a step that returned ``dq`` alone left the dK/dV
    ``pallas_call`` dead under ``jit``, so the sweep never timed it."""
    q, k, v, g = _tune_operands()
    step = tool.flash_step(k, v, g, 128, 128)
    calls = [e.params["name"] for e in _live_eqns(
        lambda x: tool._chain(step, x, 2), q) if e.primitive.name == "pallas_call"]
    assert sorted(calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
    fwd = [e.params["name"] for e in _live_eqns(
        lambda x: tool._chain(tool.flash_forward(k, v, 128, 128), x, 2), q)
        if e.primitive.name == "pallas_call"]
    assert fwd == ["flash_fwd"]


def test_tune_baseline_is_the_gradient_of_the_scan(tool):
    """The scan is timed as what the round runs of it, its forward and its
    transposed (reverse) scan in q, k and v; no kernel."""
    q, k, v, g = _tune_operands()
    eqns = _live_eqns(lambda x: tool._chain(tool.scan_step(k, v, g), x, 2), q)
    assert not any(e.primitive.name == "pallas_call" for e in eqns)
    scans = [e.params["reverse"] for e in eqns
             if e.primitive.name == "scan" and e.params["length"] == 2]
    assert scans == [False]                     # the chain itself
    inner = [e.params["reverse"] for e in eqns
             if e.primitive.name == "scan" and e.params["length"] != 2]
    assert sorted(inner) == [False, True], inner
    # and it is a gradient: one step moves q by dq, as jax.vjp gives it
    _, vjp = jax.vjp(lambda q, k, v: blockwise_attention(q, k, v, True)
                     .astype(jnp.float32), q, k, v)
    dq, dk, dv = vjp(g.astype(jnp.float32))
    want = tool._fold(q, dq, dk, dv)
    np.testing.assert_allclose(np.asarray(tool.scan_step(k, v, g)(q), np.float32),
                               np.asarray(want, np.float32), atol=1e-2)


def test_tune_decides_on_forward_plus_backward(tool):
    """The tile with the least forward + backward wins, whatever its
    forward alone reads, and enters the table only if it beats the scan's
    forward + backward."""
    rows = [{"bq": 1024, "bk": 1024, "fwd_s": 0.010, "bwd_s": 0.060, "total_s": 0.070},
            {"bq": 256, "bk": 512, "fwd_s": 0.012, "bwd_s": 0.030, "total_s": 0.042},
            {"bq": 512, "bk": 1024, "error": "VMEM"}]
    best, wins = tool.choose(rows, base_total_s=0.300)
    assert (best["bq"], best["bk"]) == (256, 512) and wins
    assert tool.choose(rows, base_total_s=0.040)[1] is False
    assert tool.choose(rows[2:], base_total_s=0.3) == (None, False)
    assert (8, 32, 8, 1024, 128) in tool.SHAPES
