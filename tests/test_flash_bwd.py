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


def _walk_dots(jaxpr, out):
    """Collect every dot_general eqn in a (nested) jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):          # ClosedJaxpr
                _walk_dots(v.jaxpr, out)
            elif hasattr(v, "eqns"):         # Jaxpr
                _walk_dots(v, out)
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
