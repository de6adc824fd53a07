"""MoE with expert parallelism: routing correctness vs a per-token loop
reference, aux loss, no drop under skew, and an EP-sharded run on the mesh."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from fedml_tpu.llm.moe import MoEMLP


def _reference_moe(params, x, n_experts, top_k, cap):
    """Per-token numpy re-implementation of capacity-limited top-k MoE."""
    b, s, dim = x.shape
    xt = np.asarray(x, np.float64).reshape(-1, dim)
    router = np.asarray(params["router"]["kernel"], np.float64)
    logits = xt @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    counts = np.zeros(n_experts, np.int64)
    # slot assignment mirrors the kernel: per k-choice, tokens in order
    assignments = []  # (token, expert, weight)
    order = np.argsort(-probs, axis=-1)[:, :top_k]
    gate = np.take_along_axis(probs, order, 1)
    gate = gate / gate.sum(-1, keepdims=True)
    counts = np.zeros(n_experts, np.int64)  # shared queue across branches
    for j in range(top_k):
        for nth in range(xt.shape[0]):
            e = order[nth, j]
            if counts[e] < cap:
                assignments.append((nth, e, gate[nth, j]))
            counts[e] += 1
    for nth, e, w in assignments:
        wg = np.asarray(params["w_gate"], np.float64)[e]
        wu = np.asarray(params["w_up"], np.float64)[e]
        wd = np.asarray(params["w_down"], np.float64)[e]
        h = xt[nth] @ wg
        u = xt[nth] @ wu
        silu = h / (1.0 + np.exp(-h)) * u
        out[nth] += w * (silu @ wd)
    return out.reshape(b, s, dim)


def test_moe_matches_per_token_reference():
    b, s, dim, ffn, e, k = 2, 8, 16, 32, 4, 2
    m = MoEMLP(dim=dim, ffn_dim=ffn, n_experts=e, top_k=k)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, dim))
    variables = m.init(jax.random.PRNGKey(1), x)
    out, state = m.apply(variables, x, mutable=["losses"])
    ref = _reference_moe(variables["params"], x, e, k, cap=b * s * k)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-3)
    aux = state["losses"]["moe_aux"]
    assert np.isfinite(float(aux[0] if hasattr(aux, "__len__") else aux))


def test_moe_dropless_under_skew():
    """Every token to ONE expert (a router that scores expert 2 far above
    the rest): nothing is dropped — each row is exactly that expert's
    SwiGLU of it, whatever the skew."""
    b, s, dim, ffn, e = 1, 16, 8, 16, 4
    m = MoEMLP(dim=dim, ffn_dim=ffn, n_experts=e, top_k=1)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, dim))
    variables = m.init(jax.random.PRNGKey(1), x)
    params = dict(variables["params"])
    params["router"] = {"kernel": jnp.zeros((dim, e))}
    x = x.at[..., 0].set(1.0)
    params["router"]["kernel"] = params["router"]["kernel"].at[0, 2].set(50.0)
    out, state = m.apply({"params": params}, x,
                         mutable=["losses", "moe_counters"])
    ref = _reference_moe(params, x, e, 1, cap=b * s)
    assert np.abs(ref).max(-1).min() > 1e-6          # no row is zero
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-4)
    pairs, hit, most, tiles = np.asarray(state["moe_counters"]["layer"][0])
    assert (pairs, hit, most) == (s, 1, s)
    assert tiles == 0                   # widths of 8 and 16: ragged_dot ran


def test_moe_expert_parallel_on_mesh():
    """EP sharding: experts constrained over the model axis; jitted step
    runs on the 8-device mesh and matches the unsharded output."""
    from fedml_tpu.core.mesh import make_mesh

    mesh = make_mesh(client=1, data=1, model=8, seq=1)
    b, s, dim, ffn, e = 2, 16, 16, 32, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, dim))

    m_plain = MoEMLP(dim=dim, ffn_dim=ffn, n_experts=e, top_k=2)
    variables = m_plain.init(jax.random.PRNGKey(1), x)
    ref, _ = m_plain.apply(variables, x, mutable=["losses"])

    m_ep = MoEMLP(dim=dim, ffn_dim=ffn, n_experts=e, top_k=2, mesh=mesh)

    @jax.jit
    def run(v, x):
        out, _ = m_ep.apply(v, x, mutable=["losses"])
        return out

    with mesh:
        got = run(variables, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)


@pytest.mark.slow
def test_llama_with_moe_trains():
    """LlamaLM with n_experts>0: the MoE block slots into the LM and a
    training step produces finite loss + grads (sown aux loss accessible)."""
    import optax
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM, causal_nll

    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=32,
                      dtype=jnp.float32, attn_impl="blockwise",
                      n_experts=4, moe_top_k=2)
    model = LlamaLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 128)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]

    def loss_fn(p):
        logits, state = model.apply({"params": p}, tokens, train=True,
                                    mutable=["losses"])
        aux = sum(jnp.asarray(v).sum()
                  for v in jax.tree_util.tree_leaves(state["losses"]))
        return causal_nll(logits[:, :-1], tokens[:, 1:]) + 0.01 * aux

    loss, g = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(optax.global_norm(g)))
    # router + expert params exist per layer
    assert "moe_mlp" in params["layer_0"]
    assert params["layer_0"]["moe_mlp"]["w_gate"].shape == (4, 32, 64)


@pytest.mark.slow
def test_llama_moe_ep_engages_under_context_mesh():
    """EP through the MODEL path: under `with mesh:` the ambient-mesh
    constraint inside Block->MoEMLP must fire (not silently no-op) and the
    sharded result must match the unsharded one."""
    from fedml_tpu.core.mesh import make_mesh
    from fedml_tpu.llm import moe as moe_mod
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM

    cfg = LlamaConfig(vocab_size=64, dim=16, n_layers=1, n_heads=2,
                      n_kv_heads=2, ffn_dim=32, max_seq_len=16,
                      dtype=jnp.float32, attn_impl="blockwise",
                      n_experts=4, moe_top_k=2)
    model = LlamaLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    ref = model.apply({"params": params}, tokens)

    mesh = make_mesh(client=1, data=1, model=4, seq=1)
    seen = []
    orig = moe_mod._ep_constraint

    def spy(x, m):
        out = orig(x, m)
        seen.append(out is not x)
        return out

    moe_mod._ep_constraint = spy
    try:
        with mesh:
            got = jax.jit(
                lambda p, t: model.apply({"params": p}, t))(params, tokens)
    finally:
        moe_mod._ep_constraint = orig
    assert any(seen), "EP constraint never engaged through LlamaLM"
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)


def test_moe_via_args_in_causal_lm_trainer():
    """args.n_experts plumbs MoE into the standard LLM surface
    (config_from_args -> build_causal_lm); a centralized trainer step runs
    and the aux-loss sow is a safe no-op when the collection isn't
    mutable."""
    import types
    from fedml_tpu.llm.model import config_from_args, build_causal_lm

    args = types.SimpleNamespace(model="tiny_llama", n_experts=4,
                                 moe_top_k=2, seq_len=16, llm_dim=32,
                                 llm_n_layers=1, llm_n_heads=2,
                                 llm_n_kv_heads=2, llm_ffn_dim=64,
                                 attn_impl="blockwise")
    cfg = config_from_args(args, vocab=64)
    assert cfg.n_experts == 4 and cfg.moe_top_k == 2
    fm = build_causal_lm(args, vocab=64)
    params = fm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    logits = fm.apply(params, toks)   # no mutable collections: sow no-ops
    assert logits.shape == (2, 16, 64)
    assert np.isfinite(np.asarray(logits)).all()
