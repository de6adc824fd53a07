"""At a tiny size on the CPU the plain reference and ``LlamaLM`` agree on
logits, loss and LoRA gradients in float32; a bfloat16 run of the program, and
the int8 control, fall outside the tolerance that holds them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_CONFIG

import weights
from reference import dense_decoder as ref

TOL = 2e-5          # float32 against float32, relative to the tensor's scale


@pytest.fixture(scope="module")
def setting():
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    cfg = dict(TINY_CONFIG)
    m = weights.dims(cfg)
    lcfg = LlamaConfig(vocab_size=m["v"], dim=m["d"], n_layers=m["layers"],
                       n_heads=m["h"], n_kv_heads=m["kv"], ffn_dim=m["f"],
                       max_seq_len=64, rope_theta=cfg["rope_theta"],
                       norm_eps=cfg["rms_norm_eps"], dtype=jnp.float32,
                       lora_rank=cfg["lora"]["rank"], lora_alpha=cfg["lora"]["alpha"],
                       attn_impl="blockwise", remat="none")
    base, lora = weights.make_base(cfg, 5), weights.make_lora(cfg, 5)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(1, m["v"], size=(2, 48)), jnp.int32)
    y = jnp.asarray(rng.integers(1, m["v"], size=(2, 48)), jnp.int32)
    return cfg, lcfg, LlamaLM, base, lora, x, y


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def program(LlamaLM, lcfg, base, lora, x, y):
    from fedml_tpu.llm.model import causal_nll
    model = LlamaLM(lcfg)

    def loss(lo):
        return causal_nll(model.apply({"params": base, "lora": lo}, x), y)

    logits = model.apply({"params": base, "lora": lora}, x)
    value, grads = jax.value_and_grad(loss)(lora)
    return logits, value, grads


def test_layout_is_the_programs(setting):
    cfg, lcfg, LlamaLM, base, lora, x, _ = setting
    theirs = jax.eval_shape(LlamaLM(lcfg).init, jax.random.PRNGKey(0), x)
    assert weights.same_layout(base, theirs["params"]) == ""
    assert weights.same_layout(lora, theirs["lora"]) == ""


def test_float32_program_agrees(setting):
    cfg, lcfg, LlamaLM, base, lora, x, y = setting
    logits, loss, grads = program(LlamaLM, lcfg, base, lora, x, y)
    assert rel(logits, ref.logits(base, lora, x, cfg)) < TOL
    want_loss, want_grads = ref.loss_and_grad(lora, base, x, y, cfg)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < TOL
    for g, w in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        assert rel(g, w) < 50 * TOL


def test_bfloat16_program_falls_outside(setting):
    cfg, lcfg, LlamaLM, base, lora, x, y = setting
    low = dataclasses.replace(lcfg, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    logits, loss, _ = program(LlamaLM, low, base, lora, x, y)
    assert rel(logits, ref.logits(base, lora, x, cfg)) > 10 * TOL


def test_int8_control_falls_outside(setting):
    cfg, _, _, base, lora, x, y = setting
    assert rel(ref.logits(base, lora, x, cfg, "int8"), ref.logits(base, lora, x, cfg)) > 10 * TOL
    low, _ = ref.loss_and_grad(lora, base, x, y, cfg, "int8")
    want, _ = ref.loss_and_grad(lora, base, x, y, cfg)
    assert abs(float(low) - float(want)) / float(want) > TOL


def test_adamw_is_optax(setting):
    import optax
    cfg, _, _, base, lora, x, y = setting
    _, grads = ref.loss_and_grad(lora, base, x, y, cfg)
    tx = optax.adamw(2e-3, weight_decay=0.0)
    opt = tx.init(lora)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, lora)
    ours, mu, nu, count = lora, zeros, zeros, jnp.zeros((), jnp.int32)
    theirs = lora
    for _ in range(2):
        ours, mu, nu, count = ref._adamw(ours, grads, mu, nu, count, jnp.float32(2e-3))
        updates, opt = tx.update(grads, opt, theirs)
        theirs = optax.apply_updates(theirs, updates)
    for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
