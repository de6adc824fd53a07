"""What PR 41 will rely on: with the Pallas flash kernels in the round, every
kernel call of the compiled gradient maps under its layer's attention module
in all three phases, and the rule of ``round_attn_ms`` / ``attn_roofline``
counts each of them.

Compiled for a TPU v5e that is described and not attached (section 2 of the
on-chip-measurement guide): nothing runs.  The topology is described inside a
module-scoped fixture, never while a module is imported; the process keeps the
TPU's library once it has.  The gate ``ops.attention._use_pallas`` is patched
to ``True``: off a chip it is ``False``, and at the round's shape it is
``False`` on the chip too (the tile table holds only seq 1024 × head 64)."""

import json
import os
import re

import pytest

from conftest import BENCH

from readers import ops

METRICS = ("round_attn_ms", "attn_roofline")
PHASES = {"forward", "recompute", "backward"}
_CALL = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"", re.M)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def round_like_rows(one_chip):
    """The Pallas calls' rows in the map of a round-shaped program: a ``vmap``
    over two clients of a scan over two local steps of ``jax.grad`` of a
    two-layer LoRA ``LlamaLM``'s loss under ``remat=full`` (the round's
    nesting, which puts ``vmap()`` in a segment of its own), heads of 128,
    grouped KV (4 q heads on 2), seq 256, bf16."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.obs import programs
    from fedml_tpu.ops import attention

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
    gate = attention._use_pallas
    attention._use_pallas = lambda s_k, d: True
    try:
        compiled = jax.jit(clients).lower(described(variables["lora"], (2,)),
                                          described(variables["params"]), tokens).compile()
    finally:
        attention._use_pallas = gate
    hlo = compiled.as_text()
    rows = programs.parse_hlo(hlo)
    return {c: rows[c] for c in _CALL.findall(hlo)}


def test_every_flash_call_lies_under_its_layers_attention_in_three_phases(round_like_rows):
    """Today a call sits at ``layer_i/attention`` itself (the flash
    ``pallas_call``s carry no ``name=``, so no scope of their own, and the
    map's ``kernel`` reads the module's name); a named kernel would sit at
    ``layer_i/attention/<name>``.  Either is the layer's attention."""
    def layer(path):
        for i in (0, 1):
            if path == f"layer_{i}/attention" or path.startswith(f"layer_{i}/attention/"):
                return i
    calls = list(round_like_rows.values())
    assert calls and all(layer(row["path"]) is not None for row in calls), calls
    for i in (0, 1):
        phases = [row["phase"] for row in calls if layer(row["path"]) == i]
        assert set(phases) == PHASES, (i, phases)
        # the backward is its own kernel calls, not a second forward under ``vjp``
        assert phases.count("backward") >= 2 and phases.count("forward") == 1, (i, phases)


@pytest.mark.parametrize("metric", METRICS)
def test_the_rule_counts_every_flash_call(round_like_rows, metric):
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        args = json.load(f)["args"]
    missed = [(c, row) for c, row in round_like_rows.items() if not ops.matches(row, args)]
    assert not missed
