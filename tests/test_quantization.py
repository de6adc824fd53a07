"""Weight-only int8 serving quantization (llm/quantization.py): byte
shrink, reconstruction error, logits fidelity, and end-to-end KV-cached /
batched decode on the quantized tree."""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.llm.model import LlamaConfig, LlamaLM
from fedml_tpu.llm.quantization import (dequantize_params,
                                        make_quantized_apply,
                                        quantization_error,
                                        quantize_params_int8)


def _model(seq=64):
    cfg = LlamaConfig(vocab_size=258, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=4, ffn_dim=128, max_seq_len=seq,
                      dtype=jnp.float32, attn_impl="blockwise")
    model = LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def test_quantize_shrink_and_error():
    model, params = _model()
    qtree, stats = quantize_params_int8(params)
    # matmul weights dominate → ~4x shrink vs f32
    assert stats["ratio"] < 0.30, stats
    err = quantization_error(params, qtree)
    # per-channel symmetric int8: worst leaf within ~1% of its max
    assert err["max_rel_err"] < 0.01, err

    # dequant round-trip keeps structure and dtype
    back = dequantize_params(qtree, jnp.float32)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(params))


def test_quantized_logits_close_and_generation_works():
    model, params = _model()
    qtree, _ = quantize_params_int8(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 258)
    full = model.apply({"params": params}, toks)
    qapply = make_quantized_apply(model)
    quant = qapply(qtree, toks)
    # logits of a random-init model are O(1); per-layer int8 error
    # compounds but stays a small fraction of the logit scale
    dev = float(jnp.max(jnp.abs(full - quant)))
    scale = float(jnp.max(jnp.abs(full)))
    assert dev < 0.1 * scale, (dev, scale)

    # KV-cached generation straight off the int8 tree
    from fedml_tpu.serving.templates.openai_compat import generate
    out_q = generate(None, qtree, [5, 17, 42], max_new_tokens=10,
                     buf_len=64, model=model)
    out_f = generate(None, params, [5, 17, 42], max_new_tokens=10,
                     buf_len=64, model=model)
    assert len(out_q) == 10
    # greedy decode is robust to the tiny logit perturbation on most steps
    agree = sum(a == b for a, b in zip(out_q, out_f))
    assert agree >= 7, (out_q, out_f)


def test_batching_engine_serves_quantized_tree():
    from fedml_tpu.serving.batching import ContinuousBatchingEngine

    model, params = _model()
    qtree, _ = quantize_params_int8(params)
    engine = ContinuousBatchingEngine(model, qtree, slots=2, buf_len=64)
    try:
        outs = [engine.generate([i + 1, i + 2], max_new_tokens=6)
                for i in range(3)]
        assert all(len(o) == 6 for o in outs)
    finally:
        engine.stop()


def test_int8_kv_cache_decode_fidelity():
    """kv_cache_dtype="int8" halves decode-path KV HBM bytes; cached decode
    logits must track the native-cache path closely, and the cache tree
    must actually store int8 K/V with per-position scales."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM

    base = dict(vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                ffn_dim=128, max_seq_len=32, dtype=jnp.float32,
                attn_impl="blockwise")
    logits = {}
    for kvd in ("native", "int8"):
        cfg = LlamaConfig(**base, kv_cache_dtype=kvd)
        model = LlamaLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 128)
        out, mut = model.apply({"params": params}, toks, decode=True,
                               start_pos=jnp.int32(0), mutable=["cache"])
        cache = mut["cache"]
        seq = [out[0, -1]]
        for i in range(8, 14):      # a few cached single-token steps
            step_out, mut = model.apply(
                {"params": params, "cache": cache},
                jnp.argmax(seq[-1])[None, None].astype(jnp.int32),
                decode=True, start_pos=jnp.int32(i), mutable=["cache"])
            cache = mut["cache"]
            seq.append(step_out[0, 0])
        logits[kvd] = np.stack([np.asarray(s) for s in seq])
        if kvd == "int8":
            leaves = jax.tree_util.tree_leaves_with_path(cache)
            dtypes = {jax.tree_util.keystr(p): l.dtype for p, l in leaves}
            assert any(d == jnp.int8 for d in dtypes.values()), dtypes
            assert any("scale" in k for k in dtypes), dtypes

    err = np.max(np.abs(logits["int8"] - logits["native"]))
    rel = err / (np.max(np.abs(logits["native"])) + 1e-9)
    assert rel < 0.05, (err, rel)
    # greedy tokens should agree on this model
    assert (logits["int8"].argmax(-1) == logits["native"].argmax(-1)).all()


def test_int8_kv_cache_through_batching_engine():
    """kv_cache_dtype="int8" must work through the continuous-batching
    engine (int8 page pools with a pool of scales beside each), with
    greedy output identical to the single-request cached generate on the
    same int8-KV model."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    from fedml_tpu.serving.templates.openai_compat import generate

    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=32,
                      dtype=jnp.float32, attn_impl="blockwise",
                      kv_cache_dtype="int8")
    model = LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    apply_fn = lambda p, t: model.apply({"params": p}, t)

    eng = ContinuousBatchingEngine(model, params, slots=2, buf_len=32,
                                   horizon=4)
    try:
        leaves = jax.tree_util.tree_leaves(eng._pool)
        assert sum(l.dtype == jnp.int8 for l in leaves) == 2 * cfg.n_layers
        assert len(leaves) == 4 * cfg.n_layers      # k, v and their scales
        for p in ([5, 17, 42], [7, 7, 7, 7]):
            got = eng.generate(p, max_new_tokens=8)
            want = generate(apply_fn, params, p, max_new_tokens=8,
                            buf_len=32, model=model)
            assert got == want, (p, got, want)
    finally:
        eng.stop()
