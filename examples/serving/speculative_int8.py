"""Advanced serving: weight-only int8 quantization + speculative decoding
together — the quantized target verified against its own draft, over the
OpenAI-compatible HTTP surface.

Run: python examples/serving/speculative_int8.py
"""
import http.client
import json
import time

import jax
import jax.numpy as jnp

from fedml_tpu.llm.model import LlamaConfig, LlamaLM
from fedml_tpu.llm.quantization import quantize_params_int8
from fedml_tpu.serving.speculative import speculative_generate
from fedml_tpu.serving.templates.openai_compat import OpenAICompatServer

if __name__ == "__main__":
    cfg = LlamaConfig(vocab_size=258, dim=128, n_layers=4, n_heads=8,
                      n_kv_heads=4, ffn_dim=256, max_seq_len=128,
                      dtype=jnp.float32, attn_impl="blockwise")
    target = LlamaLM(cfg)
    tparams = target.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    dcfg = LlamaConfig(vocab_size=258, dim=32, n_layers=1, n_heads=4,
                       n_kv_heads=2, ffn_dim=64, max_seq_len=128,
                       dtype=jnp.float32, attn_impl="blockwise")
    draft = LlamaLM(dcfg)
    dparams = draft.init(jax.random.PRNGKey(1),
                         jnp.zeros((1, 8), jnp.int32))["params"]

    qtree, stats = quantize_params_int8(tparams)
    print(f"int8 target weights: {100 * stats['ratio']:.1f}% of dense bytes")

    out, spec = speculative_generate(target, qtree, draft, dparams,
                                     [5, 17, 42], max_new_tokens=48,
                                     buf_len=128, k=4)
    print(f"speculative: {len(out)} tokens with "
          f"{spec['target_forwards']} target forwards "
          f"(acceptance {spec['acceptance_rate']:.2f} — random-init models "
          f"disagree; a distilled draft pushes this toward 1.0 and cuts "
          f"target forwards ~k-fold, output unchanged)")

    # draft_model on a server without batch_slots: greedy requests go
    # through speculative_generate one at a time (buf_len shrinks so
    # max_seq_len covers the buf_len + k + 1 block slack)
    srv = OpenAICompatServer(None, qtree, buf_len=120, model=target,
                             draft_model=draft, draft_params=dparams)
    port = srv.start()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    t0 = time.time()
    conn.request("POST", "/v1/completions", json.dumps(
        {"prompt": "once upon a time", "max_tokens": 32}),
        {"Content-Type": "application/json"})
    r = json.loads(conn.getresponse().read())
    print(f"HTTP completion via speculative decode "
          f"({time.time() - t0:.2f}s): {len(r['choices'][0]['text'])} chars")
    srv.stop()
