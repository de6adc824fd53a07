"""Federated personalization end-to-end: train per-client LoRA adapters
with ``FedLLMAPI``, then serve ALL of them from ONE OpenAI-compatible
endpoint over one shared base — each request picks its client's adapter
with ``{"adapter": "<client>"}`` (no field = the zero adapter = global
base behavior).  One compiled decode program serves every adapter; the
reference would deploy a full model copy per personalized endpoint.

Run: python examples/serving/personalized_adapters.py
"""
import http.client
import json

import jax
import jax.numpy as jnp
import numpy as np

import fedml_tpu
from fedml_tpu import data as data_mod
from fedml_tpu.arguments import load_arguments
from fedml_tpu.llm.fedllm import FedLLMAPI
from fedml_tpu.llm.model import LlamaLM
from fedml_tpu.serving.templates.openai_compat import OpenAICompatServer

if __name__ == "__main__":
    # -- 1. federated LoRA fine-tune (tiny shapes; the mechanics scale) ---
    args = load_arguments()
    args.update(dataset="stackoverflow_nwp", train_size=256, test_size=64,
                seq_len=32, model="llama", llm_dim=64, llm_n_layers=2,
                llm_n_heads=4, llm_n_kv_heads=2, llm_ffn_dim=128,
                llm_max_seq_len=128, client_num_in_total=4,
                client_num_per_round=2, comm_round=2, batch_size=2,
                llm_max_local_steps=2, lora_rank=4, learning_rate=3e-3,
                random_seed=0)
    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, vocab = data_mod.load(args)
    # clip the synthetic vocab into byte range so completions decode as
    # printable text under the server's default ByteTokenizer (ids >= 256
    # would render as empty strings)
    for attr in ("train_x", "train_y", "test_x", "test_y"):
        setattr(dataset, attr, np.minimum(getattr(dataset, attr), 125))
    dataset.num_classes = 258
    api = FedLLMAPI(args, dataset)
    for r in range(2):
        m = api.train_one_round(r)
        print(f"round {r}: loss {float(np.asarray(m['train_loss'])):.3f}")

    # the federation's merged adapters become the served personalization;
    # a real deployment would register each client's own tree instead
    global_adapter = api.global_lora
    spicy_adapter = jax.tree_util.tree_map(lambda l: l * 3.0, global_adapter)

    # -- 2. serve every adapter from one endpoint -------------------------
    model = LlamaLM(api.cfg)
    srv = OpenAICompatServer(
        lambda p, t: model.apply(
            {"params": p, "lora": jax.tree_util.tree_map(
                jnp.zeros_like, global_adapter)}, t),
        api.base_params, model=model, buf_len=96,
        adapters={"global": global_adapter}, prefix_cache_slots=4)
    port = srv.start()
    srv.add_adapter("spicy", spicy_adapter)   # hot registration
    print(f"serving base + {sorted(srv.adapters)} on 127.0.0.1:{port}")

    def ask(adapter=None):
        body = {"prompt": "hello", "max_tokens": 8}
        if adapter:
            body["adapter"] = adapter
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        text = json.loads(conn.getresponse().read())["choices"][0]["text"]
        conn.close()
        return text

    base = ask()
    glob = ask("global")
    spicy = ask("spicy")
    print(f"base      : {base!r}")
    print(f"global    : {glob!r}")
    print(f"spicy     : {spicy!r}")
    print(f"personalized outputs differ from base: "
          f"{glob != base or spicy != base}")
    srv.stop()
