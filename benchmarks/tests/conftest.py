"""CPU rehearsals of the benchmark at tiny size.  Not part of tier-1 (which
collects ``tests/`` only): run with ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q``."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny-dense", "source": "none: a throw-away configuration of the tests",
    "architecture": "dense_decoder", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 256, "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-05, "reduced": [], "assumed": [],
    "compute_dtype": "float32", "weight_dtype": "float32",
    "lora": {"rank": 4, "alpha": 4.0, "targets": ["wq", "wk", "wv", "wo"]},
}

TINY_FEDROUND = {
    "name": "tiny.fedround", "config": "tiny-dense", "traffic_name": "fedround",
    "driver": "fedround", "chips": 1, "why": "throw-away cell of the tests",
    "traffic": {"clients_total": 8, "clients_per_round": 2, "local_steps": 2,
                "epochs": 2, "batch": 2, "seq_len": 32, "client_rows": [2, 3],
                "learning_rate": 0.002, "remat": "full", "streaming_xent_chunk": 0},
    "trace_seconds": 1,
    "check": {"rounds": 2, "limits": {"loss_r1": 2e-5, "loss_r2": 2e-5,
                                      "dnorm_r1": 2e-3, "dnorm_r2": 2e-3,
                                      "dnorm_med_r1": 1e-3, "dnorm_med_r2": 1e-3}},
}

TINY_SERVE = {
    "name": "tiny.serve", "config": "tiny-dense", "traffic_name": "serve-closed",
    "driver": "serve", "chips": 1, "why": "throw-away cell of the tests",
    "engine": {"slots": 4, "buf_len": 96, "page_tokens": 8, "pool_pages": 0,
               "prefill_chunk_tokens": 16, "adapter_slots": 4},
    "traffic": {"callers": 6, "requests": 24, "block": 6,
                "prompt": {"lo": 4, "hi": 48}, "answer": {"lo": 3, "hi": 16},
                "adapters": {"count": 3, "power_a": 1.0},
                "stagger_first": 6, "ramp_seconds": 0.5},
    "trace_seconds": 1,
    "check": {"sample": 6, "limits": {"served_gap": 1e-3, "unanswered": 0,
                                      "short_answers": 0}},
}


def fake_devices(chips, peaks):
    """The harness's look for a chip, lifted in the tests only."""
    import jax
    return jax.devices()[:chips], peaks["TPU v5 lite"]


@pytest.fixture
def checkout(tmp_path):
    """A temporary copy of the benchmark beside a link to the program, with a
    throw-away configuration and two throw-away cells added as new files."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"))
    os.symlink(os.path.join(ROOT, "fedml_tpu"), root / "fedml_tpu")
    bench = root / "benchmarks"
    (bench / "configs" / "tiny-dense.json").write_text(json.dumps(TINY_CONFIG))
    (bench / "workloads" / "tiny.fedround.json").write_text(json.dumps(TINY_FEDROUND))
    (bench / "workloads" / "tiny.serve.json").write_text(json.dumps(TINY_SERVE))
    return root


def load_harness(root):
    """The copy's own harness module (its paths point into the copy)."""
    import importlib.util
    for name in [m for m in sys.modules if m.split(".")[0] in (
            "harness", "drivers", "readers", "rooflines", "reference",
            "weights", "traffic")]:
        del sys.modules[name]
    bench = str(root / "benchmarks")
    sys.path[:] = [p for p in sys.path if p != BENCH]
    sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location("harness", os.path.join(bench, "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["harness"] = mod
    spec.loader.exec_module(mod)
    return mod
