"""``chip_smoke.py``'s phases, called at toy sizes on the CPU (the Pallas
kernels in interpret mode), its refusal of any platform but the TPU, and the
compile-cache rule of ``fedml_tpu/__init__.py``.  The sizes a chip run uses
are ``chip_smoke.FLAGSHIP``; nothing here says anything about speed."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(dim=128, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=256,
            vocab=12000, seq=64, lora_rank=8)
ENGINE = dict(rounds=4, clients=8, cohort=4, train_size=256)


@pytest.fixture(scope="module")
def trained():
    """One toy ``phase_fedllm``; the server cases serve its base."""
    report, api = chip_smoke.phase_fedllm(**TINY)
    return report, api


def test_phase_engine_sp_equals_mesh():
    report = chip_smoke.phase_engine(**ENGINE)
    assert report["devices"] == jax.device_count() == 8
    assert report["sp"]["warm_compilations"] == 0
    assert report["mesh"]["warm_compilations"] == 0
    assert report["params_max_ulp"] <= 4


def test_phase_kernels_interpret():
    report = chip_smoke.phase_kernels(shapes=((1, 4, 2, 128, 64),),
                                      interpret=True)
    assert set(report["rows"][0]["rel_err"]) == {"out", "dq", "dk", "dv"}


def test_phase_fedllm_trains(trained):
    report, _ = trained
    assert report["warm_compilations"] == 0
    assert report["train_loss"][-1] < report["train_loss"][0]
    assert report["n_lora_params"] > 0


def test_phase_server_answers_as_generate(trained):
    report = chip_smoke.phase_server(trained[1], buf_len=48, page_tokens=8,
                                     chunk_tokens=8, max_tokens=8)
    assert report["greedy_equals_generate"]
    assert report["warm_compilations"] == 0
    assert report["prefill_chunks"] > report["requests"]   # chunked prefill


def test_phase_server_fails_on_impossible_pool(trained):
    """A pool no request fits in must fail the phase, not shrink it."""
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.phase_server(trained[1], buf_len=48, page_tokens=8,
                                chunk_tokens=8, max_tokens=8, pool_pages=2)


def test_phase_fedllm_sharded_matches_one_device():
    """Also pins that the sharded round compiles once: its merged adapters
    come back in the placement they went in with."""
    report = chip_smoke.phase_fedllm_sharded(**TINY)
    assert report["sharded"]["warm_compilations"] == 0
    assert len(report["sharded"]["live_bytes_per_device"]) == 4


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_a_platform_that_is_not_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


@pytest.mark.parametrize("env,want", [
    ({"JAX_COMPILATION_CACHE_DIR": "/some/dir"}, "/some/dir"),
    ({}, os.path.join(REPO, ".jax_cache")),
    ({"JAX_PLATFORMS": "cpu"}, "None"),
], ids=["placed-from-outside", "fixed-path-in-checkout", "none-on-cpu"])
def test_compile_cache_rule(env, want):
    """Where JAX_COMPILATION_CACHE_DIR is set jax reads it and the package
    assigns nothing; otherwise one fixed directory inside the checkout; a
    process told to use the CPU gets none.  Asked of a child, which only
    imports: no backend is created."""
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    out = subprocess.run(
        [sys.executable, "-c",
         "import fedml_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env={**base, **env}, capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == want
