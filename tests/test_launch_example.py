"""Launch-plane end-to-end: the examples/launch/hello_job.yaml package is
built, dispatched to a local agent, executed as a REAL subprocess, and its
status stream reaches FINISHED (reference `fedml launch` flow)."""

import os

import pytest


def test_hello_job_launch():
    from fedml_tpu import api

    job = os.path.join(os.path.dirname(__file__), "..", "examples", "launch",
                       "hello_job.yaml")
    run = api.launch_job(job, wait=True, timeout_s=600,
                         env={"JAX_PLATFORMS": "cpu"})
    try:
        assert run.status == "FINISHED", (
            run.status, api.run_logs(run.run_id)[-10:])
        logs = api.run_logs(run.run_id)
        assert any("hello_world job done" in l for l in logs)
        assert any("bootstrap: environment ready" in l for l in logs)
    finally:
        api.shutdown()
