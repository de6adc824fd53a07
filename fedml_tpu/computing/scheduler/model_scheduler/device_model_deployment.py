"""Process-worker deployment (reference
``model_scheduler/device_model_deployment.py:68`` ``start_deployment``:
launch inference container → readiness-probe loop (:539) → register replica
in the Redis cache; plus the autoscaler reconcile loop the reference runs
from ``comm_utils/job_monitor.py:83`` →
``autoscaler/autoscaler.py:279`` ``scale_operation_endpoint``).

Here a replica is a real OS process (``worker_main``) serving the PACKAGED
model card — the single-host stand-in for the reference's Docker unit, with
identical lifecycle: spawn → wait for the port file → probe ``/ready`` →
register in :class:`FedMLModelCache` → route via the gateway."""

from __future__ import annotations

import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, Optional

from .autoscaler.autoscaler import Autoscaler
from .autoscaler.policies import AutoscalingPolicy
from .device_model_cache import FedMLModelCache
from .device_replica_controller import probe_ready

log = logging.getLogger(__name__)


class WorkerProcess:
    """Handle for one spawned inference worker."""

    def __init__(self, endpoint: str, replica_id: str, package: str,
                 cache: FedMLModelCache, host: str = "127.0.0.1",
                 readiness_timeout_s: float = 30.0):
        self.endpoint = endpoint
        self.replica_id = replica_id
        self.cache = cache
        port_file = os.path.join(
            tempfile.mkdtemp(prefix="fedml_worker_"), "port")
        # replicas are HOST processes: the autoscaler runs several beside a
        # gateway whose own process may hold the chip, and a chip belongs
        # to one process at a time.  A predictor that needs the chip is
        # deployed with mode="thread", inside the one process that owns it
        # (docs/ARCHITECTURE.md "Devices and processes").
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, "-m",
             "fedml_tpu.computing.scheduler.model_scheduler.worker_main",
             "--package", package, "--host", host, "--port-file", port_file],
            env=env)
        deadline = time.time() + readiness_timeout_s
        port = None
        while time.time() < deadline:
            if os.path.exists(port_file):
                with open(port_file) as f:
                    port = int(f.read().strip())
                break
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"worker {endpoint}/{replica_id} died during startup "
                    f"(rc={self.proc.returncode})")
            time.sleep(0.05)
        if port is None:
            self.stop()
            raise RuntimeError(
                f"worker {endpoint}/{replica_id} never wrote its port")
        self.url = f"http://{host}:{port}"
        if not probe_ready(self.url, max(deadline - time.time(), 1.0)):
            self.stop()
            raise RuntimeError(
                f"worker {endpoint}/{replica_id} never got ready")
        cache.add_replica(endpoint, replica_id, self.url)
        log.info("deployed worker %s/%s at %s (pid %d)", endpoint,
                 replica_id, self.url, self.proc.pid)

    def stop(self):
        self.cache.remove_replica(self.endpoint, self.replica_id)
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def start_deployment(endpoint: str, replica_id: str, package: str,
                     cache: Optional[FedMLModelCache] = None,
                     **kw) -> WorkerProcess:
    """Reference ``start_deployment`` surface over process workers."""
    return WorkerProcess(endpoint, replica_id, package,
                         cache or FedMLModelCache.get_instance(), **kw)


class ProcessReplicaController:
    """Desired-vs-actual reconcile over process workers (reference
    ``device_replica_controller.py`` semantics, container → process)."""

    def __init__(self, endpoint: str, package: str,
                 cache: Optional[FedMLModelCache] = None):
        self.endpoint = endpoint
        self.package = package
        self.cache = cache or FedMLModelCache.get_instance()
        self._workers: Dict[str, WorkerProcess] = {}
        self._next_id = 0
        self._mtx = threading.Lock()

    @property
    def current_replicas(self) -> int:
        with self._mtx:
            return len(self._workers)

    def reconcile(self, desired: int) -> int:
        desired = max(0, int(desired))
        with self._mtx:
            while len(self._workers) < desired:
                rid = f"worker-{self._next_id}"
                self._next_id += 1
                self._workers[rid] = WorkerProcess(
                    self.endpoint, rid, self.package, self.cache)
            while len(self._workers) > desired:
                rid, w = sorted(self._workers.items())[-1]
                w.stop()
                del self._workers[rid]
                log.info("scaled down %s/%s", self.endpoint, rid)
            return len(self._workers)

    def stop_all(self):
        self.reconcile(0)


class AutoscaleReconciler:
    """Background reconcile loop (reference
    ``job_monitor.autoscaler_reconcile_after_interval``): every interval,
    ask the autoscaler for the target count from live cache metrics and
    reconcile the controller to it."""

    def __init__(self, endpoint: str, controller, policy: AutoscalingPolicy,
                 cache: Optional[FedMLModelCache] = None,
                 interval_s: float = 1.0,
                 autoscaler: Optional[Autoscaler] = None):
        self.endpoint = endpoint
        self.controller = controller
        self.policy = policy
        self.interval_s = float(interval_s)
        self.autoscaler = autoscaler or Autoscaler(
            cache or FedMLModelCache.get_instance())
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def reconcile_once(self) -> int:
        self.policy.current_replicas = self.controller.current_replicas
        want = self.autoscaler.scale_operation_endpoint(
            self.policy, self.endpoint)
        if want != self.controller.current_replicas:
            log.info("autoscale %s: %d -> %d replicas", self.endpoint,
                     self.controller.current_replicas, want)
        return self.controller.reconcile(want)

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.reconcile_once()
            except Exception:
                log.exception("autoscale reconcile for %s failed",
                              self.endpoint)

    def start(self):
        self._thread = threading.Thread(
            target=self._loop, name=f"autoscale-{self.endpoint}", daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)


__all__ = ["WorkerProcess", "start_deployment", "ProcessReplicaController",
           "AutoscaleReconciler"]
