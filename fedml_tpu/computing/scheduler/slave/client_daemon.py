"""Agent daemon — respawn-on-death supervision (reference
``slave/client_daemon.py``: a login daemon that keeps the client agent
process alive and restarts it after crashes or OTA upgrades).

The daemon Popens :mod:`agent_main` with ``FEDML_AGENT_SUPERVISED=1`` and
respawns it whenever it dies: crash (any rc) → respawn with backoff, up to
``max_restarts`` within the rolling window; OTA exit (rc 75) → immediate
respawn with the staged upgrade dir prepended to ``PYTHONPATH``.  Run
recovery on the agent side (``FedMLClientAgent.recover_runs``) re-adopts or
respawns the jobs the dead agent stranded.

Daemon and agent are both launchers and stay off jax: only the jobs the
agent starts create a backend (one process for each chip —
docs/ARCHITECTURE.md "Devices and processes").
"""

from __future__ import annotations

import argparse
import logging
import os
import subprocess
import sys
import threading
import time
from typing import List, Optional

log = logging.getLogger(__name__)

OTA_EXIT_CODE = 75


class AgentDaemon:
    def __init__(self, agent_args: List[str], work_dir: str,
                 max_restarts: int = 10, window_s: float = 60.0,
                 backoff_s: float = 0.2):
        self.agent_args = list(agent_args)
        self.work_dir = work_dir
        os.makedirs(work_dir, exist_ok=True)
        self.max_restarts = int(max_restarts)
        self.window_s = float(window_s)
        self.backoff_s = float(backoff_s)
        # guards proc/_logf: the supervisor thread respawns while stop()
        # terminates — an unguarded swap can leave a freshly-respawned
        # agent running after stop() killed only the old pid
        self._plock = threading.Lock()
        self.proc: Optional[subprocess.Popen] = None
        self._logf = None
        self.restarts: List[float] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _spawn(self) -> subprocess.Popen:
        env = dict(os.environ)
        env["FEDML_AGENT_SUPERVISED"] = "1"
        # OTA: staged code dir (if any) leads PYTHONPATH on respawn
        marker = os.path.join(self.work_dir, "agent_upgrade", "current")
        if os.path.exists(marker):
            with open(marker) as f:
                lines = f.read().splitlines()
            if len(lines) >= 2 and os.path.isdir(lines[1]):
                env["PYTHONPATH"] = os.pathsep.join(
                    [lines[1], env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
                log.info("daemon: respawning with OTA code %s (v%s)",
                         lines[1], lines[0])
        cmd = [sys.executable, "-m",
               "fedml_tpu.computing.scheduler.slave.agent_main",
               *self.agent_args, "--work-dir", self.work_dir]
        log_path = os.path.join(self.work_dir, "agent_daemon.log")
        if self._logf is None:  # one handle for the daemon's lifetime —
            # per-respawn opens leaked an fd per OTA/crash cycle
            self._logf = open(log_path, "ab")
        return subprocess.Popen(cmd, env=env, stdout=self._logf,
                                stderr=subprocess.STDOUT)

    def _loop(self) -> None:
        with self._plock:
            self.proc = self._spawn()
        while not self._stop.is_set():
            with self._plock:
                rc = self.proc.poll()
            if rc is None:
                time.sleep(0.1)
                continue
            now = time.time()
            self.restarts = [t for t in self.restarts
                             if now - t < self.window_s]
            if rc == OTA_EXIT_CODE:
                log.info("daemon: agent exited for OTA; respawning")
            else:
                log.warning("daemon: agent died rc=%s; respawning", rc)
                if len(self.restarts) >= self.max_restarts:
                    log.error("daemon: %d restarts in %.0fs — giving up",
                              len(self.restarts), self.window_s)
                    return
                time.sleep(self.backoff_s * (1 + len(self.restarts)))
            self.restarts.append(now)
            with self._plock:
                # stop-check and respawn are one atomic step: once stop()
                # has set the flag (it holds _plock to read proc), no new
                # agent can appear for it to miss
                if self._stop.is_set():
                    return
                self.proc = self._spawn()

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, name="agent-daemon",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._plock:
            proc = self.proc
        # terminate/wait on the local ref OUTSIDE _plock (wait blocks up
        # to 5s; the supervisor thread needs the lock to observe _stop)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        with self._plock:
            if self._logf is not None:
                self._logf.close()
                self._logf = None

    def agent_pid(self, timeout_s: float = 60.0) -> int:
        """Pid of the CURRENT agent process (survives respawns via the
        pidfile agent_main writes)."""
        path = os.path.join(self.work_dir, "agent.pid")
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            with self._plock:
                proc = self.proc
            if proc is not None and proc.poll() is None \
                    and os.path.exists(path):
                with open(path) as f:
                    txt = f.read().strip()
                if txt and int(txt) == proc.pid:
                    return int(txt)
            time.sleep(0.05)
        raise TimeoutError("agent pidfile never matched a live agent")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--work-dir", required=True)
    ap.add_argument("agent_args", nargs=argparse.REMAINDER,
                    help="arguments forwarded to agent_main (after --)")
    opts = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    daemon = AgentDaemon([a for a in opts.agent_args if a != "--"],
                         opts.work_dir)
    daemon.start()
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        daemon.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
