"""Device agent (reference ``slave/client_runner.py:62`` FedMLClientRunner +
``client_daemon.py``): listens for start/stop-run control messages, fetches
the job package, rewrites dynamic config args, spawns the job process, and
streams status transitions back to the master.  The same agent class serves
the aggregation-server role (reference ``master/server_runner.py:71``) by
running ``server_job`` when the dispatch says so — the FSM is identical.
"""

from __future__ import annotations

import logging
import os
import subprocess
import threading
from typing import Any, Dict, Optional

from ....core.distributed.communication.message import Message
from ..comm_utils.job_monitor import JobMonitor
from ..comm_utils.sys_utils import get_sys_runner_info
from ..scheduler_core.message_center import FedMLMessageCenter
from ..scheduler_core.run_db import RunDB
from ..scheduler_core.status import RunStatus, SchedulerMsgType
from ..scheduler_entry.app_manager import fetch_job_package
from ..scheduler_entry.job_config import rewrite_dynamic_args

log = logging.getLogger(__name__)

MSG_ARG_RUN_ID = "run_id"
MSG_ARG_PACKAGE = "package_path"
MSG_ARG_ENTRY = "entry_script"
MSG_ARG_ENV = "env"
MSG_ARG_DYNAMIC_ARGS = "dynamic_args"
MSG_ARG_STATUS = "status"
MSG_ARG_RETURNCODE = "returncode"
MSG_ARG_INVENTORY = "inventory"


class FedMLClientAgent:
    """One agent per host.  ``device_id`` is its rank on the scheduler comm
    plane (master is rank 0)."""

    def __init__(self, device_id: int, com_manager, work_dir: str,
                 run_db: Optional[RunDB] = None):
        self.device_id = int(device_id)
        self.work_dir = work_dir
        os.makedirs(work_dir, exist_ok=True)
        self.run_db = run_db or RunDB(os.path.join(work_dir, "runs.db"))
        self.center = FedMLMessageCenter(com_manager)
        self.monitor = JobMonitor()
        self.center.add_listener(SchedulerMsgType.START_RUN, self._on_start)
        self.center.add_listener(SchedulerMsgType.STOP_RUN, self._on_stop)
        self.center.add_listener(SchedulerMsgType.OTA_UPGRADE, self._on_ota)
        self._run_env: Dict[str, Dict[str, str]] = {}
        # stop-before-start race guard: a STOP_RUN that lands while
        # _start_run is still provisioning must suppress the spawn
        self._stop_lock = threading.Lock()
        self._stopped_runs: set = set()
        self._draining = False

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        self.monitor.start()
        self.center.start()
        self._register()
        self.recover_runs()

    def recover_runs(self) -> None:
        """Crash recovery (reference JobMonitor re-attach +
        client_daemon respawn): for every run this device last reported
        RUNNING, either re-adopt the still-alive job process by pid or
        respawn its entry script in the preserved workspace — a kill -9'd
        agent must not strand its runs."""
        for row in self.run_db.list_runs():
            if (int(row.get("device_id", -1)) != self.device_id
                    or row.get("status") != RunStatus.RUNNING):
                continue
            run_id = str(row["run_id"])
            info = row.get("info") or {}
            pid = info.get("pid")
            alive = False
            if pid:
                try:
                    os.kill(int(pid), 0)
                    alive = True
                except (ProcessLookupError, PermissionError, ValueError):
                    alive = False
            ws_done = info.get("ws", "")
            rc_path = os.path.join(ws_done, "run.rc") if ws_done else ""
            if not alive and rc_path and os.path.exists(rc_path):
                # the job FINISHED while the agent was down and persisted
                # its exit code — report it, never re-run completed work
                try:
                    with open(rc_path) as f:
                        rc = int(f.read().strip())
                except (OSError, ValueError):
                    rc = -1
                log.info("agent %d: run %s completed during downtime "
                         "(rc=%d)", self.device_id, run_id, rc)
                self._on_run_exit(run_id, rc)
                continue
            if alive:
                log.info("agent %d: re-adopting run %s (pid %s)",
                         self.device_id, run_id, pid)
                ws = info.get("ws", "")

                def on_exit(rid, rc, _ws=ws):
                    # the reparented orphan's rc comes from its run.rc file
                    rc_path = os.path.join(_ws, "run.rc")
                    try:
                        with open(rc_path) as f:
                            rc = int(f.read().strip())
                    except (OSError, ValueError):
                        rc = -1  # killed before writing its exit code
                    self._on_run_exit(rid, rc)

                self.monitor.watch_pid(run_id, int(pid), on_exit)
            elif info.get("entry") and info.get("ws"):
                log.warning("agent %d: run %s died with the previous agent; "
                            "respawning", self.device_id, run_id)
                threading.Thread(
                    target=self._respawn_run, name=f"respawn-{run_id}",
                    args=(run_id, info), daemon=True).start()
            else:
                self._report(run_id, RunStatus.FAILED,
                             info={"error": "lost across agent restart"})

    def _spawn_entry(self, entry: str, ws: str, full_env: Dict[str, str],
                     logf) -> subprocess.Popen:
        """Run the entry script with its exit code mirrored to ``run.rc``
        in the workspace — a pid-adopted orphan's true exit code is
        unknowable across the reparent, so the job persists it itself.

        The job is the process that uses the chip; the agent that starts
        it never creates a jax backend (one process for each chip)."""
        from ....device import require_chip_free
        require_chip_free("the device agent")
        with open(os.path.join(ws, "entry.sh"), "w") as f:
            f.write(entry if entry.endswith("\n") else entry + "\n")
        cmd = "bash entry.sh; rc=$?; echo $rc > run.rc; exit $rc"
        return subprocess.Popen(["bash", "-c", cmd], cwd=ws, env=full_env,
                                stdout=logf, stderr=subprocess.STDOUT)

    def _respawn_run(self, run_id: str, info: Dict[str, Any]) -> None:
        try:
            ws = info["ws"]
            log_path = os.path.join(ws, "run.log")
            full_env = dict(os.environ)
            full_env.update(info.get("env") or {})
            with open(log_path, "ab") as logf:
                proc = self._spawn_entry(info["entry"], ws, full_env, logf)
            self._report(run_id, RunStatus.RUNNING, log_path=log_path,
                         info={**info, "pid": proc.pid, "respawned": True})
            self.monitor.watch(run_id, proc, self._on_run_exit)
        except Exception as e:
            log.exception("respawn of run %s failed", run_id)
            self._report(run_id, RunStatus.FAILED, info={"error": str(e)})

    def stop(self) -> None:
        with self._stop_lock:
            self._draining = True  # suppress any in-flight _start_run spawn
        for run_id in self.monitor.watched_runs():
            if self.monitor.kill(run_id):
                self._report(run_id, RunStatus.KILLED)
        self.monitor.stop()
        self.center.stop()

    def _register(self) -> None:
        msg = Message(SchedulerMsgType.REGISTER, self.device_id, 0)
        msg.add(MSG_ARG_INVENTORY, get_sys_runner_info())
        self.center.send_message(msg)

    # -- control-plane handlers --------------------------------------------
    def _on_start(self, msg: Message) -> None:
        run_id = str(msg.get(MSG_ARG_RUN_ID))
        # idempotency: a respawned agent's fresh comm channel replays old
        # control files; a run this device is still ACTIVELY tracking
        # belongs to recover_runs, and a duplicate spawn would leave an
        # unreaped child that pid adoption mistakes for a live orphan.
        # Terminal statuses do NOT block: a re-dispatch of a FAILED/KILLED
        # run is a legitimate new attempt.
        existing = self.run_db.get_status(run_id, self.device_id)
        if existing is not None and not RunStatus.is_terminal(existing):
            log.info("agent %d: ignoring duplicate START_RUN for %s "
                     "(active, status %s)", self.device_id, run_id, existing)
            return
        pkg = str(msg.get(MSG_ARG_PACKAGE))
        entry = str(msg.get(MSG_ARG_ENTRY) or "")
        env = dict(msg.get(MSG_ARG_ENV) or {})
        dynamic = dict(msg.get(MSG_ARG_DYNAMIC_ARGS) or {})
        # spawn off the FSM thread so long bootstraps don't stall the loop
        threading.Thread(target=self._start_run, name=f"run-{run_id}",
                         args=(run_id, pkg, entry, env, dynamic),
                         daemon=True).start()

    def _run_aborted(self, run_id: str) -> bool:
        with self._stop_lock:
            return self._draining or run_id in self._stopped_runs

    def _start_run(self, run_id: str, pkg: str, entry: str,
                   env: Dict[str, str], dynamic: Dict[str, Any]) -> None:
        if self._run_aborted(run_id):
            self._report(run_id, RunStatus.KILLED)
            return
        self._report(run_id, RunStatus.PROVISIONING)
        try:
            ws = fetch_job_package(
                pkg, os.path.join(self.work_dir, f"run_{run_id}"))
            cfg = os.path.join(ws, "fedml_config.yaml")
            if dynamic and os.path.exists(cfg):
                rewrite_dynamic_args(cfg, dynamic)
            self._report(run_id, RunStatus.INITIALIZING)
            log_path = os.path.join(ws, "run.log")
            full_env = dict(os.environ)
            full_env.update(env)
            # job processes must resolve the same imports as the agent
            # (agents often run from an uninstalled source tree)
            import sys as _sys
            full_env["PYTHONPATH"] = os.pathsep.join(
                [p or os.getcwd() for p in _sys.path]
                + [p for p in full_env.get("PYTHONPATH", "").split(os.pathsep)
                   if p])
            full_env["FEDML_RUN_ID"] = run_id
            full_env["FEDML_DEVICE_ID"] = str(self.device_id)
            if self._run_aborted(run_id):
                self._report(run_id, RunStatus.KILLED)
                return
            with open(log_path, "ab") as logf:
                proc = self._spawn_entry(entry, ws, full_env, logf)
            # entry/ws/env persist so a respawned agent can recover the run
            self._report(run_id, RunStatus.RUNNING, log_path=log_path,
                         info={"pid": proc.pid, "entry": entry, "ws": ws,
                               "env": env})
            self.monitor.watch(run_id, proc, self._on_run_exit)
            # re-check: a stop may have swept between Popen and watch()
            if self._run_aborted(run_id) and self.monitor.kill(run_id):
                self._report(run_id, RunStatus.KILLED)
        except Exception as e:
            log.exception("start_run %s failed", run_id)
            self._report(run_id, RunStatus.FAILED, info={"error": str(e)})

    def _on_run_exit(self, run_id: str, returncode: int) -> None:
        status = RunStatus.FINISHED if returncode == 0 else RunStatus.FAILED
        self._report(run_id, status, returncode=returncode)

    def _on_stop(self, msg: Message) -> None:
        run_id = str(msg.get(MSG_ARG_RUN_ID))
        with self._stop_lock:
            self._stopped_runs.add(run_id)
        if self.monitor.kill(run_id):
            self._report(run_id, RunStatus.KILLED)

    def _on_ota(self, msg: Message) -> None:
        """OTA upgrade (reference ``client_runner.py:867`` pip-upgrades and
        respawns the daemon).  Zero-egress version: the message carries an
        agent-code package path; the agent unpacks it into a versioned dir,
        flips the ``current`` marker, reports, and — when supervised by
        ``client_daemon`` — exits so the daemon respawns it with the new
        code on PYTHONPATH."""
        pkg = msg.get(MSG_ARG_PACKAGE)
        version = str(msg.get("version") or "0")
        if not pkg:
            log.info("agent %d: OTA ping (no package) acknowledged",
                     self.device_id)
            return
        try:
            dest = os.path.join(self.work_dir, "agent_upgrade", version)
            marker = os.path.join(self.work_dir, "agent_upgrade", "current")
            if os.path.isdir(dest) and os.path.exists(marker):
                with open(marker) as f:
                    if f.read().splitlines()[:1] == [version]:
                        # the agent this upgrade respawned reads the
                        # plane's messages anew: staging it again would
                        # empty the directory it runs from and exit again
                        log.info("agent %d: OTA %s is already current",
                                 self.device_id, version)
                        return
            ws = fetch_job_package(str(pkg), dest)
            tmp = marker + ".tmp"
            with open(tmp, "w") as f:
                f.write(f"{version}\n{ws}\n")
            os.replace(tmp, marker)
            log.info("agent %d: OTA %s staged at %s", self.device_id,
                     version, ws)
            self._report(f"ota_{version}", RunStatus.FINISHED,
                         info={"ota_version": version, "path": ws})
            if os.environ.get("FEDML_AGENT_SUPERVISED"):
                # the daemon interprets OTA_EXIT_CODE as "respawn me with
                # the staged code"; runs survive via recover_runs()
                threading.Thread(target=self._ota_exit, daemon=True).start()
        except Exception as e:
            log.exception("OTA failed")
            self._report(f"ota_{version}", RunStatus.FAILED,
                         info={"error": str(e)})

    OTA_EXIT_CODE = 75  # EX_TEMPFAIL: daemon respawns instead of giving up

    def _ota_exit(self):
        import time as _t
        _t.sleep(0.2)  # let the status message flush
        os._exit(self.OTA_EXIT_CODE)

    # -- status ------------------------------------------------------------
    def _report(self, run_id: str, status: str,
                returncode: Optional[int] = None,
                log_path: Optional[str] = None,
                info: Optional[Dict[str, Any]] = None) -> None:
        self.run_db.set_status(run_id, self.device_id, status,
                               returncode=returncode, log_path=log_path,
                               info=info)
        msg = Message(SchedulerMsgType.STATUS_UPDATE, self.device_id, 0)
        msg.add(MSG_ARG_RUN_ID, run_id)
        msg.add(MSG_ARG_STATUS, status)
        if returncode is not None:
            msg.add(MSG_ARG_RETURNCODE, returncode)
        if info is not None:
            msg.add("info", info)  # e.g. pid — master persists it for
            # cross-process stop_run
        self.center.send_message(msg)


__all__ = ["FedMLClientAgent"]
