"""JobManager: run driver scripts as supervised subprocesses.

Capability parity: reference python/ray/dashboard/modules/job/ — `ray job submit`
runs the entrypoint under a supervisor actor, tracks status (PENDING/RUNNING/
SUCCEEDED/FAILED/STOPPED), captures logs, applies the job's runtime_env
(job_manager.py, job_supervisor). Here the supervisor is a driver-side thread
per job and state persists in a session directory so the CLI can inspect it.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional


class JobStatus:
    PENDING = "PENDING"
    RUNNING = "RUNNING"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"
    STOPPED = "STOPPED"


@dataclasses.dataclass
class JobInfo:
    job_id: str
    entrypoint: str
    status: str
    start_time: float
    end_time: Optional[float] = None
    return_code: Optional[int] = None
    metadata: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self):
        return dataclasses.asdict(self)


def default_session_dir() -> str:
    from ray_tpu.config import CONFIG

    return CONFIG.session_dir


class JobManager:
    def __init__(self, session_dir: Optional[str] = None):
        self.session_dir = session_dir or default_session_dir()
        self.jobs_dir = os.path.join(self.session_dir, "jobs")
        os.makedirs(self.jobs_dir, exist_ok=True)
        self._procs: Dict[str, subprocess.Popen] = {}
        self._lock = threading.Lock()

    # -- persistence ------------------------------------------------------------
    def _job_dir(self, job_id: str) -> str:
        return os.path.join(self.jobs_dir, job_id)

    def _save(self, info: JobInfo) -> None:
        path = os.path.join(self._job_dir(info.job_id), "info.json")
        with open(path + ".tmp", "w") as f:
            json.dump(info.to_dict(), f)
        os.replace(path + ".tmp", path)

    def _load(self, job_id: str) -> Optional[JobInfo]:
        try:
            with open(os.path.join(self._job_dir(job_id), "info.json")) as f:
                return JobInfo(**json.load(f))
        except (OSError, json.JSONDecodeError):
            return None

    # -- API --------------------------------------------------------------------
    def submit_job(self, entrypoint: str, *,
                   runtime_env: Optional[Dict[str, Any]] = None,
                   metadata: Optional[Dict[str, Any]] = None,
                   submission_id: Optional[str] = None) -> str:
        job_id = submission_id or f"raytpu-job-{uuid.uuid4().hex[:10]}"
        jd = self._job_dir(job_id)
        if os.path.exists(jd):
            raise ValueError(f"job {job_id} already exists")
        os.makedirs(jd)
        info = JobInfo(job_id=job_id, entrypoint=entrypoint,
                       status=JobStatus.PENDING, start_time=time.time(),
                       metadata=metadata or {})
        self._save(info)

        env = dict(os.environ)
        renv = runtime_env or {}
        env.update(renv.get("env_vars") or {})
        if renv.get("py_modules"):
            extra = os.pathsep.join(renv["py_modules"])
            env["PYTHONPATH"] = extra + os.pathsep + env.get("PYTHONPATH", "")
        cwd = renv.get("working_dir") or os.getcwd()
        log_path = os.path.join(jd, "driver.log")

        log_f = open(log_path, "wb")
        proc = subprocess.Popen(entrypoint, shell=True, cwd=cwd, env=env,
                                stdout=log_f, stderr=subprocess.STDOUT,
                                start_new_session=True)
        log_f.close()
        with self._lock:
            self._procs[job_id] = proc
        info.status = JobStatus.RUNNING
        self._save(info)

        def supervise():
            rc = proc.wait()
            with self._lock:  # against stop_job, which marks the job STOPPED before it kills
                cur = self._load(job_id)
                if cur is None or cur.status == JobStatus.STOPPED:
                    return
                cur.status = JobStatus.SUCCEEDED if rc == 0 else JobStatus.FAILED
                cur.return_code = rc
                cur.end_time = time.time()
                self._save(cur)
                self._procs.pop(job_id, None)

        threading.Thread(target=supervise, daemon=True,
                         name=f"job-supervisor-{job_id}").start()
        return job_id

    def get_job_status(self, job_id: str) -> str:
        info = self._load(job_id)
        if info is None:
            raise KeyError(f"unknown job {job_id}")
        return info.status

    def get_job_info(self, job_id: str) -> JobInfo:
        info = self._load(job_id)
        if info is None:
            raise KeyError(f"unknown job {job_id}")
        return info

    def get_job_logs(self, job_id: str) -> str:
        try:
            with open(os.path.join(self._job_dir(job_id), "driver.log")) as f:
                return f.read()
        except OSError:
            return ""

    def list_jobs(self) -> List[JobInfo]:
        out = []
        for jid in sorted(os.listdir(self.jobs_dir)):
            info = self._load(jid)
            if info is not None:
                out.append(info)
        return out

    def stop_job(self, job_id: str) -> bool:
        info = self._load(job_id)
        if info is None:
            raise KeyError(f"unknown job {job_id}")
        with self._lock:
            proc = self._procs.get(job_id)
            if proc is None or proc.poll() is not None:
                return False
            # marked before the kill: the supervisor wakes with the process's death, and
            # would else write FAILED beside this STOPPED (two writers of one info.json.tmp)
            info.status = JobStatus.STOPPED
            self._save(info)
        # SIGTERM the whole process group (shell + script)
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            from ray_tpu.config import CONFIG

            proc.wait(timeout=CONFIG.job_stop_grace_s)
        except subprocess.TimeoutExpired:
            with __import__("contextlib").suppress(ProcessLookupError):
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            proc.wait()
        info.end_time = time.time()
        info.return_code = proc.returncode
        with self._lock:
            self._save(info)
        return True

    def wait_job(self, job_id: str, timeout: Optional[float] = None) -> str:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            status = self.get_job_status(job_id)
            if status in (JobStatus.SUCCEEDED, JobStatus.FAILED, JobStatus.STOPPED):
                return status
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"job {job_id} still {status}")
            time.sleep(0.2)
