"""Worker process spawning — local subprocess or ssh.

Reference analog: horovod/runner/common/util/safe_shell_exec.py (exec with
output forwarding + termination) and the per-slot ssh command construction
in runner/gloo_run.py:114-185.
"""

from __future__ import annotations

import os
import shlex
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

LOCAL_HOSTNAMES = {"localhost", "127.0.0.1", os.uname().nodename}


def is_local(hostname: str) -> bool:
    return hostname in LOCAL_HOSTNAMES


def build_command(hostname: str, command: List[str],
                  env: Dict[str, str], ssh_port: Optional[int] = None,
                  ) -> List[str]:
    """Local: run directly with env. Remote: ssh with inline exports
    (reference: gloo_run.py get_remote_command)."""
    if is_local(hostname):
        return command
    exports = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
    remote = f"cd {shlex.quote(os.getcwd())} && env {exports} " + \
        " ".join(shlex.quote(c) for c in command)
    ssh = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if ssh_port:
        ssh += ["-p", str(ssh_port)]
    return ssh + [hostname, remote]


def _tail_forward(path: str, tag: str, done_fn, from_offset: int = 0):
    """Poll-tail ``path`` and forward complete lines to stdout with
    ``tag`` — the durable-mode analog of the pipe-forwarding thread.
    Stops once ``done_fn()`` is true and the file is drained."""
    f = None
    try:
        while f is None:
            try:
                f = open(path, "rb")
            except OSError:
                if done_fn():
                    return
                time.sleep(0.2)
        f.seek(from_offset)
        while True:
            line = f.readline()
            if line:
                text = line.decode(errors="replace")
                sys.stdout.write(f"{tag}{text}" if tag else text)
                sys.stdout.flush()
            else:
                if done_fn():
                    # final drain: bytes may have landed between the EOF
                    # read and the done check (tagged per line like the
                    # main loop, or a 64-rank job's exit lines would be
                    # unattributable)
                    tail = f.read().decode(errors="replace")
                    for text in tail.splitlines(keepends=True):
                        sys.stdout.write(f"{tag}{text}" if tag else text)
                    if tail:
                        sys.stdout.flush()
                    return
                time.sleep(0.2)
    finally:
        if f is not None:
            f.close()


class AdoptedWorker:
    """A live worker a *recovered* driver re-learned from its KV
    heartbeats instead of spawning (the original driver that forked it is
    dead, so there is no child-process handle to poll).

    Liveness: a signal-0 pid probe on local hosts, heartbeat freshness
    (wall-clock ``ts`` the driver refreshes from the KV each scan)
    elsewhere. The exit *code* of a dead adopted worker is unknowable —
    poll() reports 1 and the driver's reap path consults the worker-state
    registry to reinterpret SUCCESS/DRAINED records as clean exits."""

    adopted = True

    def __init__(self, hostname: str, rank, pid: int,
                 heartbeat_timeout: float = 10.0,
                 log_path: Optional[str] = None):
        self.hostname = hostname
        self.rank = rank
        self.pid = int(pid or 0)
        self._timeout = heartbeat_timeout
        self._last_beat = time.time()
        self._local = is_local(hostname)
        self._code: Optional[int] = None
        if log_path:
            # resume forwarding the worker's log from where it stands now
            # (the outage window's lines stay in the file)
            try:
                offset = os.path.getsize(log_path)
            except OSError:
                offset = 0
            threading.Thread(
                target=_tail_forward,
                args=(log_path, f"[{rank}]<stdout>:",
                      lambda: self.poll() is not None, offset),
                daemon=True).start()

    def note_heartbeat(self, ts: float):
        self._last_beat = max(self._last_beat, float(ts))

    def poll(self) -> Optional[int]:
        if self._code is not None:
            return self._code
        if self._local and self.pid:
            try:
                os.kill(self.pid, 0)
            except ProcessLookupError:
                self._code = 1
                return self._code
            except PermissionError:
                pass  # pid exists but isn't ours — fall through to the
                # heartbeat check: it may be a recycled pid, not the
                # worker (a dead worker must not look alive forever)
        # Heartbeat age is authoritative even when the pid probe says
        # alive: pid reuse (or a wedged worker that stopped beating
        # against a reachable KV) would otherwise never be reaped and
        # the slot would hang the next go-barrier indefinitely.
        if time.time() - self._last_beat > self._timeout:
            self._code = 1
            return self._code
        return None

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = time.monotonic() + (timeout if timeout is not None
                                       else float("inf"))
        while self.poll() is None:
            if time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired("adopted-worker",
                                                timeout or 0)
            time.sleep(0.1)
        return self._code

    def _signal(self, sig):
        if not (self._local and self.pid):
            return  # remote adoptee: the host-side agent owns its death
        try:
            # workers are session leaders (start_new_session=True), so the
            # pid doubles as the process-group id
            os.killpg(self.pid, sig)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                os.kill(self.pid, sig)
            except (ProcessLookupError, PermissionError):
                pass

    def terminate(self):
        self._signal(signal.SIGTERM)

    def kill(self):
        self._signal(signal.SIGKILL)


class WorkerProcess:
    """A spawned worker with output forwarding and a tag prefix
    (reference: safe_shell_exec forwarding threads).

    ``log_path`` switches stdout/stderr from a pipe to an append-mode
    file, tail-forwarded instead of pipe-forwarded. This is what the
    crash-recoverable driver uses: a pipe dies with its reader, so a
    SIGKILLed driver would EPIPE every worker's next print — with a file,
    workers keep writing through the outage and the respawned driver
    resumes tailing (:class:`AdoptedWorker`)."""

    def __init__(self, hostname: str, rank: int, command: List[str],
                 env: Dict[str, str], prefix_output: bool = True,
                 capture: bool = False, log_path: Optional[str] = None):
        self.hostname = hostname
        self.rank = rank
        full_env = dict(os.environ)
        full_env.update(env)
        cmd = build_command(hostname, command, env)
        self.captured: List[str] = []
        self._capture = capture
        self.log_path = log_path
        if log_path:
            os.makedirs(os.path.dirname(log_path) or ".", exist_ok=True)
            self._logf = open(log_path, "ab")
            offset = self._logf.tell()
            self.proc = subprocess.Popen(
                cmd, env=full_env if is_local(hostname) else None,
                stdout=self._logf, stderr=subprocess.STDOUT,
                start_new_session=True)
            tag = f"[{rank}]<stdout>:" if prefix_output else ""
            self._fwd = threading.Thread(
                target=_tail_forward,
                args=(log_path, tag,
                      lambda: self.proc.poll() is not None, offset),
                daemon=True)
        else:
            self.proc = subprocess.Popen(
                cmd, env=full_env if is_local(hostname) else None,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                start_new_session=True)
            self._fwd = threading.Thread(
                target=self._forward, args=(prefix_output,), daemon=True)
        self._fwd.start()

    def _forward(self, prefix: bool):
        tag = f"[{self.rank}]<stdout>:" if prefix else ""
        for line in self.proc.stdout:
            text = line.decode(errors="replace")
            if self._capture:
                self.captured.append(text)
            sys.stdout.write(f"{tag}{text}" if tag else text)
            sys.stdout.flush()

    def wait(self, timeout: Optional[float] = None) -> int:
        rc = self.proc.wait(timeout=timeout)
        self._fwd.join(timeout=5)
        return rc

    def poll(self) -> Optional[int]:
        return self.proc.poll()

    def terminate(self):
        if self.proc.poll() is None:
            try:
                os.killpg(os.getpgid(self.proc.pid), signal.SIGTERM)
            except ProcessLookupError:
                pass

    def kill(self):
        if self.proc.poll() is None:
            try:
                os.killpg(os.getpgid(self.proc.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass
