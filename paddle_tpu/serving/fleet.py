"""Replica fleet lifecycle: spawn, crash-restart, rolling restart.

The Router (serving/router.py) decides where requests GO; the
ReplicaSupervisor here decides what EXISTS to send them to.  It owns N
`python -m paddle_tpu.serving` subprocesses (each on an ephemeral port,
discovered from the CLI's machine-readable ready line), registers them
with the router, and enforces two availability contracts:

  * Crash restart — a replica that exits unexpectedly (OOM-kill,
    preemption, chaos SIGKILL) is respawned with capped exponential
    backoff; `router.replica_restarts_total` counts them and a
    `router.replica_restart` flight event names the exit code.  The
    router meanwhile evicts the dead port via its probe machinery, so
    the restart races nothing.
  * Rolling restart with zero downtime — one replica at a time: mark it
    draining AT THE ROUTER first (no request races the signal), SIGTERM
    (the ISSUE-13 graceful-drain contract: in-flight work completes,
    exit 0), respawn against the SAME compile cache (the replicas inherit
    JAX_COMPILATION_CACHE_DIR, else share <checkout>/.jax_cache) so warmup
    replays compiled executables out of the persistent cache instead of
    recompiling, wait for the ready line AND a passing router probe,
    then move on.  At every instant N-1 replicas take traffic.

Stdlib-only (subprocess + threads), imports no jax: the supervisor is a
control plane, the replicas are the data plane.
"""

from __future__ import annotations

import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from .router import IN_ROTATION, Router

_READY_EVENTS = ("serving_ready",)


class _ReplicaProc:
    """One replica subprocess + its pipe-drain bookkeeping."""

    def __init__(self, rid: str, proc: subprocess.Popen):
        self.rid = rid
        self.proc = proc
        self.port: Optional[int] = None
        self.ready = threading.Event()
        self.spawned_at = time.monotonic()
        self.stderr_tail: "collections.deque" = collections.deque(
            maxlen=50)
        # the CLI writes ONE ready line to stdout; both pipes must be
        # drained forever regardless (a full 64KB pipe wedges the child)
        threading.Thread(target=self._drain_stdout, daemon=True).start()
        threading.Thread(target=self._drain_stderr, daemon=True).start()

    def _drain_stdout(self) -> None:
        for line in self.proc.stdout:
            if not self.ready.is_set():
                try:
                    msg = json.loads(line)
                    if msg.get("event") in _READY_EVENTS:
                        self.port = int(msg["port"])
                        self.ready.set()
                except (ValueError, KeyError):
                    pass

    def _drain_stderr(self) -> None:
        for line in self.proc.stderr:
            self.stderr_tail.append(line.rstrip("\n"))


class ReplicaSupervisor:
    """Owns N serving replicas and keeps the router's view of them true.

    `replica_args` are the CLI arguments after `python -m
    paddle_tpu.serving` (models, buckets, ...); the supervisor forces
    `--port 0` per spawn and reads the real port from the ready line.
    `env` overlays os.environ for every replica; `per_replica_env[i]`
    overlays one replica (how chaos flags arm exactly one victim)."""

    def __init__(self, replica_args: List[str], n: int,
                 router: Optional[Router] = None,
                 env: Optional[dict] = None,
                 per_replica_env: Optional[Dict[int, dict]] = None,
                 python: Optional[str] = None,
                 cwd: Optional[str] = None,
                 host: str = "127.0.0.1",
                 ready_timeout_s: float = 180.0,
                 restart_base_delay_s: float = 0.5,
                 restart_max_delay_s: float = 10.0):
        args = list(replica_args)
        if "--port" in args:  # the supervisor owns port assignment
            i = args.index("--port")
            del args[i:i + 2]
        self.replica_args = args
        self.n = int(n)
        self.router = router if router is not None else Router(host=host)
        self.env = dict(env or {})
        self.per_replica_env = dict(per_replica_env or {})
        self.python = python or sys.executable
        self.cwd = cwd
        self.host = host
        self.ready_timeout_s = ready_timeout_s
        self.restart_base_delay_s = restart_base_delay_s
        self.restart_max_delay_s = restart_max_delay_s
        self._procs: Dict[str, _ReplicaProc] = {}
        self._restart_counts: Dict[str, int] = {}  # backoff (resettable)
        self._total_restarts: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._stopping = threading.Event()
        self._restarting: set = set()
        self._monitor_thread: Optional[threading.Thread] = None

    # -- lifecycle -------------------------------------------------------
    def start(self) -> Router:
        """Spawn the fleet, wait until every replica is ready, register
        each with the router, start crash monitoring.  Returns the
        router (started, serving)."""
        for i in range(self.n):
            rid = f"r{i}"
            self._procs[rid] = self._spawn(rid, i)
        for rid, rp in self._procs.items():
            self._await_ready(rp)
        self.router.start()
        for i in range(self.n):
            rid = f"r{i}"
            self.router.add_replica(self.host, self._procs[rid].port,
                                    rid=rid)
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, name="paddle-tpu-fleet-monitor",
            daemon=True)
        self._monitor_thread.start()
        return self.router

    def stop(self) -> None:
        self._stopping.set()
        if self._monitor_thread is not None:
            self._monitor_thread.join(timeout=5.0)
            self._monitor_thread = None
        with self._lock:
            procs = list(self._procs.values())
        for rp in procs:
            if rp.proc.poll() is None:
                try:
                    rp.proc.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + 15.0
        for rp in procs:
            try:
                rp.proc.wait(
                    timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                rp.proc.kill()
                rp.proc.wait(timeout=5.0)
        self.router.stop()

    def replica_port(self, rid: str) -> Optional[int]:
        with self._lock:
            rp = self._procs.get(rid)
            return rp.port if rp is not None else None

    def replica_pid(self, rid: str) -> Optional[int]:
        with self._lock:
            rp = self._procs.get(rid)
            return rp.proc.pid if rp is not None else None

    def restart_count(self, rid: str) -> int:
        """Total crash restarts of this slot over the supervisor's life
        (the backoff counter resets after a stable hour; this doesn't)."""
        with self._lock:
            return self._total_restarts.get(rid, 0)

    # -- spawn plumbing --------------------------------------------------
    def _spawn(self, rid: str, index: int) -> _ReplicaProc:
        env = dict(os.environ)
        env.update(self.env)
        env.update(self.per_replica_env.get(index, {}))
        argv = ([self.python, "-m", "paddle_tpu.serving",
                 "--host", self.host, "--port", "0"]
                + self.replica_args)
        proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            cwd=self.cwd, env=env, text=True)
        return _ReplicaProc(rid, proc)

    def _await_ready(self, rp: _ReplicaProc) -> None:
        if not rp.ready.wait(timeout=self.ready_timeout_s):
            tail = "\n".join(rp.stderr_tail)
            raise RuntimeError(
                f"replica {rp.rid} (pid {rp.proc.pid}) not ready after "
                f"{self.ready_timeout_s}s; stderr tail:\n{tail}")

    # -- crash restart ---------------------------------------------------
    def _monitor_loop(self) -> None:
        while not self._stopping.wait(0.2):
            with self._lock:
                dead = [
                    (rid, rp) for rid, rp in self._procs.items()
                    if rp.proc.poll() is not None
                    and rid not in self._restarting]
                for rid, _rp in dead:
                    self._restarting.add(rid)
            for rid, rp in dead:
                try:
                    self._restart(rid, rp)
                finally:
                    with self._lock:
                        self._restarting.discard(rid)

    def _restart(self, rid: str, rp: _ReplicaProc) -> None:
        """Respawn a crashed replica with capped exponential backoff.
        A replica that stayed up 60s earns a fresh backoff budget (a
        stable process that finally dies is an incident, not a crash
        loop)."""
        with self._lock:
            if time.monotonic() - rp.spawned_at > 60.0:
                self._restart_counts[rid] = 0
            self._restart_counts[rid] = \
                self._restart_counts.get(rid, 0) + 1
            count = self._restart_counts[rid]
            self._total_restarts[rid] = \
                self._total_restarts.get(rid, 0) + 1
        code = rp.proc.returncode
        from ..monitor import counter, enabled, flight

        if enabled():
            counter("router.replica_restarts_total").inc()
        flight.record("router.replica_restart", replica=rid,
                      exit_code=code, attempt=count)
        delay = min(self.restart_max_delay_s,
                    self.restart_base_delay_s * (2 ** (count - 1)))
        if self._stopping.wait(delay):
            return
        index = int(rid[1:]) if rid[1:].isdigit() else 0
        new_rp = self._spawn(rid, index)
        with self._lock:
            self._procs[rid] = new_rp
        try:
            self._await_ready(new_rp)
        except RuntimeError:
            # not ready in time: leave it; if it exited the monitor loop
            # takes another swing (with a longer backoff)
            return
        self.router.update_replica(rid, self.host, new_rp.port)

    # -- rolling restart -------------------------------------------------
    def rolling_restart(self,
                        drain_timeout_s: float = 30.0,
                        ready_wait_s: Optional[float] = None) -> None:
        """Restart every replica, one at a time, with zero downtime:
        router-drain -> SIGTERM (graceful drain, exit 0) -> respawn
        (same compile cache directory: warmup replays the persistent
        compilation cache) -> ready line -> passing probe -> next."""
        from ..monitor import flight

        if ready_wait_s is None:
            ready_wait_s = self.ready_timeout_s
        for i in range(self.n):
            rid = f"r{i}"
            with self._lock:
                rp = self._procs.get(rid)
                if rp is None:
                    continue
                self._restarting.add(rid)  # the crash monitor stands down
            try:
                flight.record("router.rolling_restart", replica=rid,
                              phase="drain")
                self.router.set_draining(rid)
                if rp.proc.poll() is None:
                    rp.proc.send_signal(signal.SIGTERM)
                    try:
                        rc = rp.proc.wait(timeout=drain_timeout_s + 10.0)
                    except subprocess.TimeoutExpired:
                        rp.proc.kill()
                        rc = rp.proc.wait(timeout=5.0)
                    if rc != 0:
                        flight.record("router.rolling_restart",
                                      replica=rid, phase="dirty_exit",
                                      exit_code=rc)
                new_rp = self._spawn(rid, i)
                with self._lock:
                    self._procs[rid] = new_rp
                self._await_ready(new_rp)
                self.router.update_replica(rid, self.host, new_rp.port)
                deadline = time.monotonic() + ready_wait_s
                while (self.router.replica_state(rid) != IN_ROTATION
                       and time.monotonic() < deadline):
                    self.router.probe_now(rid)
                    time.sleep(0.05)
                if self.router.replica_state(rid) != IN_ROTATION:
                    raise RuntimeError(
                        f"replica {rid} not back in rotation after "
                        f"{ready_wait_s}s (state "
                        f"{self.router.replica_state(rid)})")
                flight.record("router.rolling_restart", replica=rid,
                              phase="readmitted")
            finally:
                with self._lock:
                    self._restarting.discard(rid)
