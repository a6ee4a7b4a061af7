"""ReplicaSupervisor (serving/fleet.py, ISSUE 18): real `python -m
paddle_tpu.serving` replica subprocesses behind an in-process Router —
the rolling-restart satellite (zero client-visible errors, compile
counter flat on the warm persistent cache) plus crash-restart and the
structured /health readiness detail across the process boundary."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.flags import FLAGS
from paddle_tpu.monitor import default_registry, flight
from paddle_tpu.serving.fleet import ReplicaSupervisor
from paddle_tpu.serving.router import IN_ROTATION, Router

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_state():
    FLAGS.reset()
    FLAGS.monitor = True
    default_registry().reset()
    flight.default_recorder().clear()
    yield
    FLAGS.reset()
    default_registry().reset()
    flight.default_recorder().clear()


def _export_fc_model(dirname, in_dim=4, out_dim=2, seed=3):
    prog, startup = pt.Program(), pt.Program()
    prog.random_seed = startup.random_seed = seed
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[in_dim], dtype="float32")
        h = layers.fc(x, size=8, act="relu")
        out = layers.fc(h, size=out_dim)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup, scope=scope)
        pt.io.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=prog, scope=scope)
    return dirname


def _fleet_env(cache_dir):
    return {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
        "FLAGS_serving_drain_timeout_s": "10",
    }


def _get_json(url, timeout=5.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _scrape_scalar(port, name):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=5) as r:
        for line in r.read().decode().splitlines():
            if line.startswith(name + " "):
                return float(line.rsplit(" ", 1)[1])
    return 0.0


def _cache_entries(cache_dir):
    return sorted(
        os.path.join(dp, f)[len(cache_dir):]
        for dp, _dn, fns in os.walk(cache_dir) for f in fns)


class _Stream:
    """Closed-loop client stream against the router; every response is
    recorded so 'zero client-visible errors' is checkable after the
    fact (429s excluded: shed load is a replica policy, not an
    availability failure)."""

    def __init__(self, url):
        self.url = url
        self.results = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        body = json.dumps({"inputs": {"x": [[0.1] * 4]},
                           "timeout_s": 15}).encode()
        while not self._stop.is_set():
            req = urllib.request.Request(
                f"{self.url}/v1/models/demo:predict", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=20) as r:
                    self.results.append((r.status, r.read()))
            except urllib.error.HTTPError as e:
                self.results.append((e.code, e.read()))
            except Exception as e:  # noqa: BLE001 — recorded, asserted on
                self.results.append((None, repr(e)))
            time.sleep(0.05)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=30)
        return self.results

    def errors(self):
        return [(c, b) for c, b in self.results
                if c != 200 and c != 429]


class TestFleetLifecycle:
    def test_rolling_restart_and_crash_restart(self, tmp_path):
        """One fleet session, three acts (subprocess spawns are the
        cost, so they amortize):

        1. readiness detail + fleet introspection across the wire;
        2. rolling restart under a continuous client stream — zero
           non-429 client errors, replica compile counters flat during
           the post-restart stream, and the persistent-cache dir gains
           NO new entries (warmup replayed, nothing recompiled);
        3. chaos SIGKILL -> supervisor crash-restart -> back in
           rotation, stream still clean.
        """
        model_dir = _export_fc_model(str(tmp_path / "fc"))
        cache_dir = str(tmp_path / "xla_cache")
        sup = ReplicaSupervisor(
            ["--model", f"demo={model_dir}", "--buckets", "1,2",
             "--max-wait-ms", "1"],
            n=2, router=Router(),
            env=_fleet_env(cache_dir), cwd=REPO_ROOT,
            restart_base_delay_s=0.1)
        router = sup.start()
        stream = None
        try:
            url = router.url
            # -- act 1: the fleet is introspectable end to end ---------
            status, reps = _get_json(f"{url}/v1/replicas")
            assert status == 200
            reps = reps["replicas"]
            assert [r["rid"] for r in reps] == ["r0", "r1"]
            assert all(r["state"] == IN_ROTATION for r in reps)
            # structured readiness detail straight off a replica
            p0 = sup.replica_port("r0")
            status, health = _get_json(f"http://127.0.0.1:{p0}/health")
            assert status == 200
            detail = health["serving"]["models"]["demo"]
            assert detail["state"] == "ready"
            assert detail["warm_buckets"] == detail["ladder_size"] == 2
            # warmup populated the shared persistent cache
            entries_before = _cache_entries(cache_dir)
            assert entries_before, "persistent cache not populated"

            # -- act 2: rolling restart under load ---------------------
            stream = _Stream(url).start()
            deadline = time.time() + 10
            while not stream.results and time.time() < deadline:
                time.sleep(0.02)
            sup.rolling_restart(drain_timeout_s=15)
            # both replicas came back on NEW pids/ports, in rotation
            assert router.replica_state("r0") == IN_ROTATION
            assert router.replica_state("r1") == IN_ROTATION
            phases = [e["phase"] for e in flight.default_recorder()
                      .events(kind="router.rolling_restart")]
            assert phases.count("drain") == 2
            assert phases.count("readmitted") == 2
            # compile counters flat while serving continues post-restart
            ports = [sup.replica_port(r) for r in ("r0", "r1")]
            compiles_0 = [_scrape_scalar(p, "executor_compiles")
                          for p in ports]
            n_before = len(stream.results)
            deadline = time.time() + 20
            while (len(stream.results) < n_before + 10
                   and time.time() < deadline):
                time.sleep(0.05)
            compiles_1 = [_scrape_scalar(p, "executor_compiles")
                          for p in ports]
            assert compiles_1 == compiles_0, (
                "post-restart serving recompiled", compiles_0,
                compiles_1)
            # ...and the persistent cache gained no new entries: the
            # respawned warmup replayed compiled executables from disk
            assert _cache_entries(cache_dir) == entries_before

            # -- act 3: crash restart ----------------------------------
            pid = sup.replica_pid("r0")
            os.kill(pid, signal.SIGKILL)
            deadline = time.time() + 60
            while ((sup.restart_count("r0") < 1
                    or router.replica_state("r0") != IN_ROTATION
                    or sup.replica_pid("r0") == pid)
                   and time.time() < deadline):
                time.sleep(0.1)
            assert sup.restart_count("r0") == 1
            assert sup.replica_pid("r0") != pid
            assert router.replica_state("r0") == IN_ROTATION
            restarts = flight.default_recorder().events(
                kind="router.replica_restart")
            assert restarts and restarts[-1]["replica"] == "r0"
            assert restarts[-1]["exit_code"] == -signal.SIGKILL
            assert default_registry().get(
                "router.replica_restarts_total").value == 1

            # the whole session: zero client-visible non-429 errors
            results = stream.stop()
            stream = None
            assert len(results) >= 10, "stream barely ran"
            assert [] == [
                (c, b) for c, b in results if c != 200 and c != 429]
        finally:
            if stream is not None:
                stream.stop()
            sup.stop()


class TestFleetCLI:
    def test_cli_replicas_flag_boots_fleet(self, tmp_path):
        """`python -m paddle_tpu.serving --replicas 2` prints a
        machine-readable router_ready line and serves through the
        router; SIGTERM tears the whole fleet down cleanly."""
        model_dir = _export_fc_model(str(tmp_path / "fc"))
        env = dict(os.environ, **_fleet_env(str(tmp_path / "cache")))
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving",
             "--port", "0", "--replicas", "2",
             "--model", f"demo={model_dir}",
             "--buckets", "1,2", "--max-wait-ms", "1"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            cwd=REPO_ROOT, env=env, text=True)
        try:
            line = proc.stdout.readline()
            ready = json.loads(line)
            assert ready["event"] == "router_ready"
            assert ready["replicas"] == 2
            assert len(ready["replica_ports"]) == 2
            url = f"http://127.0.0.1:{ready['port']}"
            req = urllib.request.Request(
                f"{url}/v1/models/demo:predict",
                data=json.dumps(
                    {"inputs": {"x": [[0.1] * 4]}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                out = json.loads(r.read())
            assert r.status == 200 and "outputs" in out
            status, reps = _get_json(f"{url}/v1/replicas")
            assert len(reps["replicas"]) == 2
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=30) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)

    def test_supervisor_strips_port_from_replica_args(self):
        sup = ReplicaSupervisor(
            ["--model", "m=/x", "--port", "8080", "--buckets", "1"],
            n=1, router=Router())
        assert "--port" not in sup.replica_args
        assert "8080" not in sup.replica_args
        assert sup.replica_args == ["--model", "m=/x", "--buckets", "1"]

    def test_zero_cost_import_contract_fresh_interpreter(self):
        """`import paddle_tpu.serving` on a fresh interpreter must not
        load the router/fleet modules (nor jax via them) — the scale-out
        tier is pay-for-use."""
        code = (
            "import sys\n"
            "import paddle_tpu.serving\n"
            "bad = [m for m in sys.modules\n"
            "       if m.endswith(('serving.router', 'serving.fleet'))]\n"
            "assert not bad, bad\n"
            "from paddle_tpu.serving import Router  # lazy export works\n"
            "assert 'paddle_tpu.serving.router' in sys.modules\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO_ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=REPO_ROOT, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 0, r.stderr
