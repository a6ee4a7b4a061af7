"""Flight recorder + watchdog + unified timeline + scrape endpoint
(the observability-PR tentpole), asserted on the CPU mesh:

  * a run with an injected NaN trips the watchdog and dumps a flight
    record naming the bad step;
  * a SIGTERM'd bench.py subprocess leaves a parseable flight dump with
    the last completed step, the trigger, and the event history;
  * /metrics serves the PR-1 counters; /health and /flight respond;
  * the merged chrome trace holds host flight spans AND xplane events on
    one clock, and tools/trace_report.py summarizes it.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, monitor, profiler
from paddle_tpu.flags import FLAGS
from paddle_tpu.monitor import flight, serve
from paddle_tpu.monitor.watchdog import Watchdog, WatchdogError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def monitor_on():
    monitor.default_registry().reset()
    flight.default_recorder().clear()
    FLAGS.monitor = True
    yield
    FLAGS.reset("monitor")
    FLAGS.reset("flight_dir")
    flight.default_recorder().clear()
    monitor.default_registry().reset()


def _loss_program():
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[3], dtype="float32")
        loss = layers.reduce_mean(x)
    return prog, startup, loss


# ---------------------------------------------------------------------------
# Flight recorder core
# ---------------------------------------------------------------------------


def test_ring_is_bounded_and_counts_drops():
    rec = flight.FlightRecorder(capacity=32)
    for i in range(100):
        rec.record("ev", i=i)
    evs = rec.events()
    assert len(evs) == 32
    assert evs[-1]["i"] == 99 and evs[0]["i"] == 68  # oldest evicted
    assert rec.header("t")["events_dropped"] == 68


def test_record_is_noop_when_monitor_off():
    assert not FLAGS.monitor
    flight.default_recorder().clear()
    flight.record("ev", x=1)
    flight.note_step(7, 0.5)
    assert flight.default_recorder().events() == []
    assert flight.default_recorder().last_step is None
    assert Watchdog().arm() is False  # watchdog rides the same gate


def test_dump_names_last_step_and_history(tmp_path, monitor_on):
    flight.record("executor.run", t0=time.time(), dur=0.01)
    flight.note_step(41, 1.25)
    path = flight.dump(path=str(tmp_path / "f.jsonl"), trigger="manual")
    lines = [json.loads(ln) for ln in open(path)]
    hdr = lines[0]
    assert hdr["kind"] == "flight.header"
    assert hdr["trigger"] == "manual"
    assert hdr["last_step"] == 41 and hdr["last_loss"] == 1.25
    assert "flags" in hdr and hdr["flags"]["monitor"] is True
    assert [ln["kind"] for ln in lines[1:]] == ["executor.run"]


def test_executor_records_spans_and_recompile_causes(monitor_on):
    prog, startup, loss = _loss_program()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    feed_a = {"x": np.ones((2, 3), "float32")}
    exe.run(prog, feed=feed_a, fetch_list=[loss], scope=scope)  # compile
    exe.run(prog, feed=feed_a, fetch_list=[loss], scope=scope)  # hit
    # shape change -> miss after hit -> a recompile, cause = feed-signature
    exe.run(prog, feed={"x": np.ones((5, 3), "float32")},
            fetch_list=[loss], scope=scope)
    kinds = [e["kind"] for e in flight.default_recorder().events()]
    assert "executor.compile" in kinds and "executor.run" in kinds
    recs = flight.default_recorder().events(kind="executor.recompile")
    assert recs and "feed-signature" in recs[-1]["changed"]
    spans = flight.default_recorder().events(kind="executor.run")
    assert all("t0" in e and e["dur"] >= 0 for e in spans)


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------


def test_watchdog_nan_loss_raises_and_dumps(tmp_path, monitor_on):
    """The NaN-injection acceptance path: a real executor run goes NaN at
    step 6; the watchdog trips at that step and the flight dump names
    it."""
    FLAGS.flight_dir = str(tmp_path)
    prog, startup, loss = _loss_program()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    # the loss alone may trip it: a millisecond step that takes 5 x the
    # median while other work shares the cores raised `throughput_collapse`
    # at step 5 (seen in a whole run and under load, one run in six)
    wd = Watchdog(action="raise", min_steps=2, collapse_factor=float("inf"))
    mon = monitor.StepMonitor(name="nan_test", watchdog=wd)
    mon.step()  # arm the timer
    with pytest.raises(WatchdogError, match="step 6"):
        for i in range(1, 11):
            fill = np.nan if i == 6 else 1.0
            (lv,) = exe.run(prog,
                            feed={"x": np.full((2, 3), fill, "float32")},
                            fetch_list=[loss], scope=scope)
            mon.step(loss=float(np.asarray(lv).ravel()[0]))
    assert i == 6  # the loop died AT the bad step, not later
    dumps = sorted(tmp_path.glob("flight-*-watchdog.jsonl"))
    assert len(dumps) == 1
    lines = [json.loads(ln) for ln in open(dumps[0])]
    hdr = lines[0]
    assert hdr["trigger"] == "watchdog"
    assert hdr["trip"] == "nan_loss" and hdr["trip_step"] == 6
    assert "step 6" in hdr["trip_detail"]
    assert hdr["last_step"] == 6
    # recent event history: executor spans + step records, NaN marked
    steps = [ln for ln in lines if ln.get("kind") == "step"]
    assert steps and steps[-1]["step"] == 6 and steps[-1]["loss"] == "NaN"
    assert any(ln["kind"].startswith("executor.") for ln in lines[1:])
    assert any(ln["kind"] == "watchdog.trip" for ln in lines[1:])


def test_watchdog_loss_spike_zscore():
    wd = Watchdog(action="log", min_steps=2, z_threshold=4.0, window=16)
    rng = np.random.RandomState(0)
    for i in range(1, 13):
        wd.observe_step(i, 1.0 + 0.01 * rng.randn(), 0.01)
    assert not wd.trips
    trip = wd.observe_step(13, 9.0, 0.01)
    assert trip is not None and trip.kind == "loss_spike"
    assert "sigma" in trip.detail


def test_watchdog_throughput_collapse():
    wd = Watchdog(action="log", min_steps=2, collapse_factor=5.0)
    for i in range(1, 11):
        wd.observe_step(i, 1.0, 0.01)
    assert not wd.trips
    trip = wd.observe_step(11, 1.0, 0.5)
    assert trip is not None and trip.kind == "throughput_collapse"
    assert "median" in trip.detail


def test_watchdog_hang_daemon_thread(monitor_on):
    trips = []
    wd = Watchdog(min_steps=2, hang_factor=2.0, hang_floor_s=0.2,
                  on_trip=trips.append)
    for i in range(1, 6):
        wd.observe_step(i, 1.0, 0.05)
    assert wd.arm(poll_interval_s=0.05) is True
    try:
        deadline = time.time() + 5.0
        while not trips and time.time() < deadline:
            time.sleep(0.05)  # no steps complete: this IS the hang
    finally:
        wd.disarm()
    assert trips and trips[0].kind == "hang"
    assert "no step completed" in trips[0].detail


# ---------------------------------------------------------------------------
# SIGTERM'd bench subprocess leaves a black box
# ---------------------------------------------------------------------------


def test_sigterm_bench_leaves_flight_dump(tmp_path):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "FLAGS_monitor": "1",
        "FLAGS_flight_dir": str(tmp_path),
        "FLAGS_monitor_jsonl": str(tmp_path / "steps.jsonl"),
    })
    # enough calls that the run is mid-steps when the signal lands; the
    # armed flight dir puts timed_steps in live-stepping mode, so
    # steps.jsonl grows per call — our readiness signal
    proc = subprocess.Popen(
        [sys.executable, "bench.py", "--model", "mnist", "--smoke",
         "--calls", "2000", "--scan-steps", "2", "--batch-size", "8"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    try:
        steps_file = tmp_path / "steps.jsonl"
        deadline = time.time() + 150.0
        while time.time() < deadline:
            if proc.poll() is not None:
                out, err = proc.communicate()
                pytest.fail(f"bench exited early rc={proc.returncode}: "
                            f"{err.decode()[-800:]}")
            if steps_file.exists() and \
                    len(steps_file.read_text().splitlines()) >= 3:
                break
            time.sleep(0.25)
        else:
            pytest.fail("bench never started stepping")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert rc == -signal.SIGTERM  # handler re-raised: conventional death
    dumps = sorted(tmp_path.glob("flight-*-sigterm.jsonl"))
    assert len(dumps) == 1, list(tmp_path.iterdir())
    lines = [json.loads(ln) for ln in open(dumps[0])]  # parseable JSONL
    hdr = lines[0]
    assert hdr["trigger"] == "sigterm"
    assert hdr["last_step"] >= 3  # names the last completed step
    assert hdr["argv"][0].endswith("bench.py")
    kinds = {ln["kind"] for ln in lines[1:]}
    assert "step" in kinds  # recent event history made it to disk
    assert any(k.startswith("executor.") for k in kinds)
    assert any(ln["kind"] == "signal" and ln.get("name") == "SIGTERM"
               for ln in lines[1:])


# ---------------------------------------------------------------------------
# Scrape endpoint
# ---------------------------------------------------------------------------


def test_serve_metrics_health_flight(monitor_on):
    prog, startup, loss = _loss_program()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    exe.run(prog, feed={"x": np.ones((2, 3), "float32")},
            fetch_list=[loss], scope=scope)
    flight.note_step(3, 0.5)
    port = serve.start(port=0)  # 0 = ephemeral; FLAGS 0 means disabled
    try:
        base = f"http://127.0.0.1:{port}"
        prom = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "# TYPE executor_compiles counter" in prom
        assert "executor_compile_seconds_count" in prom  # PR-1 histogram

        health = json.loads(
            urllib.request.urlopen(base + "/health").read())
        assert health["status"] == "ok" and health["last_step"] == 3

        fl = urllib.request.urlopen(base + "/flight?n=50").read().decode()
        lines = [json.loads(ln) for ln in fl.splitlines()]
        assert lines[0]["kind"] == "flight.header"
        assert any(ln.get("kind", "").startswith("executor.")
                   for ln in lines[1:])

        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/nope")
        assert e.value.code == 404
    finally:
        serve.stop()


def test_serve_disabled_without_port(monitor_on):
    FLAGS.reset("monitor_port")
    assert serve.start() is None  # FLAGS.monitor_port=0 -> no server


# ---------------------------------------------------------------------------
# Unified host+device timeline + trace report
# ---------------------------------------------------------------------------


def test_unified_trace_merges_host_and_device(tmp_path, monitor_on):
    trace_dir = str(tmp_path / "trace")
    prog, startup, loss = _loss_program()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 3), "float32")}
    mon = monitor.StepMonitor(name="tr", watchdog=None)
    profiler.start_profiler(trace_dir=trace_dir)
    try:
        mon.step()
        for _ in range(3):
            exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
            mon.step(loss=1.0)
    finally:
        profiler.stop_profiler(tracing=True)

    out = str(tmp_path / "merged.json")
    n = profiler.export_unified_chrome_trace(out)
    assert n > 0
    doc = json.load(open(out))
    procs = {e["pid"]: e.get("args", {}) for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    flight_pids = {p for p, a in procs.items()
                   if a.get("source") == "flight"}
    xplane_pids = {p for p, a in procs.items()
                   if a.get("source") == "xplane"}
    assert flight_pids and xplane_pids  # both worlds in ONE file

    host = [e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["pid"] in flight_pids]
    assert any(e["name"].startswith("executor.") for e in host)
    assert any(e["name"] == "step" for e in host)
    xp = [e for e in doc["traceEvents"]
          if e.get("ph") == "X" and e["pid"] in xplane_pids]
    assert xp  # xplane op events (device planes on TPU; host plane on CPU)

    # shared clock: every span lands inside the capture window (generous
    # slack for the start_trace call itself)
    window_us = 120e6
    for e in host:
        assert -5e6 < e["ts"] < window_us, e
    # embedded flight section for postmortem tooling
    assert doc["flight"]["header"]["kind"] == "flight.header"

    # trace_report over the merged file: top-ops + host breakdown +
    # recompile causes, stdlib-only (runs as a subprocess like a human)
    r = subprocess.run(
        [sys.executable, os.path.join("tools", "trace_report.py"), out],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "Top ops by total time" in r.stdout
    assert "Host time breakdown" in r.stdout
    assert "compile" in r.stdout and "run" in r.stdout


def test_host_only_unified_trace(tmp_path, monitor_on):
    """No jax trace captured: the export still produces a valid host-only
    timeline (crash postmortems rarely have a live profiler session)."""
    rec = flight.FlightRecorder(capacity=64)
    t = time.time()
    rec.record("executor.compile", mode="run", t0=t, dur=1.5)
    rec.record("executor.run", t0=t + 1.6, dur=0.1)
    rec.record("executor.recompile", changed=["feed-signature"])
    out = str(tmp_path / "host_only.json")
    profiler.export_unified_chrome_trace(out, trace_dir="", flight=rec,
                                         trace_start_epoch=t)
    doc = json.load(open(out))
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"executor.compile",
                                          "executor.run"}
    inst = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
    assert any(e["name"] == "executor.recompile" for e in inst)
