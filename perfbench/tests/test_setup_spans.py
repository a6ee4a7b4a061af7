"""The arithmetic over the executor's first-calls record (setup_spans.py)
and the five `setup_*_s.train` readers built on it, on hand-made flight
events, and once on the record a real Executor writes on the CPU."""

import time
import types

import pytest

import registry
import setup_spans as S

NEW = ["setup_miss_calls_s.train", "setup_hit_calls_s.train",
       "setup_exec_prep_s.train", "setup_jit_other_s.train",
       "setup_outside_s.train"]
T_START = 1000.0


def call(kind, t0, phases, first=True, **deltas):
    out, at = [], 0.0
    for name, d in phases:
        out.append([name, at, d])
        at += d
    ev = {"kind": kind, "t0": t0, "dur": at + 0.001, "phases": out, **deltas}
    if first:
        ev["first_calls"] = 1
    return ev


def hit(t0, dispatch, fetch, mode="run_steps", first=True):
    return call(f"executor.{mode}", t0, [
        ("feed", .002), ("key", .001), ("gather", .004),
        ("dispatch", dispatch), ("writeback", .001), ("fetch", fetch)],
        first)


def miss(t0, compile_, gather, dispatch, in_gather, in_dispatch,
         mode="run_steps"):
    """`in_*`: jax's (trace, lower, backend) seconds inside the phase."""
    by_phase = {p: dict(zip(("trace_s", "lower_s", "backend_s"), t))
                for p, t in (("gather", in_gather), ("dispatch", in_dispatch))
                if t}
    return call("executor.compile", t0, [
        ("feed", .002), ("key", .001), ("compile", compile_),
        ("gather", gather), ("dispatch", dispatch), ("writeback", .001),
        ("fetch", .5)], mode=mode, jax_by_phase=by_phase, **{
            k: sum(t[k] for t in by_phase.values())
            for k in ("trace_s", "lower_s", "backend_s")},
        cache_load_s=0.1)


# a set-up as T's: start-up (a miss, then a hit), the step's miss, four
# more checked calls and the warm call; then the window's first calls.
# The process's first call compiles the step key's eager ops in `gather`.
SETUP = [miss(1002.0, 0.05, 0.3, 1.0, (0.02, 0.1, 0.15), (0.3, 0.2, 0.4),
              mode="run"),
         miss(1004.0, 0.8, 0.004, 15.0, None, (6.0, 4.0, 3.5)),
         hit(1020.0, 0.002, 0.6),
         hit(1021.0, 0.002, 0.3, mode="run")] + [
         hit(1022.0 + i, 0.002, 0.6) for i in range(4)]
SETUP_S = SETUP[-1]["t0"] + SETUP[-1]["dur"] + 5e-5 - T_START
WINDOW = [hit(T_START + SETUP_S + 1e-4 + 0.62 * i, 0.002, 0.6)
          for i in range(3)]


def test_set_up_is_what_ends_before_setup_s_does():
    events = SETUP + WINDOW + [hit(1030.0, .002, .6, first=False)]
    calls = S.select(events, T_START + SETUP_S, 16)
    assert calls == SETUP
    assert [e["kind"] for e in S.misses(calls)] == ["executor.compile"] * 2
    assert len(S.hits(calls)) == 6
    # a call that ends a microsecond after set-up is the window's
    assert S.select(SETUP, T_START + SETUP_S - 1e-4, 16) == SETUP[:-1]


def test_no_record_or_a_full_one_reads_nothing():
    assert S.select([], T_START + SETUP_S, 16) is None
    assert S.select([dict(e, first_calls=None) for e in SETUP],
                    T_START + SETUP_S, 16) is None
    # the record filled up before set-up ended: its split would be short
    assert S.select(SETUP, T_START + SETUP_S, len(SETUP)) is None
    assert S.select(SETUP, T_START + SETUP_S, len(SETUP) + 1) == SETUP


def test_the_split_adds_up_to_setup_s():
    miss_s, hit_s = S.miss_calls_s(SETUP), S.hit_calls_s(SETUP)
    assert miss_s == pytest.approx(
        sum(sum(d for _, _, d in e["phases"]) + 0.001 for e in SETUP[:2]))
    assert hit_s == pytest.approx(sum(e["dur"] for e in SETUP[2:]))
    assert S.outside_s(SETUP_S, SETUP) == pytest.approx(
        SETUP_S - miss_s - hit_s)
    assert S.outside_s(SETUP_S, SETUP) + miss_s + hit_s == pytest.approx(
        SETUP_S)
    assert S.exec_prep_s(SETUP) == pytest.approx(0.85)
    # dispatch less the trace + lower + backend inside it: not the eager
    # jits of `gather`, and not cache_load_s, which lies inside backend_s
    assert S.jit_other_s(SETUP) == pytest.approx(
        (1.0 - 0.9) + (15.0 - 13.5))
    assert SETUP[0]["trace_s"] + SETUP[0]["lower_s"] + SETUP[0][
        "backend_s"] == pytest.approx(1.17)
    assert S.exec_prep_s(SETUP[2:]) == 0 and S.jit_other_s(SETUP[2:]) == 0


class _Ring:
    def __init__(self, events):
        self._events = events

    def events(self, kind=None):
        return [e for e in self._events if kind is None or e["kind"] == kind
                or e["kind"].startswith(kind + ".")]


def _ctx(setup_s=SETUP_S):
    return {"cell": types.SimpleNamespace(t_start=T_START),
            "result": {"setup_s": setup_s}}


def test_readers_on_a_filled_ring(monkeypatch, capsys):
    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight

    others = [{"kind": "executor.recompile", "changed": ["feed-signature"]}]
    monkeypatch.setattr(flight, "default_recorder",
                        lambda: _Ring(SETUP + others + WINDOW))
    monkeypatch.setattr(monitor, "compile_phases", lambda: {
        "trace_s": 6.3, "lower_s": 4.2, "backend_s": 3.9},
        raising=False)
    got = {n: registry.load_reader(n).read(_ctx()) for n in NEW}
    assert got["setup_miss_calls_s.train"] == pytest.approx(
        S.miss_calls_s(SETUP))
    assert got["setup_hit_calls_s.train"] == pytest.approx(
        S.hit_calls_s(SETUP))
    assert got["setup_exec_prep_s.train"] == pytest.approx(0.85)
    assert got["setup_jit_other_s.train"] == pytest.approx(1.6)
    assert (got["setup_outside_s.train"] + got["setup_miss_calls_s.train"]
            + got["setup_hit_calls_s.train"]) == pytest.approx(SETUP_S)
    err = capsys.readouterr().err
    assert err.startswith("setup_calls ") and '"lower_s": 4.2' in err


def test_readers_find_nothing_on_a_program_that_records_nothing(
        monkeypatch):
    """The parent commit: no first-calls record (the ring holds only what
    tracing wrote) and no FIRST_CALLS.  Every reader returns None and
    none raises."""
    from paddle_tpu.core import executor
    from paddle_tpu.monitor import flight

    old = [dict(e) for e in SETUP + WINDOW]
    for e in old:
        e.pop("first_calls")
    monkeypatch.setattr(flight, "default_recorder", lambda: _Ring(old))
    for name in NEW:
        assert registry.load_reader(name).read(_ctx()) is None, name
    monkeypatch.setattr(flight, "default_recorder",
                        lambda: _Ring(SETUP + WINDOW))
    monkeypatch.delattr(executor, "FIRST_CALLS")
    for name in NEW:
        assert registry.load_reader(name).read(_ctx()) is None, name


def test_readers_on_the_record_an_executor_writes():
    """A set-up and a window of a tiny net on the CPU, tracing off: the
    readers split set-up into the two misses, the hits and the rest."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import layers, monitor
    from paddle_tpu.monitor import flight

    flight.default_recorder().clear()
    t_start = time.time()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        loss = layers.reduce_mean(layers.square(layers.fc(x, size=4)))
        pt.optimizer.Adam(1e-3).minimize(loss)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    before = monitor.compile_phases()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 4, 8), "float32")}
    for _ in range(3):  # the miss, a checked call, the warm call
        exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    setup_s = time.time() - t_start
    for _ in range(2):  # the window
        exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    ctx = {"cell": types.SimpleNamespace(t_start=t_start),
           "result": {"setup_s": setup_s}}
    calls = S.setup_calls(ctx)
    assert [e["kind"] for e in calls] == [
        "executor.compile", "executor.compile", "executor.run_steps",
        "executor.run_steps"]
    got = {n: registry.load_reader(n).read(ctx) for n in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["setup_miss_calls_s.train"] >= sum(
        monitor.compile_phases()[k] - before[k] for k in S.JAX_PHASES)
    assert got["setup_exec_prep_s.train"] + got["setup_jit_other_s.train"] \
        < got["setup_miss_calls_s.train"]
    flight.default_recorder().clear()


def test_benchmark_lists_the_five_for_every_cell():
    bench = registry.load_json(registry.os.path.join(registry.ROOT,
                                                     "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": "executor",
            "moves": "setup_s"}
    for w in bench["workloads"]:
        cell = registry.Cell(w["name"], w["chips"], {}, {}, {}, bench=bench)
        assert set(NEW) <= {m["name"] for m in cell.metrics("per_layer")}
