"""The measurement inside the training path (core/executor.py,
monitor/flight.py, kernels/*.py): an executor call's host phases as spans
in any profiler session and in the flight ring, jax's compile phases
totalled inside Executor calls, a name on every Pallas kernel, and each
op's type in its HLO metadata."""

import ast
import glob
import os
import sys
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, monitor
from paddle_tpu.core.executor import FIRST_CALLS
from paddle_tpu.flags import FLAGS
from paddle_tpu.monitor import flight

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIT_PHASES = ["feed", "key", "gather", "dispatch", "writeback", "fetch"]
MISS_PHASES = ["feed", "key", "compile", "gather", "dispatch", "writeback",
               "fetch"]
STEPS = 3


def _train_net():
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.layer_norm(layers.fc(x, size=16, act="relu"))
        loss = layers.reduce_mean(layers.square(layers.fc(h, size=1) - y))
        pt.optimizer.Adam(1e-3).minimize(loss)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)

    def call(batch=4):
        """One `run_steps` call; another `batch` is another signature."""
        feed = {"x": np.ones((STEPS, batch, 8), "float32"),
                "y": np.ones((STEPS, batch, 1), "float32")}
        return exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)

    return exe, call


def _tiles(ev):
    """The phases lie back to back inside [0, dur], in order; after the
    last, the call's frame comes down and its event is written."""
    at = ev["phases"][0][1]
    assert 0 <= at < 1e-3
    for _, start, dur in ev["phases"]:
        assert dur >= 0 and start == pytest.approx(at, abs=2e-6)
        at = start + dur
    assert at <= ev["dur"] + 2e-6 and ev["dur"] - at < 0.05


# (a) ------------------------------------------------------------------------


def _past_the_first_calls(call):
    """Calls 2..FIRST_CALLS of `_train_net`'s executor (start-up was the
    first): after them, tracing off records nothing."""
    for _ in range(FIRST_CALLS - 1):
        call()


def test_profiler_session_turns_the_spans_on(clean_ring, tmp_path):
    from jax.profiler import ProfileData

    _, call = _train_net()
    # the miss and the hits after it, outside any session: the first-calls
    # record holds them and nothing else
    _past_the_first_calls(call)
    assert {e.get("first_calls") for e in clean_ring.events()} == {1}
    assert len(clean_ring.events()) == FIRST_CALLS
    clean_ring.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        call()
    finally:
        jax.profiler.stop_trace()
    call()  # the session is over: off again
    (ev,) = clean_ring.events()
    assert ev["kind"] == "executor.run_steps" and ev["steps"] == STEPS
    assert ev["call"] == FIRST_CALLS + 1 and "first_calls" not in ev
    assert [p[0] for p in ev["phases"]] == HIT_PHASES
    _tiles(ev)
    assert ev["dispatch_s"] + ev["device_wait_s"] == pytest.approx(
        ev["dur"], abs=2e-6)
    # a profiler session is not FLAGS.monitor: no histogram was written
    assert monitor.default_registry().names() == []

    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("executor."):
                    found[e.name] = (e.start_ns, e.duration_ns,
                                     dict(e.stats))
    assert sorted(found) == sorted(
        ["executor.run_steps"] + ["executor." + p for p in HIT_PHASES])
    p0, pd, pstats = found["executor.run_steps"]
    assert pstats["call"] == ev["call"] and pstats["steps"] == STEPS
    assert pstats["compiled"] == 0
    for name in HIT_PHASES:
        s, d, stats = found["executor." + name]
        assert stats["call"] == ev["call"]
        assert p0 <= s and s + d <= p0 + pd
    order = sorted(HIT_PHASES, key=lambda n: found["executor." + n][0])
    assert order == HIT_PHASES


def test_a_miss_records_compile_with_jaxs_phases(clean_ring):
    FLAGS.monitor = True
    _, call = _train_net()
    call()
    (ev,) = clean_ring.events(kind="executor.compile")[-1:]
    assert ev["mode"] == "run_steps"
    assert [p[0] for p in ev["phases"]] == MISS_PHASES
    _tiles(ev)
    # jax's own work comes with the first dispatch, inside this call
    assert ev["trace_s"] > 0 and ev["lower_s"] > 0 and ev["backend_s"] > 0
    assert ev["trace_s"] + ev["lower_s"] + ev["backend_s"] < ev["dur"]


def test_run_and_run_accumulated_share_the_phases(clean_ring):
    FLAGS.monitor = True
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        loss = layers.reduce_mean(layers.fc(x, size=1))
        pt.optimizer.SGD(1e-2).minimize(loss)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)
    one = {"x": np.ones((4, 8), "float32")}
    acc = {"x": np.ones((2, 4, 8), "float32")}
    for _ in range(2):
        exe.run(prog, feed=one, fetch_list=[loss], scope=scope)
        exe.run_accumulated(prog, feed=acc, fetch_list=[loss], scope=scope)
    (run_ev,) = clean_ring.events(kind="executor.run")[-1:]
    assert [p[0] for p in run_ev["phases"]] == HIT_PHASES
    _tiles(run_ev)
    (acc_ev,) = clean_ring.events(kind="executor.run_accumulated")[-1:]
    assert [p[0] for p in acc_ev["phases"]] == HIT_PHASES
    assert acc_ev["steps"] == 2 and acc_ev["call"] > run_ev["call"]
    _tiles(acc_ev)


@pytest.mark.parametrize("mode", ["run", "run_steps"])
@pytest.mark.parametrize("layout", ["data_parallel", "sharded"])
def test_a_wrapped_programs_calls_are_recorded_like_any(clean_ring, layout,
                                                        mode):
    """A CompiledProgram or ShardedProgram is an argument of the one call
    path (8-device CPU mesh): its miss leaves the seven phases, its hit
    six, under the mode's own kind."""
    from paddle_tpu.parallel.sharding import ShardedProgram, ShardingPlan

    FLAGS.monitor = True
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        loss = layers.reduce_mean(layers.fc(x, size=1))
        pt.optimizer.SGD(1e-2).minimize(loss)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)
    target = (pt.CompiledProgram(prog).with_data_parallel(loss.name)
              if layout == "data_parallel" else
              ShardedProgram(prog, ShardingPlan(mesh_axes={"data": 8}),
                             loss_name=loss.name))
    shape = (8, 8) if mode == "run" else (STEPS, 8, 8)
    for _ in range(2):
        getattr(exe, mode)(target, feed={"x": np.ones(shape, "float32")},
                           fetch_list=[loss], scope=scope)
    (miss,) = clean_ring.events(kind="executor.compile")[-1:]
    assert miss["mode"] == mode
    assert [p[0] for p in miss["phases"]] == MISS_PHASES
    (hit,) = clean_ring.events(kind=f"executor.{mode}")
    assert [p[0] for p in hit["phases"]] == HIT_PHASES
    assert hit["call"] == miss["call"] + 1 and hit.get("steps") == (
        None if mode == "run" else STEPS)
    _tiles(hit)
    assert monitor.default_registry().get("executor.delegated.calls") is None


# (b) ------------------------------------------------------------------------


def _counting_clocks(monkeypatch):
    """The clock reads made from core/executor.py from here on."""
    from paddle_tpu.core import executor as ex

    reads = []

    def counting(real):
        def clock():
            if sys._getframe(1).f_code.co_filename == ex.__file__:
                reads.append(real.__name__)
            return real()
        return clock

    for name in ("perf_counter", "perf_counter_ns", "time", "monotonic"):
        monkeypatch.setattr(time, name, counting(getattr(time, name)))
    return reads


def test_tracing_off_records_the_first_calls_then_nothing(clean_ring,
                                                          monkeypatch):
    _, call = _train_net()
    _past_the_first_calls(call)
    evs = clean_ring.events()
    # start-up, the step's miss, then hits: each with its phases, tracing
    # off, on the epoch clock
    assert [e["kind"] for e in evs] == (
        ["executor.compile"] * 2 + ["executor.run_steps"] * (FIRST_CALLS - 2))
    assert [e["call"] for e in evs] == list(range(1, FIRST_CALLS + 1))
    assert [e["mode"] for e in evs[:2]] == ["run", "run_steps"]
    for ev in evs:
        assert ev["first_calls"] == 1 and "counters" not in ev
        assert [p[0] for p in ev["phases"]] == (
            MISS_PHASES if ev["kind"] == "executor.compile" else HIT_PHASES)
        _tiles(ev)
        assert abs(ev["t0"] - time.time()) < 60
    assert evs[1]["trace_s"] > 0 and evs[1]["backend_s"] > 0
    clean_ring.clear()
    reads = _counting_clocks(monkeypatch)
    call()  # the 17th, a hit
    assert reads == []
    assert clean_ring.events() == []
    assert monitor.default_registry().names() == []
    FLAGS.monitor = True
    call()
    assert "perf_counter_ns" in reads  # the probe does see the clock


def test_each_executor_keeps_its_own_first_calls(clean_ring):
    _, call = _train_net()
    _past_the_first_calls(call)
    call()
    assert len(clean_ring.events()) == FIRST_CALLS
    _, other = _train_net()  # start-up and its first step are recorded
    other()
    evs = clean_ring.events()[FIRST_CALLS:]
    assert [(e["kind"], e["call"], e["first_calls"]) for e in evs] == [
        ("executor.compile", 1, 1), ("executor.compile", 2, 1)]


def test_a_miss_after_the_first_calls_is_recorded_from_the_miss_on(
        clean_ring, monkeypatch):
    _, call = _train_net()
    _past_the_first_calls(call)
    clean_ring.clear()
    before = monitor.compile_phases()
    call(5)
    after = monitor.compile_phases()
    (ev,) = clean_ring.events()
    assert ev["kind"] == "executor.compile" and "first_calls" not in ev
    assert ev["call"] == FIRST_CALLS + 1
    assert [p[0] for p in ev["phases"]] == MISS_PHASES[2:]
    _tiles(ev)
    for k in before:
        assert ev[k] == pytest.approx(after[k] - before[k], abs=2e-6), k
    assert ev["jax_by_phase"]["dispatch"]["backend_s"] == pytest.approx(
        ev["backend_s"], abs=2e-6)
    # and the hit after it is silent again
    reads = _counting_clocks(monkeypatch)
    call(5)
    assert reads == [] and len(clean_ring.events()) == 1


def test_the_recorded_misses_sum_to_the_process_totals(clean_ring):
    before = monitor.compile_phases()
    _, call = _train_net()
    _past_the_first_calls(call)
    call(5)  # two misses past the first calls
    call(6)
    after = monitor.compile_phases()
    misses = clean_ring.events(kind="executor.compile")
    assert len(misses) == 4
    for k in ("trace_s", "lower_s", "backend_s"):
        total = after[k] - before[k]
        # a hit re-traces the step key's fold_in (tens of microseconds)
        assert sum(e[k] for e in misses) == pytest.approx(total, rel=0.01), k
        assert sum(e[k] for e in misses) <= total + 1e-5


def test_a_first_call_with_tracing_off_reads_no_device_counter(clean_ring,
                                                               monkeypatch):
    from paddle_tpu.core import executor as ex

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        loss = layers.reduce_mean(layers.fc(x, size=1))
        seen = layers.reduce_sum(x)
        pt.optimizer.SGD(1e-2).minimize(loss)
    monitor.device_counter(prog, "feed_sum", seen)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)

    class Numpy:
        """core/executor.py's numpy, counting what it turns from a device
        array into a host one."""
        reads = 0

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, v, *args, **kwargs):
            Numpy.reads += isinstance(v, jax.Array)
            return np.asarray(v, *args, **kwargs)

    monkeypatch.setattr(ex, "np", Numpy())
    feed = {"x": np.ones((STEPS, 4, 8), "float32")}
    for _ in range(2):  # the miss and a hit, both in the first-calls record
        assert exe.run_steps(prog, feed=feed, fetch_list=[],
                             scope=scope) == []
    assert Numpy.reads == 0
    evs = clean_ring.events()
    assert len(evs) == 3 and not any("counters" in e for e in evs)
    FLAGS.monitor = True
    exe.run_steps(prog, feed=feed, fetch_list=[], scope=scope)
    assert Numpy.reads == 1
    assert clean_ring.events()[-1]["counters"] == {"feed_sum": 32.0}


# (c) ------------------------------------------------------------------------


def test_compile_phases_count_inside_executor_calls_only(clean_ring):
    _, call = _train_net()
    before = monitor.compile_phases()
    call()  # the miss
    miss = monitor.compile_phases()
    for k in ("trace_s", "lower_s", "backend_s"):
        assert miss[k] > before[k], k
    call()  # settles whatever small eager op a second call still meets
    hit0 = monitor.compile_phases()
    call()
    hit1 = monitor.compile_phases()
    # nothing is lowered or compiled on a hit.  (jax re-traces the step
    # key's `fold_in` under its vmap on every call: some tens of
    # microseconds, which is why `trace_s` is held loosely here.)
    assert {k: v for k, v in hit1.items() if k != "trace_s"} == {
        k: v for k, v in hit0.items() if k != "trace_s"}
    assert 0 <= hit1["trace_s"] - hit0["trace_s"] < 1e-3
    jax.jit(lambda a: a * 3 + 1)(np.ones(5, "float32"))
    assert monitor.compile_phases() == hit1
    assert set(hit0) == {"trace_s", "lower_s", "backend_s", "cache_load_s",
                         "cache_hits", "cache_misses", "grad_direct",
                         "grad_generic", "qkv_bwd_composed",
                         "attn_tiles_visited", "attn_tiles_total",
                         "short_conv_sites_kernel", "short_conv_sites_xla",
                         "bthd_sites_kernel", "bthd_sites_xla",
                         "kda_sites_kernel", "kda_sites_xla",
                         "kda_bwd_sites_kept"}
    # the net's grad ops have no lowering of their own: all went through
    # the generic vjp, once each, in the miss call's trace and nowhere else
    assert miss["grad_generic"] - before["grad_generic"] > 0
    assert miss["grad_direct"] == before["grad_direct"]


def test_a_nested_trace_is_counted_once():
    """A jit traced while another is traced reports first and lies
    inside the outer one's duration."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    with flight.executor_call():
        t0 = monitor.compile_phases()["trace_s"]
        flight._on_compile_duration(event, 0.001)   # a sibling, long ago
        time.sleep(0.02)
        flight._on_compile_duration(event, 0.004)   # nested in the next
        flight._on_compile_duration(event, 0.010)   # the outer one
        assert monitor.compile_phases()["trace_s"] - t0 == pytest.approx(
            0.011)
    flight._on_compile_duration(event, 5.0)  # outside any Executor call
    assert monitor.compile_phases()["trace_s"] - t0 == pytest.approx(0.011)


# (d) ------------------------------------------------------------------------


def test_every_pallas_kernel_has_a_name():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import lint_rules
    finally:
        sys.path.pop(0)
    names = {}
    for path in sorted(glob.glob(
            os.path.join(REPO, "paddle_tpu", "kernels", "*.py"))):
        tree = ast.parse(open(path).read(), filename=path)
        assert lint_rules.check_file(path, lint_rules.declared_flags()) == []
        for site, name in lint_rules.pallas_call_names(tree):
            assert name is not None, f"{path}:{site}: pallas_call unnamed"
            assert name not in names, f"{name}: {names.get(name)} and {path}"
            names[name] = f"{os.path.basename(path)}:{site}"
    assert len(names) >= 22
    for name in names:
        assert ("_fwd" in name) != ("_bwd" in name), name
    assert {"flash_bthd_fwd", "flash_bthd_bwd_dq",
            "flash_bthd_bwd_dkv"} <= set(names)
    # no fused-qkv kernel is left (the forward one was deleted in PR 30,
    # the backward walks in PR 28): a fused_qkv_attention site is the bthd
    # kernels between XLA projection dots, both ways
    assert not [n for n in names if n.startswith("fused_qkv")]


def test_the_lint_refuses_an_unnamed_or_two_faced_kernel(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import lint_rules
    finally:
        sys.path.pop(0)
    d = tmp_path / "paddle_tpu" / "kernels"
    d.mkdir(parents=True)
    bad = d / "k.py"
    bad.write_text(
        "from jax.experimental import pallas as pl\n"
        "a = pl.pallas_call(k, grid=(1,))\n"
        "b = pl.pallas_call(k, name='x_fwd_bwd', grid=(1,))\n"
        "c = pl.pallas_call(k, name=some_variable, grid=(1,))\n"
        "d = pl.pallas_call(k, name=f'flash_{fmt}_fwd', grid=(1,))\n"
        "e = pl.pallas_call(k, name='ok_bwd_dq', grid=(1,))\n"
        "f = pl.pallas_call(k, name='ok_bwd_dq', grid=(1,))\n")
    msgs = [m for _, _, m in lint_rules.check_file(str(bad), set())]
    assert len(msgs) == 4 and all("kernel-named" in m for m in msgs)


# (e) ------------------------------------------------------------------------


def test_lowered_text_names_each_op_type():
    from paddle_tpu.core.executor import latest_jitted_entry, prng_key

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.layer_norm(layers.fc(x, size=16, act="relu"))
        loss = layers.reduce_mean(layers.square(layers.fc(h, size=1) - y))
        pt.optimizer.Adam(1e-3).minimize(loss)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((STEPS, 4, 8), "float32"),
            "y": np.ones((STEPS, 4, 1), "float32")}
    exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    entry = latest_jitted_entry(exe)
    # re-lower the executed computation (nothing runs, nothing is donated)
    lowered = entry.jitted.lower(
        [feed[n] for n in sorted(feed)],
        [scope.find_var(n) for n in entry.rw_state],
        [scope.find_var(n) for n in entry.ro_state],
        jax.random.fold_in(prng_key(0), 1))
    text = lowered.as_text(debug_info=True)
    types = {op.type for op in prog.global_block().ops}
    # (an op that lowers to nothing, as `elementwise_sub_grad` does here,
    # leaves no location to name)
    emitting = {"mul", "relu", "layer_norm", "layer_norm_grad", "mul_grad",
                "relu_grad", "square", "elementwise_add", "adam",
                "reduce_mean"}
    assert emitting <= types
    for t in emitting:
        assert f'loc("{t}/' in text, t


# the operator's table ---------------------------------------------------------


def _vint(v):
    out = b""
    while True:
        b7, v = v & 0x7F, v >> 7
        if not v:
            return out + bytes([b7])
        out += bytes([b7 | 0x80])


def _f(num, payload):
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(payload, int):
        return _vint(num << 3) + _vint(payload)
    return _vint((num << 3) | 2) + _vint(len(payload)) + payload


def _device_plane(ops):
    """A TPU plane whose `XLA Ops` line holds `ops`: (HLO text, tf_op,
    offset_ps, duration_ps)."""
    tf_op_id = 9
    body = _f(2, b"/device:TPU:0") + _f(5, _f(1, tf_op_id) + _f(
        2, _f(1, tf_op_id) + _f(2, b"tf_op")))
    events = b""
    for i, (text, tf_op, off, dur) in enumerate(ops, start=1):
        meta = _f(1, i) + _f(2, text.encode())
        if tf_op:
            meta += _f(5, _f(1, tf_op_id) + _f(5, tf_op.encode()))
        body += _f(4, _f(1, i) + _f(2, meta))
        events += _f(4, _f(1, i) + _f(2, off) + _f(3, dur))
    return body + _f(3, _f(2, b"XLA Ops") + events)


def test_op_table_groups_by_scope_and_by_kernel(tmp_path, capsys):
    from paddle_tpu import profiler

    body = "jit(scan_fn)/while/body/closed_call/"
    mosaic = ' = (bf16[8]) custom-call(%x), custom_call_target="tpu_custom_call"'
    ops = [
        ("%while.1 = (s32[]) while(%t)", "jit(scan_fn)/while", 0, 100_000),
        ("%fusion.1 = f32[8] fusion(%a)", body + "adam/mul:", 0, 10_000),
        ("%fusion.2 = f32[8] fusion(%a)", body + "adam/sqrt:", 10_000, 5_000),
        ("%fusion.3 = f32[8] fusion(%a)",
         body + "layer_norm_grad/transpose(jvp())/mul:", 20_000, 20_000),
        ("%flash_bthd_fwd.4" + mosaic,
         body + "fused_qkv_attention/pallas_call:", 40_000, 30_000),
        ("%jvp_flash_bthd_bwd_dq_.9" + mosaic,
         body + "fused_qkv_attention_grad/transpose(jvp())/pallas_call:",
         70_000, 25_000),
        ("%copy.7 = f32[8] copy(%p)", body + "mul", 95_000, 4_000),
        ("%bitcast.1 = f32[8] bitcast(%p)", "", 99_000, 1_000),
    ]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_f(1, _device_plane(ops)))

    scope = dict(profiler.xplane_op_table(str(tmp_path), None, by="scope"))
    # the `while` holds its body and is no leaf: nothing is counted twice
    assert sum(scope.values()) == pytest.approx(95_000 / 1e12)
    assert scope["adam"] == pytest.approx(15_000 / 1e12)
    assert scope["layer_norm_grad"] == pytest.approx(20_000 / 1e12)
    assert scope["fused_qkv_attention"] == pytest.approx(30_000 / 1e12)
    # a jax `mul` under no op's scope is not the op `mul`
    assert scope[profiler.NO_SCOPE] == pytest.approx(5_000 / 1e12)
    kernels = dict(profiler.xplane_op_table(str(tmp_path), by="kernel"))
    assert kernels == {"flash_bthd_fwd": pytest.approx(30_000 / 1e12),
                       "jvp_flash_bthd_bwd_dq_": pytest.approx(
                           25_000 / 1e12)}
    # the table that was there: name prefixes, containers included
    group = dict(profiler.xplane_op_table(str(tmp_path)))
    assert group["%fusion"] == pytest.approx(35_000 / 1e12)
    assert group["%while"] == pytest.approx(100_000 / 1e12)
    rows = profiler.print_op_table(str(tmp_path), 2, by="scope")
    assert [n for n, _ in rows] == ["fused_qkv_attention",
                                    "fused_qkv_attention_grad"]
    out = capsys.readouterr().out
    assert "Op type (scope)" in out and "31.6%" in out
    with pytest.raises(ValueError):
        profiler.xplane_op_table(str(tmp_path), by="nothing")


def test_profiler_prints_the_executor_calls_it_ran(clean_ring, capsys):
    from paddle_tpu import profiler

    _, call = _train_net()
    _past_the_first_calls(call)  # before the session: not in its table
    profiler.start_profiler()
    assert monitor.spans_on()  # a session with no jax trace turns them on
    call(5)  # a miss
    call(5)
    call(5)
    rows = profiler.stop_profiler(sorted_key="calls")
    assert not monitor.spans_on()
    table = {name: rest for name, *rest in rows}
    assert sorted(table) == sorted(
        ["executor.run_steps"] + ["executor." + p for p in MISS_PHASES])
    assert table["executor.run_steps"][0] == 3
    assert table["executor.compile"][0] == 1  # the miss's phase
    assert [table["executor." + p][0] for p in HIT_PHASES] == [3] * 6
    evs = clean_ring.events()[-3:]
    calls, total, ave, mx = table["executor.dispatch"]
    spent = [d for e in evs for n, _, d in e["phases"] if n == "dispatch"]
    assert total == pytest.approx(sum(spent)) and mx == max(spent)
    assert ave == pytest.approx(total / 3)
    assert table["executor.run_steps"][1] == pytest.approx(
        sum(e["dur"] for e in evs))
    out = capsys.readouterr().out
    assert out.splitlines()[0].startswith("Event") and len(
        out.splitlines()) == 1 + len(rows)
    assert "executor.run_steps" in out


def test_trace_report_attribution_reads_the_phases():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)

    def ev(kind, fetch):
        return {"kind": kind, "t0": 1.0, "dur": 0.004 + fetch, "phases": [
            ["feed", 0.0, 0.001], ["key", 0.001, 0.001],
            ["gather", 0.002, 0.001], ["dispatch", 0.003, 0.001],
            ["writeback", 0.004, 0.0], ["fetch", 0.004, fetch]]}

    doc = {"flight": {"events": [
        ev("executor.run_steps", 0.5), ev("executor.run", 0.496),
        ev("executor.compile", 30.0),  # a compile is no run
        {"kind": "executor.run", "t0": 1.0, "dur": 1.0}]}}  # an old event
    phases, n = trace_report.phase_split(doc)
    assert n == 2 and phases["fetch"] == pytest.approx(0.996)
    assert list(phases) == HIT_PHASES
    text = trace_report.report(doc, 5)
    assert ("executor split over 2 runs: dispatch 0.0080s vs device-wait "
            "0.9960s") in text
    assert "host phases: feed 0.0020s  key 0.0020s" in text
