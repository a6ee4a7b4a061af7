"""One way to execute a program (core/executor.py `_call`): every call
mode (`run`, `run_steps`, `run_accumulated`) and every layout (a plain
Program, a data-parallel CompiledProgram, a ShardedProgram on the 8-device
CPU mesh) goes through the same host path, so each combination behaves
like the others: same results, same run id in the step key, same locks,
same cache key, same instruments."""

import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, monitor
from paddle_tpu.flags import FLAGS
from paddle_tpu.parallel.sharding import ShardedProgram, ShardingPlan

K, BATCH, WIDTH = 4, 16, 32
# chip_smoke.py's sharded leg holds its loss trajectory to this
TOL_SHARDED_LOSS = 2e-3
LAYOUTS = ["plain", "data_parallel", "sharded"]


def _net(dropout=0.0, seed=7):
    prog, startup = pt.Program(), pt.Program()
    prog.random_seed = startup.random_seed = seed
    with pt.program_guard(prog, startup):
        with pt.core.framework.guard_unique_name():
            x = layers.data(name="x", shape=[WIDTH], dtype="float32")
            y = layers.data(name="y", shape=[1], dtype="float32")
            h = layers.fc(x, size=64, act="relu",
                          param_attr=pt.ParamAttr(name="fc1_w"),
                          bias_attr=pt.ParamAttr(name="fc1_b"))
            if dropout:
                h = layers.dropout(h, dropout_prob=dropout)
            out = layers.fc(h, size=1, param_attr=pt.ParamAttr(name="fc2_w"),
                            bias_attr=pt.ParamAttr(name="fc2_b"))
            loss = layers.reduce_mean(layers.square(out - y))
            pt.optimizer.SGD(0.05).minimize(loss)
    return prog, startup, loss


def _wrap(layout, prog, loss):
    from jax.sharding import PartitionSpec as P

    if layout == "data_parallel":
        return pt.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
    if layout == "sharded":
        plan = ShardingPlan(
            mesh_axes={"data": 2, "model": 4}, zero_stage=1,
            param_rules=[("fc1_w", P(None, "model")), ("fc1_b", P("model")),
                         ("fc2_w", P("model", None))])
        return ShardedProgram(prog, plan, loss_name=loss.name)
    return prog


def _feeds(k=K, seed=3):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(k, BATCH, WIDTH).astype("float32"),
            "y": rng.rand(k, BATCH, 1).astype("float32")}


def _started(startup):
    """A fresh executor and scope after start-up: the run id stands at 1
    whichever way the program is then run."""
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)
    return exe, scope


def _params(prog, scope):
    return {p.name: np.asarray(scope.find_var(p.name))
            for p in prog.all_parameters()}


# the parity of the modes and layouts ----------------------------------------


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("mode", ["run_steps", "run_accumulated"])
def test_stacked_modes_match_single_runs(mode, layout):
    """No random op: `run_steps(steps=k)` is k calls of `run`, and
    `run_accumulated` over k micro-batches one `run` of the whole batch
    (a mean loss under SGD), from the same state, on every layout."""
    prog, startup, loss = _net()
    feeds = _feeds()
    exe, scope = _started(startup)
    if mode == "run_steps":
        want = [float(exe.run(prog, feed={n: v[s] for n, v in feeds.items()},
                              fetch_list=[loss], scope=scope)[0])
                for s in range(K)]
    else:
        whole = {n: v.reshape((K * BATCH,) + v.shape[2:])
                 for n, v in feeds.items()}
        want = float(exe.run(prog, feed=whole, fetch_list=[loss],
                             scope=scope)[0])
    want_params = _params(prog, scope)

    exe, scope = _started(startup)
    target = _wrap(layout, prog, loss)
    (got,) = getattr(exe, mode)(target, feed=feeds, fetch_list=[loss],
                                scope=scope)
    assert np.asarray(got).shape[0] == K
    if mode == "run_accumulated":
        got = np.mean(got)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    for name, value in _params(prog, scope).items():
        np.testing.assert_allclose(value, want_params[name], rtol=1e-4,
                                   atol=1e-6, err_msg=name)
    if layout == "sharded":
        assert not scope.find_var("fc1_w").sharding.is_fully_replicated


@pytest.mark.parametrize("layout", ["data_parallel", "sharded"])
@pytest.mark.parametrize("mode", ["run", "run_steps"])
def test_a_wrapped_programs_masks_are_the_plain_programs(mode, layout):
    """With dropout (the hash masks, which no layout changes): a wrapped
    program folds the executor's run id like the plain one, so the same
    sequence of calls on one executor draws the same masks."""
    prog, startup, loss = _net(dropout=0.5)
    feeds = _feeds()

    def losses(target):
        exe, scope = _started(startup)
        if mode == "run_steps":
            return np.asarray(exe.run_steps(
                target, feed=feeds, fetch_list=[loss], scope=scope)[0])
        return np.asarray([
            exe.run(target, feed={n: v[s] for n, v in feeds.items()},
                    fetch_list=[loss], scope=scope)[0] for s in range(K)])

    plain, wrapped = losses(prog), losses(_wrap(layout, prog, loss))
    assert np.all(np.abs(wrapped - plain) / np.abs(plain) <= TOL_SHARDED_LOSS)
    # (the masks matter: a stream that started elsewhere leaves the bound)
    exe, scope = _started(startup)
    exe.run(startup, scope=pt.Scope())  # one more run id drawn
    shifted = exe.run_steps(prog, feed=feeds, fetch_list=[loss],
                            scope=scope)[0]
    assert np.max(np.abs(shifted - plain) / np.abs(plain)) > TOL_SHARDED_LOSS


def test_a_resumed_sharded_run_continues_the_mask_stream(tmp_path):
    """The run id a sharded call folds is the executor's, which the
    checkpoint holds: steps 3-4 after a resume are steps 3-4."""
    prog, startup, loss = _net(dropout=0.5)
    feeds = _feeds()

    def step(exe, target, scope, s):
        return float(exe.run(target, feed={n: v[s] for n, v in feeds.items()},
                             fetch_list=[loss], scope=scope)[0])

    exe, scope = _started(startup)
    target = _wrap("sharded", prog, loss)
    mgr = pt.io.CheckpointManager(str(tmp_path), exe, interval_steps=1,
                                  main_program=prog, scope=scope)
    for s in range(2):
        step(exe, target, scope, s)
    mgr.on_step(1)
    mgr.wait()
    uninterrupted = [step(exe, target, scope, s) for s in (2, 3)]

    exe2, scope2 = pt.Executor(pt.CPUPlace()), pt.Scope()
    mgr2 = pt.io.CheckpointManager(str(tmp_path), exe2, interval_steps=1,
                                   main_program=prog, scope=scope2)
    assert mgr2.resume() == 2
    target2 = _wrap("sharded", prog, loss)
    resumed = [step(exe2, target2, scope2, s) for s in (2, 3)]
    np.testing.assert_allclose(resumed, uninterrupted, rtol=1e-5)


# locks ----------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["run_steps", "run_accumulated"])
def test_a_stacked_call_waits_for_the_stateful_lock(mode):
    """A stateful entry donates the scope's arrays: no second call may
    gather them while one is between gather and write-back."""
    prog, startup, loss = _net()
    exe, scope = _started(startup)
    call = lambda: getattr(exe, mode)(  # noqa: E731
        prog, feed=_feeds(), fetch_list=[loss], scope=scope)
    call()  # compiled: the thread below only has to run
    done = threading.Event()
    worker = threading.Thread(target=lambda: (call(), done.set()))
    with exe._stateful_lock:
        worker.start()
        assert not done.wait(0.5), f"{mode} ran through the stateful lock"
    worker.join(timeout=60)
    assert done.is_set() and not worker.is_alive()


def test_two_threads_missing_on_one_signature_compile_once(monkeypatch):
    import time

    prog, startup, loss = _net()
    exe, scope = _started(startup)
    compiles = []
    real = exe._compile

    def slow_compile(*args, **kw):
        compiles.append(args[0].name)
        time.sleep(0.3)  # the other thread reaches the look-up meanwhile
        return real(*args, **kw)

    monkeypatch.setattr(exe, "_compile", slow_compile)
    start, errors = threading.Barrier(2), []

    def work():
        try:
            start.wait(timeout=30)
            exe.run_steps(prog, feed=_feeds(), fetch_list=[loss],
                          scope=scope)
        except Exception as e:  # noqa: BLE001 — read below
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert errors == []
    assert compiles == ["run_steps"]


# the cache key --------------------------------------------------------------


def test_run_accumulated_keys_on_is_test():
    prog, startup, loss = _net(dropout=0.5)
    exe, scope = _started(startup)
    feeds, start = _feeds(), _params(prog, scope)

    def call():
        for name, value in start.items():
            scope.set_var(name, value)
        return np.asarray(exe.run_accumulated(
            prog, feed=feeds, fetch_list=[loss], scope=scope)[0])

    train = [call(), call()]
    assert not np.allclose(train[0], train[1])  # another run id, other masks
    n = len(exe._cache)
    prog._is_test = True
    try:
        evals = [call(), call()]
    finally:
        prog._is_test = False
    assert len(exe._cache) == n + 1
    # no mask in test mode: the run id no longer shows
    np.testing.assert_array_equal(evals[0], evals[1])
    call()
    assert len(exe._cache) == n + 1


def test_the_detector_names_the_count(clean_ring):
    FLAGS.monitor = True
    prog, startup, loss = _net()
    exe, scope = _started(startup)
    for k in (2, 2, 3):
        exe.run_steps(prog, feed=_feeds(k), fetch_list=[loss], scope=scope)
    (ev,) = clean_ring.events(kind="executor.recompile")
    assert ev["changed"] == ["count", "feed-signature"]


def test_use_program_cache_is_without_effect():
    prog, startup, loss = _net()
    exe, scope = _started(startup)
    one = {n: v[0] for n, v in _feeds().items()}
    n = len(exe._cache)
    for _ in range(2):
        exe.run(prog, feed=one, fetch_list=[loss], scope=scope,
                use_program_cache=False)
    assert len(exe._cache) == n + 1


# instruments ----------------------------------------------------------------


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_scope_signature_is_in_every_layouts_key(layout):
    """What the program writes back depends on what the scope holds, so a
    scope that holds more is another executable."""
    prog, startup, loss = _net()
    exe, scope = _started(startup)
    target = _wrap(layout, prog, loss)
    one = {n: v[0] for n, v in _feeds().items()}
    exe.run(target, feed=one, fetch_list=[loss], scope=scope)
    assert scope.find_var(loss.name) is None
    scope.set_var(loss.name, np.zeros((), "float32"))
    (got,) = exe.run(target, feed=one, fetch_list=[loss], scope=scope)
    assert float(np.asarray(scope.find_var(loss.name))) == float(got) != 0.0


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("mode", ["run", "run_steps"])
def test_check_nan_inf_holds_on_every_layout(mode, layout):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        loss = layers.mean(layers.log(layers.fc(x, size=8)))
        pt.optimizer.SGD(0.1).minimize(loss)
    exe, scope = pt.Executor(pt.CPUPlace(), check_nan_inf=True), pt.Scope()
    exe.run(startup, scope=scope)
    target = _wrap(layout, prog, loss)
    feed = -np.ones((8, 8) if mode == "run" else (2, 8, 8), "float32")
    with pytest.raises(FloatingPointError, match="log"):
        getattr(exe, mode)(target, feed={"x": feed}, fetch_list=[loss],
                           scope=scope)
    # the state was written back before the raise, wherever it lives
    for p in prog.all_parameters():
        np.asarray(scope.find_var(p.name))


@pytest.mark.parametrize("mode", ["run", "run_steps", "run_accumulated"])
def test_device_counters_ride_every_mode(clean_ring, mode):
    FLAGS.monitor = True
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        loss = layers.reduce_mean(layers.fc(x, size=1))
        seen = layers.reduce_sum(x)
        pt.optimizer.SGD(1e-2).minimize(loss)
    monitor.device_counter(prog, "feed_sum", seen)
    exe, scope = _started(startup)
    feed = np.ones((4, 8), "float32") if mode == "run" \
        else np.ones((3, 4, 8), "float32")
    for _ in range(2):
        outs = getattr(exe, mode)(prog, feed={"x": feed}, fetch_list=[loss],
                                  scope=scope)
        assert len(outs) == 1  # the counter is no fetch of the caller's
    (ev,) = clean_ring.events(kind=f"executor.{mode}")[-1:]
    assert ev["counters"] == {"feed_sum": 32.0}


# Executor.lower -------------------------------------------------------------


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("steps", [None, K])
def test_lower_gives_the_calls_executable_and_draws_no_run_id(steps, layout):
    prog, startup, loss = _net(dropout=0.5)
    exe, scope = _started(startup)
    target = _wrap(layout, prog, loss)
    feeds = _feeds()
    feed = feeds if steps else {n: v[0] for n, v in feeds.items()}
    rid = exe._run_counter
    lowered = exe.lower(target, feed, [loss], scope, steps=steps)
    assert exe._run_counter == rid
    before = _params(prog, scope)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert ("while" in text) == bool(steps)
    collectives = sum(text.count(f" {op}(") + text.count(f" {op}-start(")
                      for op in ("all-reduce", "all-gather",
                                 "reduce-scatter"))
    assert (collectives > 0) == (layout != "plain")
    # nothing ran and nothing was donated
    for name, value in _params(prog, scope).items():
        np.testing.assert_array_equal(value, before[name])
    # the call that follows finds the entry `lower` compiled
    n = len(exe._cache)
    (exe.run_steps if steps else exe.run)(
        target, feed=feed, fetch_list=[loss], scope=scope)
    assert len(exe._cache) == n
