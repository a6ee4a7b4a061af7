"""Telemetry subsystem tests (tier-1, no TPU): metrics-registry semantics,
executor instrumentation + the recompile detector, StepMonitor JSONL,
data-feed / inference metrics, and the hash_rng uint32 wrap guard."""

import json
import logging
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.flags import FLAGS
from paddle_tpu.monitor import (
    Counter,
    Histogram,
    MetricsRegistry,
    StepMonitor,
    default_registry,
)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Each test starts with default flags and an empty default registry."""
    FLAGS.reset()
    default_registry().reset()
    yield
    FLAGS.reset()
    default_registry().reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("a.calls")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("a.depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value == 5.0
        # get-or-create returns the same object; kind mismatch raises
        assert reg.counter("a.calls") is c
        with pytest.raises(TypeError):
            reg.gauge("a.calls")

    def test_histogram_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(5.56)
        # cumulative le counts: 0.01->2, 0.1->3, 1.0->4, +Inf->5
        assert snap["buckets"] == [[0.01, 2], [0.1, 3], [1.0, 4],
                                   [float("inf"), 5]]
        # boundary lands in its own bucket (le semantics)
        h2 = reg.histogram("lat2", buckets=(1.0, 2.0))
        h2.observe(1.0)
        assert h2.snapshot()["buckets"][0] == [1.0, 1]
        with pytest.raises(ValueError):
            reg.histogram("bad", buckets=(2.0, 1.0))

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("executor.cache_miss").inc(3)
        reg.histogram("req.seconds", buckets=(0.1, 1.0)).observe(0.05)
        text = reg.prometheus_text()
        assert "# TYPE executor_cache_miss counter" in text
        assert "executor_cache_miss 3" in text
        assert '# TYPE req_seconds histogram' in text
        assert 'req_seconds_bucket{le="0.1"} 1' in text
        assert 'req_seconds_bucket{le="+Inf"} 1' in text
        assert "req_seconds_count 1" in text

    def test_jsonl_exposition(self):
        reg = MetricsRegistry()
        reg.counter("n.calls").inc()
        reg.gauge("n.depth").set(2)
        lines = [json.loads(l) for l in reg.jsonl().splitlines()]
        by_name = {r["metric"]: r for r in lines}
        assert by_name["n.calls"]["type"] == "counter"
        assert by_name["n.calls"]["value"] == 1
        assert by_name["n.depth"]["value"] == 2
        assert all("ts" in r for r in lines)

    def test_quantile_inf_bucket_clamps_to_max_observed(self):
        """Regression (ISSUE 14 satellite): one outlier past the top
        bucket bound used to make quantile() return +Inf — /v1/models
        then reported "p99": Infinity.  The +Inf tail now clamps to the
        largest OBSERVED value."""
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
        for _ in range(99):
            h.observe(0.005)
        h.observe(50.0)  # single outlier beyond the last bound
        assert h.quantile(0.5) == 0.01
        p99 = h.quantile(0.999)
        assert p99 == 50.0 and p99 != float("inf")
        assert h.max == 50.0
        # every observation past the top bound: still finite
        h2 = reg.histogram("lat2", buckets=(0.01,))
        h2.observe(3.0)
        h2.observe(7.0)
        assert h2.quantile(0.5) == 7.0
        assert h2.quantile(0.99) == 7.0
        # in-range behavior unchanged: bucket upper bound
        h3 = reg.histogram("lat3", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5):
            h3.observe(v)
        assert h3.quantile(0.99) == 1.0
        # max rides the snapshot for artifact consumers
        assert h3.snapshot()["max"] == 0.5

    def test_collect_hooks_run_at_snapshot(self):
        reg = MetricsRegistry()
        calls = []

        def hook():
            calls.append(1)
            reg.gauge("derived.g").set(42)

        reg.add_collect_hook(hook)
        reg.add_collect_hook(hook)  # idempotent
        text = reg.prometheus_text()
        assert calls == [1]
        assert "derived_g 42" in text

        def broken():
            raise RuntimeError("must not fail the scrape")

        reg.add_collect_hook(broken)
        assert "derived_g" in reg.prometheus_text()
        reg.remove_collect_hook(hook)
        reg.remove_collect_hook(broken)
        calls.clear()
        reg.snapshot()
        assert calls == []

    def test_slo_tracker_windows_and_burn_rate(self):
        from paddle_tpu.monitor import SloTracker

        tr = SloTracker("m", objective_ms=100.0, target=0.9)
        t0 = 1_000_000.0
        for _ in range(8):
            tr.observe(True, now=t0)
        for _ in range(2):
            tr.observe(False, now=t0)
        # 20% bad against a 10% budget -> burn rate 2.0
        assert tr.burn_rate(300, now=t0 + 5) == pytest.approx(2.0)
        assert tr.good_total == 8 and tr.bad_total == 2
        # the bad events age out of the 5m window but stay in the 1h one
        for _ in range(10):
            tr.observe(True, now=t0 + 1000)
        assert tr.burn_rate(300, now=t0 + 1000) == pytest.approx(0.0)
        assert tr.burn_rate(3600, now=t0 + 1000) == pytest.approx(1.0)
        # empty window burns nothing
        assert tr.burn_rate(300, now=t0 + 10_000) == 0.0

    def test_thread_safety_smoke(self):
        reg = MetricsRegistry()
        c = reg.counter("smoke.calls")
        h = reg.histogram("smoke.lat", buckets=(0.5,))
        n_threads, per = 8, 2000

        def work():
            for i in range(per):
                c.inc()
                h.observe((i % 10) / 10.0)

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n_threads * per
        assert h.count == n_threads * per
        assert h.snapshot()["buckets"][-1][1] == n_threads * per


# ---------------------------------------------------------------------------
# executor instrumentation + recompile detector
# ---------------------------------------------------------------------------


def _build_train_net():
    x = layers.data(name="x", shape=[8], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    loss = layers.mean(layers.square(pred - y))
    pt.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return loss


def _feed(bs=4, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(bs, 8).astype("float32"),
            "y": rng.randn(bs, 1).astype("float32")}


class TestExecutorTelemetry:
    def test_training_loop_counters_and_jsonl(self, tmp_path):
        """The acceptance-criteria loop: nonzero compile/run counters, a
        cache miss->hit transition, and a populated step-telemetry JSONL."""
        FLAGS.monitor = True
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())

        jsonl = tmp_path / "steps.jsonl"
        mon = StepMonitor(name="loop", examples_per_step=4,
                          jsonl_path=str(jsonl))
        mon.step()  # arm the timer
        feed = _feed()
        for _ in range(3):
            (lv,) = exe.run(feed=feed, fetch_list=[loss])
            mon.step(loss=float(np.asarray(lv).reshape(-1)[0]))
        mon.close()

        reg = default_registry()
        # compile/run counters nonzero (startup + train program compiles)
        assert reg.get("executor.compiles").value >= 2
        assert reg.get("executor.run.calls").value == 4
        # run_seconds holds cache-HIT calls only (startup + first train
        # call were compiles and land in compile_seconds instead)
        assert reg.get("executor.run_seconds").count == 2
        assert reg.get("executor.compile_seconds").count >= 2
        # miss -> hit transition: both sides populated
        assert reg.get("executor.cache_miss").value >= 2
        assert reg.get("executor.cache_hit").value >= 2
        # transfer byte counters moved
        assert reg.get("executor.feed_bytes").value > 0
        assert reg.get("executor.fetch_bytes").value > 0
        # no recompile storm: same key all loop -> no recompiles metric
        assert reg.get("executor.recompiles") is None

        recs = [json.loads(l) for l in jsonl.read_text().splitlines()]
        assert len(recs) == 3
        assert recs[0]["metric"] == "loop.step"
        assert recs[0]["unit"] == "examples/sec"
        assert recs[0]["value"] > 0
        assert "loss" in recs[-1] and "step_seconds" in recs[-1]
        assert reg.get("loop.steps").value == 3

    def test_recompile_detector_names_feed_signature(self, caplog):
        FLAGS.monitor = True
        FLAGS.vlog = 1
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        exe.run(feed=_feed(bs=4), fetch_list=[loss])  # miss (compile)
        exe.run(feed=_feed(bs=4), fetch_list=[loss])  # hit
        with caplog.at_level(logging.INFO, logger="paddle_tpu"):
            # forced feed-signature change: new batch size -> cache miss
            exe.run(feed=_feed(bs=2), fetch_list=[loss])
        msgs = [r.getMessage() for r in caplog.records
                if "recompile" in r.getMessage()]
        assert msgs, "recompile detector logged nothing"
        assert "feed-signature" in msgs[-1]
        # the unchanged components are NOT blamed
        assert "program-stamp" not in msgs[-1]
        assert "fetch-list" not in msgs[-1]
        assert default_registry().get("executor.recompiles").value == 1

    def test_recompile_storm_counts_every_miss(self):
        """A ragged-shape loop must count EVERY recompile of the storm,
        not just the first miss-after-hit; a first-compile burst (misses
        before anything ever hit) must count none."""
        FLAGS.monitor = True
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())     # miss (burst)
        exe.run(feed=_feed(bs=4), fetch_list=[loss])   # miss (burst)
        assert default_registry().get("executor.recompiles") is None
        exe.run(feed=_feed(bs=4), fetch_list=[loss])   # hit
        for bs in (2, 3, 5, 6):                        # 4-miss storm
            exe.run(feed=_feed(bs=bs), fetch_list=[loss])
        assert default_registry().get("executor.recompiles").value == 4
        # a hit ends the storm; the next first-compile is not a recompile
        exe.run(feed=_feed(bs=6), fetch_list=[loss])   # hit
        exe.run(feed=_feed(bs=7), fetch_list=[loss])   # miss-after-hit
        assert default_registry().get("executor.recompiles").value == 5

    def test_fetch_list_change_named(self, caplog):
        FLAGS.monitor = True
        FLAGS.vlog = 1
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        exe.run(feed=_feed(), fetch_list=[loss])
        exe.run(feed=_feed(), fetch_list=[loss])
        with caplog.at_level(logging.INFO, logger="paddle_tpu"):
            exe.run(feed=_feed(), fetch_list=[])
        msgs = [r.getMessage() for r in caplog.records
                if "recompile" in r.getMessage()]
        assert msgs and "fetch-list" in msgs[-1]

    def test_monitor_off_no_registry_writes(self):
        """Flag off (default): the executor hot path must not touch the
        registry at all."""
        assert FLAGS.monitor is False
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        for _ in range(2):
            exe.run(feed=_feed(), fetch_list=[loss])
        assert default_registry().names() == []

    def test_delegated_program_coarse_telemetry(self):
        """A program that runs itself (the pipeline programs' _run hook)
        gets coarse call/wall-time metrics round the delegation; a
        CompiledProgram is no such program any more: it unwraps into the
        one call path and gets the full instrumentation."""
        FLAGS.monitor = True
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        main = pt.default_main_program()

        class RunsItself:
            def _run(self, executor, feed, fetch_list, scope, return_numpy):
                return executor.run(main, feed, fetch_list, scope,
                                    return_numpy)

        exe.run(RunsItself(), feed=_feed(), fetch_list=[loss])
        reg = default_registry()
        assert reg.get("executor.delegated.calls").value == 1
        assert reg.get("executor.delegated_seconds").count == 1
        calls = reg.get("executor.run.calls").value
        exe.run(pt.CompiledProgram(main), feed=_feed(), fetch_list=[loss])
        assert reg.get("executor.delegated.calls").value == 1
        assert reg.get("executor.run.calls").value == calls + 1
        # the wrapper compiled nothing of its own: the inner program's hit
        assert reg.get("executor.cache_hit").value >= 1

    def test_error_counter_on_failed_run(self):
        FLAGS.monitor = True
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        with pytest.raises(Exception):
            exe.run(feed=_feed(), fetch_list=["no_such_var"])
        assert default_registry().get("executor.errors").value == 1
        # a healthy run afterwards still records normally
        exe.run(feed=_feed(), fetch_list=[loss])
        assert default_registry().get("executor.run.calls").value >= 1

    def test_run_steps_counters(self):
        FLAGS.monitor = True
        loss = _build_train_net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        feed = {k: np.stack([v, v]) for k, v in _feed().items()}
        exe.run_steps(feed=feed, fetch_list=[loss])
        exe.run_steps(feed=feed, fetch_list=[loss])
        reg = default_registry()
        assert reg.get("executor.run_steps.calls").value == 2
        assert reg.get("executor.cache_hit").value >= 1


# ---------------------------------------------------------------------------
# StepMonitor
# ---------------------------------------------------------------------------


class TestStepMonitor:
    def test_rates_and_mfu(self):
        import time

        mon = StepMonitor(name="t", examples_per_step=32,
                          tokens_per_step=64, flops_per_step=1e6,
                          peak_flops=1e12, window=4)
        assert mon.step(loss=2.0) is None  # arming call
        recs = []
        for i in range(5):
            time.sleep(0.002)  # bound dt away from 0 so mfu stays < 1
            recs.append(mon.step(loss=2.0 - 0.1 * i))
        assert all(r is not None for r in recs)
        r = recs[-1]
        assert r["unit"] == "examples/sec" and r["value"] > 0
        assert r["tokens_per_sec"] > 0
        assert 0 <= r["mfu"] <= 1.0
        assert "rolling_mfu" in r
        s = mon.summary()
        assert s["steps"] == 5 and s["examples_per_sec"] > 0
        reg = default_registry()
        assert reg.get("t.steps").value == 5
        assert reg.get("t.loss").value == pytest.approx(1.6)

    def test_cost_from_uses_xla_cost_model(self):
        """MFU FLOPs can come lazily from profiler.cost_analysis."""
        x = layers.data(name="x", shape=[64], dtype="float32")
        h = layers.fc(x, size=128, bias_attr=False)
        loss = layers.mean(h)
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        feed = {"x": np.zeros((32, 64), "float32")}
        mon = StepMonitor(
            name="c", peak_flops=1e12,
            cost_from=(pt.default_main_program(), feed, [loss]))
        assert mon.flops_per_step >= 2 * 32 * 64 * 128
        mon.step()
        rec = mon.step(loss=1.0)
        assert "mfu" in rec


# ---------------------------------------------------------------------------
# data feed + inference metrics
# ---------------------------------------------------------------------------


class TestDataFeedTelemetry:
    def _desc(self):
        from paddle_tpu.data_feed import DataFeedDesc

        desc = DataFeedDesc(batch_size=2)
        desc.add_slot("f", type="float", is_dense=True, dim=2)
        return desc

    def test_malformed_line_located_and_counted(self, tmp_path):
        from paddle_tpu.data_feed import MultiSlotDataFeed

        FLAGS.monitor = True
        path = tmp_path / "shard.txt"
        path.write_text("2 1.0 2.0\n2 3.0\n2 5.0 6.0\n")  # line 2 is short
        feed = MultiSlotDataFeed(self._desc())
        with pytest.raises(ValueError) as ei:
            list(feed.read_file(str(path)))
        msg = str(ei.value)
        assert "malformed" in msg
        # the exception names the offending content, not just a count
        assert "2 3.0" in msg or "line 2" in msg
        assert default_registry().get(
            "data_feed.malformed_lines").value >= 1

    def test_queue_gauges_populate(self, tmp_path):
        from paddle_tpu.data_feed import AsyncExecutor

        FLAGS.monitor = True
        path = tmp_path / "data.txt"
        path.write_text("".join(f"2 {i}.0 {i}.5\n" for i in range(6)))
        x = layers.data(name="f", shape=[2], dtype="float32")
        loss = layers.mean(x)
        exe = AsyncExecutor(pt.CPUPlace())
        scope = pt.Scope()
        results = exe.run_from_files(
            pt.default_main_program(), self._desc(), [str(path)],
            thread_num=1, fetch_list=[loss], scope=scope)
        assert len(results) == 3
        reg = default_registry()
        assert reg.get("data_feed.batches").value == 3
        assert reg.get("data_feed.stall_seconds").value >= 0
        assert reg.get("data_feed.queue_depth") is not None


class TestInferenceTelemetry:
    def test_request_histogram_and_qps_counter(self, tmp_path):
        from paddle_tpu.inference import Predictor

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[8], dtype="float32")
            pred = layers.fc(x, size=3, act="softmax")
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup, scope=scope)
            pt.io.save_inference_model(
                str(tmp_path / "m"), ["x"], [pred], exe,
                main_program=prog, scope=scope)

        FLAGS.monitor = True
        p = Predictor(str(tmp_path / "m"))
        feed = {"x": np.random.RandomState(0).randn(4, 8).astype("float32")}
        for _ in range(5):
            (out,) = p.run(feed)
        assert out.shape == (4, 3)
        reg = default_registry()
        assert reg.get("inference.requests").value == 5
        h = reg.get("inference.request_seconds")
        assert isinstance(h, Histogram) and h.count == 5
        assert h.sum > 0
        assert reg.get("inference.examples").value == 20

    def test_use_aot_defaults_off(self):
        """ADVICE high: bundle loading runs jax's pickle-based executable
        deserializer — it must be explicit opt-in."""
        import inspect

        from paddle_tpu.inference import Predictor

        sig = inspect.signature(Predictor.__init__)
        assert sig.parameters["use_aot"].default is False


class TestCollectiveCounters:
    def test_trace_time_byte_accounting(self):
        from paddle_tpu.parallel import distributed as dist

        FLAGS.monitor = True
        x = np.zeros((4, 8), np.float32)
        dist._count_collective("all_reduce", x)
        dist._count_collective("all_reduce", x)
        dist._count_collective("all_gather", np.zeros((2,), np.int64))
        reg = default_registry()
        assert reg.get("collective.all_reduce.ops").value == 2
        assert reg.get("collective.all_reduce.bytes").value == 2 * 4 * 8 * 4
        assert reg.get("collective.all_gather.bytes").value == 16

    def test_gated_off(self):
        from paddle_tpu.parallel import distributed as dist

        dist._count_collective("all_reduce", np.zeros((4,), np.float32))
        assert default_registry().names() == []


# ---------------------------------------------------------------------------
# hash_rng uint32 wrap guard
# ---------------------------------------------------------------------------


class TestHashRngWrapGuard:
    def test_keep_mask_attn_raises_past_2_32(self):
        import jax.numpy as jnp

        from paddle_tpu.kernels import hash_rng

        seed = jnp.uint32(7)
        # fine: below the wrap line (tiny tensors; just probe the check)
        m = hash_rng.keep_mask_attn(seed, (1, 1, 4, 4), 0.5)
        assert m.shape == (1, 1, 4, 4)
        # tq*tk == 2^32 exactly still fits (max index 2^32 - 1): the
        # guard must be strictly greater-than
        with pytest.raises(ValueError, match="2\\^32"):
            hash_rng.keep_mask_attn(seed, (1, 1, 1 << 16, 1 << 17), 0.5)

    def test_flash_attention_guard(self):
        import jax.numpy as jnp

        from paddle_tpu.kernels.attention import flash_attention

        # shapes are validated BEFORE any compute: a >=2^32 mask plane
        # with dropout must raise, not silently wrap
        tq, tk = 1 << 16, 1 << 17
        q = jnp.zeros((1, 1, tq, 8), jnp.float32)
        kv = jnp.zeros((1, 1, tk, 8), jnp.float32)
        with pytest.raises(ValueError, match="2\\^32"):
            flash_attention(q, kv, kv, dropout_rate=0.1,
                            dropout_seed=jnp.uint32(1))

    def test_small_shapes_still_work(self):
        import jax.numpy as jnp

        from paddle_tpu.kernels.attention import flash_attention

        q = jnp.ones((1, 2, 8, 4), jnp.float32)
        out = flash_attention(q, q, q, dropout_rate=0.5,
                              dropout_seed=jnp.uint32(3))
        assert out.shape == q.shape
