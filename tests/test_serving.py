"""Serving tier (paddle_tpu/serving): multi-model inference server with
dynamic batching on the AOT-bundle path.

Covers the PR-6 tentpole + satellites: pad-to-bucket dynamic batching
(every executed batch on a warm compiled signature), Predictor/executor
thread-safety under concurrent callers (N threads x M signatures ->
exactly M compiles), serving-tier recompile tagging, int8 replicas via
contrib.quantize.freeze_int8, /health readiness-vs-liveness, the HTTP
endpoint surface, export_aot_bundle -> fresh-process zero-trace serving
(subprocess), corrupted-bundle JIT degradation, and the loadgen harness.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.flags import FLAGS
from paddle_tpu.inference import Predictor, export_aot_bundle
from paddle_tpu.monitor import default_registry, flight
from paddle_tpu.monitor import serve as mserve
from paddle_tpu.serving import (
    DynamicBatcher,
    InferenceServer,
    ModelConfig,
    ServingModel,
    parse_buckets,
)
from paddle_tpu.serving.model import item_signature

rng = np.random.RandomState(7)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Default flags + empty registry around every test; never leak the
    serving readiness provider (it would flip other suites' /health)."""
    FLAGS.reset()
    default_registry().reset()
    yield
    mserve.set_readiness_provider(None)
    FLAGS.reset()
    default_registry().reset()


# ---------------------------------------------------------------------------
# model export helpers (explicit programs/scopes: independent of the
# per-test default-program reset, so module-scoped dirs stay valid)
# ---------------------------------------------------------------------------


def _export_fc_model(dirname, in_dim=6, out_dim=3, seed=3):
    """Plain fc inference artifact with randomized (startup-initialized)
    weights; feed "x" declares (-1, in_dim)."""
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


def _export_dynamic_model(dirname):
    """Artifact whose feed "x" declares (-1, -1): requests with different
    trailing lengths are DIFFERENT item signatures (spill + ladder-gap
    coverage); warmup cannot synthesize the unknown dim."""
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[-1], dtype="float32")
        out = layers.reduce_mean(x, dim=-1, keep_dim=True)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup, scope=scope)
        pt.io.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=prog, scope=scope)
    return dirname


def _export_fixed_fetch_model(dirname):
    """Artifact whose only fetch has a FIXED leading dim (reduce over the
    batch axis of (-1, 4) -> shape (4,)): regression bait for the
    de-batching heuristic, since the fixed dim equals a ladder bucket."""
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        out = layers.reduce_mean(x, dim=0)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup, scope=scope)
        pt.io.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=prog, scope=scope)
    return dirname


def _export_qat_model(dirname, seed=11):
    """QAT-transpiled fc artifact with warmed activation scales — the
    int8-replica path (freeze_int8) needs its fake_quantize ops."""
    from paddle_tpu.contrib.quantize import QuantizeTranspiler

    lrng = np.random.RandomState(seed)
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[16], dtype="float32")
        h = layers.fc(x, size=32, act="relu")
        out = layers.fc(h, size=10)
    with pt.program_guard(prog, startup):
        QuantizeTranspiler().training_transpile(prog, startup)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup, scope=scope)
        feed = {"x": lrng.rand(8, 16).astype("float32")}
        for _ in range(10):  # warm the moving-average activation scales
            exe.run(prog, feed=feed, fetch_list=[out], scope=scope)
        test_prog = prog.clone(for_test=True)
        pt.io.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=test_prog, scope=scope)
    return dirname


@pytest.fixture(scope="module")
def fc_dir(tmp_path_factory):
    return _export_fc_model(str(tmp_path_factory.mktemp("serving") / "fc"))


@pytest.fixture(scope="module")
def dyn_dir(tmp_path_factory):
    return _export_dynamic_model(
        str(tmp_path_factory.mktemp("serving") / "dyn"))


@pytest.fixture(scope="module")
def qat_dir(tmp_path_factory):
    return _export_qat_model(
        str(tmp_path_factory.mktemp("serving") / "qat"))


def _serving_model(dirname, **kw):
    kw.setdefault("buckets", "1,2,4,8")
    kw.setdefault("max_wait_ms", 20.0)
    return ServingModel(ModelConfig("m", dirname, **kw))


# ---------------------------------------------------------------------------
# bucket ladder + padding units
# ---------------------------------------------------------------------------


class TestBucketLadder:
    def test_parse_buckets(self):
        assert parse_buckets("1,2,4,8") == (1, 2, 4, 8)
        assert parse_buckets("8, 2,2, 1") == (1, 2, 8)  # sorted, deduped
        assert parse_buckets([4, 2]) == (2, 4)
        with pytest.raises(ValueError):
            parse_buckets("")
        with pytest.raises(ValueError):
            parse_buckets("1,0,4")

    def test_bucket_for(self, fc_dir):
        m = _serving_model(fc_dir, buckets="2,4,8")
        assert m.bucket_for(1) == 2
        assert m.bucket_for(2) == 2
        assert m.bucket_for(5) == 8
        assert m.bucket_for(9) is None  # past the ladder

    def test_pad_feed_repeats_last_row(self):
        feed = {"x": np.arange(6, dtype="float32").reshape(2, 3)}
        out = ServingModel.pad_feed(feed, 2, 5)
        assert out["x"].shape == (5, 3)
        np.testing.assert_array_equal(out["x"][:2], feed["x"])
        for i in range(2, 5):
            np.testing.assert_array_equal(out["x"][i], feed["x"][-1])
        # no-op pad returns the feed unchanged
        assert ServingModel.pad_feed(feed, 2, 2) is feed

    def test_item_signature_excludes_batch_dim(self):
        a = {"x": np.zeros((2, 3), "float32")}
        b = {"x": np.zeros((7, 3), "float32")}
        c = {"x": np.zeros((2, 4), "float32")}
        assert item_signature(a) == item_signature(b)
        assert item_signature(a) != item_signature(c)

    def test_model_name_must_be_path_safe(self, fc_dir):
        with pytest.raises(ValueError):
            ModelConfig("a/b", fc_dir)
        with pytest.raises(ValueError):
            ModelConfig("", fc_dir)


# ---------------------------------------------------------------------------
# Predictor thread-safety (satellite: required before the batcher drains
# the compile cache from scheduler threads)
# ---------------------------------------------------------------------------


class TestConcurrentPredictor:
    def test_n_threads_m_signatures_exactly_m_compiles(self, fc_dir):
        """8 threads hammering 3 feed signatures -> exactly 3 compiles,
        and every result matches the single-threaded reference (no torn
        outputs from interleaved cache fills)."""
        sizes = (1, 2, 4)
        feeds = {b: {"x": rng.randn(b, 6).astype("float32")}
                 for b in sizes}
        ref_pred = Predictor(fc_dir, optimize=False)
        refs = {b: np.asarray(ref_pred.run(feeds[b])[0]) for b in sizes}

        pred = Predictor(fc_dir, optimize=False)
        n_threads, iters = 8, 25
        errors, mismatches = [], []

        def work(tid):
            lrng = np.random.RandomState(tid)
            try:
                for _ in range(iters):
                    b = sizes[lrng.randint(len(sizes))]
                    (out,) = pred.run(feeds[b])
                    if not np.allclose(np.asarray(out), refs[b],
                                       rtol=1e-5, atol=1e-6):
                        mismatches.append(b)
            except Exception as e:  # noqa: BLE001 — surface in main thread
                errors.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert not mismatches, mismatches
        assert pred.compile_count == len(sizes), pred.compile_count

    def test_run_entries_carry_the_stateful_lock(self):
        """Entries compiled via plain Executor.run — the path serving's
        batcher and Predictor hit — must carry the executor's stateful
        run lock when the program writes state (donated rw buffers +
        scope write-back must be atomic across threads), and must NOT
        serialize stateless programs."""
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            h = layers.batch_norm(x)  # training mode: running-stat writes
        prog2, startup2 = pt.Program(), pt.Program()
        with pt.program_guard(prog2, startup2):
            x2 = layers.data(name="x", shape=[4], dtype="float32")
            stateless = layers.fc(x2, size=2)
        scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
        feed = {"x": rng.randn(2, 4).astype("float32")}
        with pt.scope_guard(scope):
            exe.run(startup, scope=scope)
            exe.run(prog, feed=feed, fetch_list=[h], scope=scope)
            stateful_entry = list(exe._cache.values())[-1]
            exe.run(startup2, scope=scope)
            exe.run(prog2, feed=feed, fetch_list=[stateless], scope=scope)
            stateless_entry = list(exe._cache.values())[-1]
        assert stateful_entry.state_writes, "premise: batch_norm writes"
        assert stateful_entry.run_lock is exe._stateful_lock
        assert not stateless_entry.state_writes
        assert stateless_entry.run_lock is None


# ---------------------------------------------------------------------------
# dynamic batcher
# ---------------------------------------------------------------------------


def _start_batcher(model, **kw):
    b = DynamicBatcher(model, **kw)
    b.start()
    return b


class TestDynamicBatcher:
    def test_coalesces_concurrent_requests_and_slices_rows(self, fc_dir):
        """Concurrent 1-row submits coalesce into one padded batch; each
        caller gets exactly its own rows back (correct slicing)."""
        m = _serving_model(fc_dir, max_wait_ms=100.0)
        m.warmup()
        ref = Predictor(fc_dir, optimize=False)
        b = _start_batcher(m)
        try:
            n = 6
            feeds = [{"x": rng.randn(1, 6).astype("float32")}
                     for _ in range(n)]
            results = [None] * n

            def fire(i):
                results[i] = b.submit(feeds[i])

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            coalesced = [r[1]["coalesced"] for r in results]
            assert max(coalesced) > 1, coalesced  # batching happened
            for i, (outs, meta) in enumerate(results):
                (want,) = ref.run(feeds[i])
                np.testing.assert_allclose(
                    np.asarray(outs[0]), np.asarray(want),
                    rtol=1e-5, atol=1e-6)
                assert meta["request_rows"] == 1
                assert meta["bucket"] in m.buckets
        finally:
            b.stop()

    def test_multi_row_requests_slice_at_offsets(self, fc_dir):
        m = _serving_model(fc_dir, max_wait_ms=100.0)
        m.warmup()
        ref = Predictor(fc_dir, optimize=False)
        b = _start_batcher(m)
        try:
            sizes = [1, 2, 3]
            feeds = [{"x": rng.randn(s, 6).astype("float32")}
                     for s in sizes]
            results = [None] * len(sizes)

            def fire(i):
                results[i] = b.submit(feeds[i])

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(len(sizes))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, (outs, meta) in enumerate(results):
                assert np.asarray(outs[0]).shape[0] == sizes[i]
                (want,) = ref.run(feeds[i])
                np.testing.assert_allclose(
                    np.asarray(outs[0]), np.asarray(want),
                    rtol=1e-5, atol=1e-6)
        finally:
            b.stop()

    def test_fixed_leading_dim_fetch_is_not_sliced(self, tmp_path):
        """A fetch whose fixed leading dim coincidentally equals the
        executed bucket (reduce over the batch axis -> shape (4,) on a
        1,2,4,8 ladder) must reach every request WHOLE — the de-batch
        decision comes from the declared fetch shape, not from comparing
        the output's leading dim against the bucket.  (Such outputs are
        computed over the padded/coalesced batch; the serving contract
        for them is "whole value", and slicing them is silent
        corruption.)"""
        d = _export_fixed_fetch_model(str(tmp_path / "fixed"))
        m = _serving_model(d, max_wait_ms=10.0)
        assert m.fetch_batched == [False], m.fetch_batched
        m.warmup()
        b = _start_batcher(m)
        try:
            # 3 rows pad to bucket 4 == the fetch's fixed dim: the old
            # shape heuristic sliced the (4,) vector to its first 3
            # elements
            outs, meta = b.submit(
                {"x": rng.randn(3, 4).astype("float32")})
            assert meta["bucket"] == 4, meta
            assert np.asarray(outs[0]).shape == (4,), \
                np.asarray(outs[0]).shape
        finally:
            b.stop()

    def test_max_batch_caps_coalescing(self, fc_dir):
        m = _serving_model(fc_dir, max_batch=2, max_wait_ms=50.0)
        m.warmup()
        b = _start_batcher(m)
        try:
            n = 6
            results = [None] * n
            feeds = [{"x": rng.randn(1, 6).astype("float32")}
                     for _ in range(n)]

            def fire(i):
                results[i] = b.submit(feeds[i])

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert all(r[1]["batch_rows"] <= 2 for r in results), \
                [r[1] for r in results]
        finally:
            b.stop()

    def test_oversize_request_runs_and_is_counted(self, fc_dir):
        FLAGS.monitor = True
        m = _serving_model(fc_dir, buckets="1,2")
        m.warmup()
        b = _start_batcher(m)
        try:
            outs, meta = b.submit({"x": rng.randn(5, 6).astype("float32")})
            assert np.asarray(outs[0]).shape[0] == 5
            assert meta["bucket"] == 5  # exact-size execution
            c = default_registry().get("serving.m.oversize_batches")
            assert c is not None and c.value == 1
        finally:
            b.stop()

    def test_mixed_item_signatures_spill_not_mix(self, dyn_dir):
        """Requests with different trailing lengths never coalesce into
        one batch, and all of them are answered correctly."""
        m = _serving_model(dyn_dir, max_wait_ms=100.0)
        m.warmup()  # nothing warmable: the trailing dim is unknown
        b = _start_batcher(m)
        try:
            lens = [5, 7, 5, 7, 5, 7]
            feeds = [{"x": rng.randn(1, L).astype("float32")}
                     for L in lens]
            results = [None] * len(lens)

            def fire(i):
                results[i] = b.submit(feeds[i])

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(len(lens))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, (outs, meta) in enumerate(results):
                want = feeds[i]["x"].mean(axis=-1, keepdims=True)
                np.testing.assert_allclose(np.asarray(outs[0]), want,
                                           rtol=1e-5, atol=1e-6)
        finally:
            b.stop()

    def test_validation_errors(self, fc_dir):
        m = _serving_model(fc_dir)
        m.warmup()
        b = _start_batcher(m)
        try:
            with pytest.raises(KeyError):  # missing feed
                b.submit({})
            with pytest.raises(ValueError):  # zero rows
                b.submit({"x": np.zeros((0, 6), "float32")})
            with pytest.raises(KeyError):  # unknown precision
                b.submit({"x": np.zeros((1, 6), "float32")},
                         precision="int8")
        finally:
            b.stop()


# ---------------------------------------------------------------------------
# warmup + compile-cache behavior (the tentpole property)
# ---------------------------------------------------------------------------


class TestWarmupAndCompileCache:
    def test_shape_varying_stream_zero_compiles_after_warmup(self, fc_dir):
        """The acceptance property at unit scale: after warming the
        ladder, an unbounded stream of request sizes causes ZERO further
        compiles (every batch padded onto a warm signature)."""
        FLAGS.monitor = True
        m = _serving_model(fc_dir, buckets="1,2,4,8")
        warmed = m.warmup()
        assert warmed == 4 and m.ready
        pred = m.predictor()
        frozen = pred.compile_count
        assert frozen == 4
        b = _start_batcher(m)
        try:
            for i in range(30):
                s = 1 + (i % 8)
                outs, meta = b.submit(
                    {"x": rng.randn(s, 6).astype("float32")})
                assert np.asarray(outs[0]).shape[0] == s
                assert meta["bucket"] >= s
        finally:
            b.stop()
        assert pred.compile_count == frozen  # flat: no retrace, ever
        c = default_registry().get("serving.unplanned_compiles")
        assert c is None or c.value == 0

    def test_serving_recompile_is_flight_tagged(self, dyn_dir):
        """Satellite: a compile taken while serving (ladder gap) lands in
        /flight with the requested vs bucketed signature + a named
        counter — diagnosable, not a silent retrace stall."""
        FLAGS.monitor = True
        m = _serving_model(dyn_dir, buckets="1,2")
        m.warmup()  # warms nothing; flips ready
        assert m.ready
        b = _start_batcher(m)
        try:
            b.submit({"x": rng.randn(1, 9).astype("float32")})
        finally:
            b.stop()
        evs = flight.default_recorder().events(kind="serving.compile")
        assert evs, "serving-tier compile not flight-recorded"
        ev = evs[-1]
        assert ev["model"] == "m" and ev["after_warmup"]
        assert ev["requested_rows"] == 1 and ev["bucketed_rows"] == 1
        assert ev["requested_signature"] == [["x", [9], "float32"]]
        assert ev["ctx"] == "serving/m"
        c = default_registry().get("serving.unplanned_compiles")
        assert c is not None and c.value >= 1



# ---------------------------------------------------------------------------
# int8 replicas (contrib.quantize.freeze_int8 path)
# ---------------------------------------------------------------------------


class TestInt8Replica:
    def test_int8_replica_serves_and_matches_fp32(self, qat_dir):
        m = ServingModel(ModelConfig("q", qat_dir, int8=True,
                                     buckets="1,2,4", max_wait_ms=20.0))
        assert m.precisions == ["fp32", "int8"]
        # the replica's program really is frozen: int8 consumers, no fakes
        i8_ops = [op.type for op in
                  m.predictor("int8")._program.global_block().ops]
        assert "int8_mul" in i8_ops
        assert not any(t.startswith("fake_") for t in i8_ops)
        m.warmup()
        b = _start_batcher(m)
        try:
            feed = {"x": rng.rand(2, 16).astype("float32")}
            fp, _ = b.submit(feed)
            i8, meta = b.submit(feed, precision="int8")
            assert meta["precision"] == "int8"
            fp, i8 = np.asarray(fp[0]), np.asarray(i8[0])
            err = np.abs(fp - i8).max() / (np.abs(fp).max() + 1e-6)
            assert err < 0.1, err  # int8 quantization error bound
        finally:
            b.stop()

    def test_int8_requires_qat_artifact(self, fc_dir):
        with pytest.raises(ValueError, match="fake_quantize"):
            ServingModel(ModelConfig("f", fc_dir, int8=True))


# ---------------------------------------------------------------------------
# /health: trainer liveness vs serving readiness (satellite)
# ---------------------------------------------------------------------------


@pytest.fixture()
def _saved_step_state():
    rec = flight.default_recorder()
    saved = (rec.last_step, rec.last_loss, rec.last_step_ts)
    yield rec
    rec.last_step, rec.last_loss, rec.last_step_ts = saved


class TestHealth:
    def test_zero_steps_is_not_stalled(self, _saved_step_state):
        rec = _saved_step_state
        rec.last_step = rec.last_loss = rec.last_step_ts = None
        body, code = mserve.health_body()
        assert code == 200 and body["status"] == "ok"
        assert body["trainer"] is None  # no step monitor -> no liveness

    def test_stall_threshold_is_the_flag(self, _saved_step_state):
        rec = _saved_step_state
        rec.last_step, rec.last_step_ts = 42, time.time() - 5.0
        FLAGS.health_stall_s = 2.0
        body, code = mserve.health_body()
        assert code == 503 and body["status"] == "stalled"
        assert body["trainer"]["alive"] is False
        assert body["trainer"]["stall_after_s"] == 2.0
        FLAGS.health_stall_s = 60.0  # same staleness, wider threshold
        body, code = mserve.health_body()
        assert code == 200 and body["status"] == "ok"
        assert body["trainer"]["alive"] is True

    def test_readiness_distinct_from_liveness(self, _saved_step_state):
        rec = _saved_step_state
        rec.last_step = rec.last_loss = rec.last_step_ts = None
        mserve.set_readiness_provider(
            lambda: {"ready": False, "models": {}})
        body, code = mserve.health_body()
        assert code == 503 and body["status"] == "not_ready"
        mserve.set_readiness_provider(lambda: {"ready": True})
        body, code = mserve.health_body()
        assert code == 200 and body["status"] == "ok"
        # a broken probe answers 503, never raises
        def boom():
            raise RuntimeError("probe exploded")
        mserve.set_readiness_provider(boom)
        body, code = mserve.health_body()
        assert code == 503 and "probe exploded" in body["serving"]["error"]


# ---------------------------------------------------------------------------
# HTTP server surface
# ---------------------------------------------------------------------------


def _http(url, data=None, headers=None, timeout=30):
    """-> (status, body bytes); HTTP errors return their status+body."""
    req = urllib.request.Request(url, data=data, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture()
def fc_server(fc_dir):
    srv = InferenceServer(
        [ModelConfig("fc", fc_dir, buckets="1,2,4", max_wait_ms=5.0)],
        port=0)
    srv.start()
    yield srv, f"http://127.0.0.1:{srv.port}"
    srv.stop()


class TestHTTPServer:
    def test_predict_json_matches_direct_predictor(self, fc_server,
                                                   fc_dir):
        srv, url = fc_server
        x = rng.randn(3, 6).astype("float32")
        status, raw = _http(
            f"{url}/v1/models/fc:predict",
            data=json.dumps({"inputs": {"x": x.tolist()}}).encode(),
            headers={"Content-Type": "application/json"})
        assert status == 200
        body = json.loads(raw)
        (want,) = Predictor(fc_dir, optimize=False).run({"x": x})
        got = np.asarray(body["outputs"][srv.model("fc").fetch_names[0]])
        np.testing.assert_allclose(got, np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        assert body["batch"]["bucket"] == 4  # 3 rows pad to bucket 4
        assert body["batch"]["request_rows"] == 3

    def test_predict_b64_and_npz_roundtrip(self, fc_server):
        import base64
        import io as _io

        srv, url = fc_server
        x = rng.randn(2, 6).astype("float32")
        # b64 raw-buffer JSON form
        status, raw = _http(
            f"{url}/v1/models/fc:predict",
            data=json.dumps({"inputs": {"x": {
                "b64": base64.b64encode(x.tobytes()).decode(),
                "dtype": "float32", "shape": [2, 6]}}}).encode(),
            headers={"Content-Type": "application/json"})
        assert status == 200
        want = np.asarray(json.loads(raw)["outputs"][
            srv.model("fc").fetch_names[0]])
        # npz request + npz response
        buf = _io.BytesIO()
        np.savez(buf, x=x)
        status, raw = _http(
            f"{url}/v1/models/fc:predict?format=npz",
            data=buf.getvalue(),
            headers={"Content-Type": "application/x-npz"})
        assert status == 200
        with np.load(_io.BytesIO(raw)) as z:
            got = z[srv.model("fc").fetch_names[0]]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_introspection_health_metrics(self, fc_server):
        srv, url = fc_server
        status, raw = _http(f"{url}/v1/models")
        assert status == 200
        (info,) = json.loads(raw)["models"]
        assert info["name"] == "fc" and info["ready"]
        assert info["buckets"] == [1, 2, 4]
        assert info["feeds"]["x"]["shape"] == [-1, 6]
        status, raw = _http(f"{url}/v1/models/fc")
        assert status == 200 and json.loads(raw)["name"] == "fc"
        # a zero-step serving process is healthy (readiness, not stall)
        status, raw = _http(f"{url}/health")
        assert status == 200
        health = json.loads(raw)
        assert health["serving"]["ready"] is True
        assert health["serving"]["models"]["fc"]["ready"] is True
        # serve one request, then the metrics surface must carry the
        # serving histograms/counters
        x = rng.randn(1, 6).astype("float32")
        _http(f"{url}/v1/models/fc:predict",
              data=json.dumps({"inputs": {"x": x.tolist()}}).encode(),
              headers={"Content-Type": "application/json"})
        status, raw = _http(f"{url}/metrics")
        text = raw.decode()
        for needle in ("serving_fc_request_seconds", "serving_fc_batches",
                       "serving_fc_batch_fill_bucket", "serving_requests",
                       "executor_compiles"):
            assert needle in text, needle

    def test_error_surface(self, fc_server):
        srv, url = fc_server
        post = {"Content-Type": "application/json"}
        cases = [
            # unknown model
            (f"{url}/v1/models/nope:predict",
             json.dumps({"inputs": {"x": [[0.0] * 6]}}).encode(), post,
             404),
            # malformed JSON
            (f"{url}/v1/models/fc:predict", b"{not json", post, 400),
            # missing "inputs" key
            (f"{url}/v1/models/fc:predict", b'{"x": 1}', post, 400),
            # missing feed
            (f"{url}/v1/models/fc:predict",
             json.dumps({"inputs": {}}).encode(), post, 400),
            # unknown precision replica
            (f"{url}/v1/models/fc:predict",
             json.dumps({"inputs": {"x": [[0.0] * 6]},
                         "precision": "int8"}).encode(), post, 400),
            # unsupported content type
            (f"{url}/v1/models/fc:predict", b"x,1,2",
             {"Content-Type": "text/csv-not-a-thing/x"}, 415),
        ]
        for target, data, headers, want in cases:
            status, raw = _http(target, data=data, headers=headers)
            assert status == want, (target, status, raw[:200])
            assert "error" in json.loads(raw)
        # GET on an unknown path still 404s through the monitor fallback
        status, _ = _http(f"{url}/definitely/not/a/route")
        assert status == 404

    def test_duplicate_model_name_rejected(self, fc_server, fc_dir):
        srv, _ = fc_server
        with pytest.raises(ValueError, match="already served"):
            srv.add_model(ModelConfig("fc", fc_dir))


# ---------------------------------------------------------------------------
# export_aot_bundle -> fresh-process serving (subprocess; satellite)
# ---------------------------------------------------------------------------


def _spawn_server(args, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("FLAGS_monitor", None)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        cwd=REPO_ROOT, env=env, text=True)
    deadline = time.time() + 120
    line = ""
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.strip():
            break
        if proc.poll() is not None:
            break
    if not line.strip() or proc.poll() is not None:
        err = proc.stderr.read() if proc.stderr else ""
        proc.kill()
        raise AssertionError(f"server did not come up: {err[-2000:]}")
    ready = json.loads(line)
    assert ready["event"] == "serving_ready"
    return proc, f"http://127.0.0.1:{ready['port']}"


def _stop_server(proc):
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=15)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=5)


def _scrape_scalar(url, name):
    text = _http(f"{url}/metrics")[1].decode()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    return 0.0


class TestAOTServingSubprocess:
    @pytest.fixture(scope="class")
    def aot_dir(self, tmp_path_factory):
        d = _export_fc_model(
            str(tmp_path_factory.mktemp("serving") / "aot_fc"))
        n = export_aot_bundle(
            d, [{"x": np.zeros((b, 6), "float32")} for b in (1, 2, 4)])
        assert n == 3
        return d

    def test_fresh_process_serves_with_zero_traces(self, aot_dir):
        """The reference's out-of-Python property, end to end: a FRESH
        process loads the exported dir with use_aot and serves 100
        shape-varying requests with the executor compile counter FLAT at
        zero — no trace, no compile, bundles only."""
        proc, url = _spawn_server(
            ["--model", f"demo={aot_dir}", "--port", "0", "--use-aot",
             "--buckets", "1,2,4", "--max-wait-ms", "1"])
        try:
            info = json.loads(_http(f"{url}/v1/models/demo")[1])
            assert info["use_aot"] and info["aot_signatures"] == 3
            compiles_after_warmup = _scrape_scalar(url, "executor_compiles")
            assert compiles_after_warmup == 0, \
                "AOT warmup must serve from bundles, not compile"
            lrng = np.random.RandomState(0)
            for i in range(100):
                s = 1 + (i % 4)
                x = lrng.randn(s, 6).astype("float32")
                status, raw = _http(
                    f"{url}/v1/models/demo:predict",
                    data=json.dumps({"inputs": {"x": x.tolist()}}).encode(),
                    headers={"Content-Type": "application/json"})
                assert status == 200, raw[:200]
            assert _scrape_scalar(url, "executor_compiles") \
                == compiles_after_warmup, "a request triggered a trace"
            assert _scrape_scalar(url, "serving_demo_requests") == 100
        finally:
            _stop_server(proc)

    def test_corrupt_bundle_degrades_to_jit_with_named_counter(
            self, aot_dir, tmp_path):
        """A corrupted sig_*.xla must not take the model down: the load
        degrades that signature to the JIT path, counts it
        (inference_aot_bundle_errors), and serves correct results."""
        import shutil

        d = str(tmp_path / "corrupt")
        shutil.copytree(aot_dir, d)
        victim = sorted(glob.glob(os.path.join(d, "__aot__",
                                               "sig_*.xla")))[0]
        with open(victim, "wb") as f:
            f.write(b"\x00garbage, definitely not an XLA payload")
        proc, url = _spawn_server(
            ["--model", f"demo={d}", "--port", "0", "--use-aot",
             "--buckets", "1,2,4", "--max-wait-ms", "1"])
        try:
            info = json.loads(_http(f"{url}/v1/models/demo")[1])
            assert info["ready"] and info["aot_signatures"] == 2
            assert _scrape_scalar(url, "inference_aot_bundle_errors") >= 1
            # the degraded signature compiled (JIT fallback), served fine
            assert _scrape_scalar(url, "executor_compiles") >= 1
            x = rng.randn(1, 6).astype("float32")
            status, raw = _http(
                f"{url}/v1/models/demo:predict",
                data=json.dumps({"inputs": {"x": x.tolist()}}).encode(),
                headers={"Content-Type": "application/json"})
            assert status == 200, raw[:200]
        finally:
            _stop_server(proc)


# ---------------------------------------------------------------------------
# AOT bundle donation safety (v2 bundles)
# ---------------------------------------------------------------------------


class TestAOTDonationSafety:
    """Regression: v1 bundles baked the executor's donate_argnums
    aliasing into the serialized executable, and jax's deserialized
    Compiled path has no donation bookkeeping — running a STATEFUL
    bundle (QAT quant-state write-backs) returned state arrays aliasing
    freed buffers, corrupting the scope nondeterministically under
    serving load.  v2 bundles serialize donation-free; loaders reject
    v1 to the JIT path."""

    @pytest.fixture()
    def qat_aot_dir(self, qat_dir, tmp_path):
        import shutil

        d = str(tmp_path / "qat_aot")
        shutil.copytree(qat_dir, d)
        assert export_aot_bundle(
            d, [{"x": np.zeros((b, 16), "float32")} for b in (2, 4)]) == 2
        return d

    def test_stateful_bundle_state_and_values_stable(self, qat_aot_dir):
        """Warmup-style zeros runs + real runs through a stateful bundle
        leave the quant state EXACTLY unchanged (test-mode passthrough)
        and serve the JIT predictor's values.  Under the v1 donation bug
        this corrupted within a couple of iterations whenever another
        predictor churned the heap."""
        with open(glob.glob(os.path.join(
                qat_aot_dir, "__aot__", "sig_*.json"))[0]) as f:
            manifest = json.load(f)
        assert manifest["aot_version"] >= 2
        state_names = manifest["state_writes"]
        assert state_names, "QAT artifact must carry quant-state writes"

        pred = Predictor(qat_aot_dir, optimize=False, use_aot=True)
        assert len(pred.aot_signatures) == 2
        # a second predictor in the same process: heap churn was part of
        # the original corruption trigger
        ref = Predictor(qat_aot_dir, optimize=False, use_aot=False)
        x = np.random.RandomState(5).rand(4, 16).astype("float32")
        want = np.asarray(ref.run({"x": x})[0])

        state0 = {n: np.asarray(pred._scope.find_var(n)).copy()
                  for n in state_names}
        for i in range(12):
            pred.run({"x": np.zeros((2 if i % 2 else 4, 16), "float32")})
            out = np.asarray(pred.run({"x": x})[0])
            np.testing.assert_allclose(out, want, rtol=0, atol=1e-6)
            for n, v0 in state0.items():
                np.testing.assert_array_equal(
                    np.asarray(pred._scope.find_var(n)), v0,
                    err_msg=f"quant state {n} drifted at iteration {i}")

    def test_v1_donating_bundle_rejected_to_jit(self, qat_aot_dir):
        for p in glob.glob(os.path.join(qat_aot_dir, "__aot__",
                                        "sig_*.json")):
            with open(p) as f:
                m = json.load(f)
            del m["aot_version"]  # pre-versioning == v1 == donating
            with open(p, "w") as f:
                json.dump(m, f)
        FLAGS.monitor = True
        pred = Predictor(qat_aot_dir, optimize=False, use_aot=True)
        assert pred.aot_signatures == []
        errs = default_registry().get("inference.aot_bundle_errors")
        assert errs is not None and errs.value >= 2
        # JIT fallback still serves correct values
        ref = Predictor(qat_aot_dir, optimize=False, use_aot=False)
        x = np.random.RandomState(5).rand(2, 16).astype("float32")
        np.testing.assert_allclose(
            np.asarray(pred.run({"x": x})[0]),
            np.asarray(ref.run({"x": x})[0]), rtol=0, atol=1e-6)
        assert pred.compile_count == 1


# ---------------------------------------------------------------------------
# loadgen harness (tools/loadgen.py)
# ---------------------------------------------------------------------------


class TestLoadgen:
    def test_loadgen_artifact_against_live_server(self, fc_dir, tmp_path):
        srv = InferenceServer(
            [ModelConfig("fc", fc_dir, buckets="1,2,4,8",
                         max_wait_ms=3.0)], port=0)
        srv.start()
        out = str(tmp_path / "loadgen.json")
        try:
            rc = subprocess.run(
                [sys.executable, os.path.join(REPO_ROOT, "tools",
                                              "loadgen.py"),
                 "--url", f"http://127.0.0.1:{srv.port}", "--model", "fc",
                 "--requests", "40", "--concurrency", "4",
                 "--batch-sizes", "1,2,3", "--out", out],
                capture_output=True, text=True, timeout=120)
            assert rc.returncode == 0, rc.stderr[-2000:]
        finally:
            srv.stop()
        art = json.loads(open(out).read())
        assert art["completed"] == 40 and art["errors"] == 0
        assert art["qps"] > 0
        assert art["latency_ms"]["p99"] >= art["latency_ms"]["p50"] > 0
        assert art["policy"]["buckets"] == [1, 2, 4, 8]
        sm = art["server_metrics"]
        assert sm["batches"] >= 1
        assert sm["unplanned_compiles"] == 0  # warm ladder held
        assert sm["batch_fill_mean"] is not None
        assert 0 < sm["batch_fill_mean"] <= 1
