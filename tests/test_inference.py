"""Inference Predictor + BN-fold pass (reference: api/paddle_api.h:153
PaddlePredictor, api_impl.h:34, analysis_predictor.h:45,
transpiler/inference_transpiler.py, ir/conv_bn_fuse_pass.cc)."""

import os

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.inference import Predictor, inference_transpile

rng = np.random.RandomState(5)


def _train_small_convnet(tmpdir, steps=12):
    """conv2d+bn+relu -> fc classifier on a separable synthetic task;
    returns (dirname, feed fn, logits var name, reference predict fn)."""
    img = layers.data(name="img", shape=[1, 8, 8], dtype="float32")
    label = layers.data(name="label", shape=[1], dtype="int64")
    conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                         act=None, bias_attr=False)
    bn = layers.batch_norm(conv, act="relu")
    flat = layers.reshape(bn, [-1, 4 * 8 * 8])
    logits = layers.fc(flat, size=3)
    loss = layers.mean(
        layers.softmax_with_cross_entropy(
            logits=logits, label=layers.reshape(label, [-1, 1])))
    pt.optimizer.AdamOptimizer(learning_rate=0.01).minimize(loss)

    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())

    def batch(n=16):
        lab = rng.randint(0, 3, (n, 1)).astype("int64")
        x = rng.randn(n, 1, 8, 8).astype("float32") + lab[:, :, None, None]
        return {"img": x, "label": lab}

    for _ in range(steps):
        exe.run(feed=batch(), fetch_list=[loss])

    dirname = str(tmpdir / "model")
    pt.io.save_inference_model(dirname, ["img"], [logits], exe)
    return dirname, batch, exe, logits


def test_predictor_matches_executor(tmp_path):
    dirname, batch, exe, logits = _train_small_convnet(tmp_path)
    feed = batch(8)

    # reference outputs via plain Executor on the live (test-mode) program
    infer_prog = pt.default_main_program().clone(for_test=True)
    (ref,) = exe.run(infer_prog, feed=feed, fetch_list=[logits])

    pred = Predictor(dirname, optimize=False)
    (out,) = pred.run({"img": feed["img"]})
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_predictor_compiles_once_across_many_runs(tmp_path):
    dirname, batch, _, _ = _train_small_convnet(tmp_path, steps=2)
    pred = Predictor(dirname)
    outs = []
    for _ in range(50):
        feed = batch(8)
        (o,) = pred.run({"img": feed["img"]})
        outs.append(np.asarray(o))
    assert pred.compile_count == 1, pred.compile_count
    # a different batch size is a new signature -> exactly one more compile
    feed = batch(4)
    pred.run({"img": feed["img"]})
    assert pred.compile_count == 2


def test_bn_fold_preserves_outputs(tmp_path):
    dirname, batch, _, _ = _train_small_convnet(tmp_path)
    feed = batch(8)

    plain = Predictor(dirname, optimize=False)
    folded = Predictor(dirname, optimize=True)
    assert folded.folded_ops == 1, folded.folded_ops
    bn_ops = [op.type for op in folded.program.global_block().ops]
    assert "batch_norm" not in bn_ops

    (a,) = plain.run({"img": feed["img"]})
    (b,) = folded.run({"img": feed["img"]})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_bn_fold_nhwc_conv(tmp_path):
    """NHWC conv + NHWC batch_norm must fold with the bias on the last
    axis (round-3 advisor finding: the fold hardcoded axis=1)."""
    img = layers.data(name="img", shape=[6, 6, 3], dtype="float32")
    conv = layers.conv2d(img, num_filters=5, filter_size=3, padding=1,
                         bias_attr=False, data_format="NHWC")
    bn = layers.batch_norm(conv, data_layout="NHWC")
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    # non-trivial BN stats so the fold actually changes W/bias
    scope = pt.global_scope()
    scope.set_var("batch_norm_0.w_0_mean",
                  rng.randn(5).astype("float32") * 0.1)
    scope.set_var("batch_norm_0.w_0_variance",
                  (1 + rng.rand(5)).astype("float32"))

    prog = pt.default_main_program().clone(for_test=True)
    feed = {"img": rng.randn(4, 6, 6, 3).astype("float32")}
    (ref,) = exe.run(prog, feed=feed, fetch_list=[bn])

    n = inference_transpile(prog, scope)
    assert n == 1
    assert "batch_norm" not in [op.type for op in prog.global_block().ops]
    (out,) = exe.run(prog, feed=feed, fetch_list=[bn])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_bn_fold_skips_layout_mismatch(tmp_path):
    """NHWC conv feeding an NCHW-labeled BN must not fold."""
    img = layers.data(name="img", shape=[4, 4, 2], dtype="float32")
    conv = layers.conv2d(img, num_filters=2, filter_size=3, padding=1,
                         bias_attr=False, data_format="NHWC")
    layers.batch_norm(conv)  # default data_layout NCHW: mismatched
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    prog = pt.default_main_program().clone(for_test=True)
    assert inference_transpile(prog, pt.global_scope()) == 0


def test_bn_fold_skips_shared_conv_output(tmp_path):
    """A conv output consumed by BN *and* something else must not fold."""
    img = layers.data(name="img", shape=[1, 4, 4], dtype="float32")
    conv = layers.conv2d(img, num_filters=2, filter_size=3, padding=1,
                         bias_attr=False)
    bn = layers.batch_norm(conv)
    both = layers.elementwise_add(bn, conv)  # second consumer of conv out
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    prog = pt.default_main_program().clone(for_test=True)
    n = inference_transpile(prog, pt.global_scope())
    assert n == 0


class TestAotServingExport:
    """VERDICT r4 item 5: serve from a serialized AOT executable with NO
    re-trace (reference: the C++ predictor's no-framework-in-the-loop
    property, api/paddle_api.h:153, api_impl.h:34)."""

    def _save_model(self, tmp_path):
        import paddle_tpu as pt
        from paddle_tpu import layers

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[8], dtype="float32")
            h = layers.fc(input=x, size=16, act="relu")
            pred = layers.fc(input=h, size=3, act="softmax")
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        feed = {"x": np.random.RandomState(0).randn(4, 8).astype("float32")}
        with pt.scope_guard(scope):
            exe.run(startup, scope=scope)
            (expected,) = exe.run(prog, feed=feed, fetch_list=[pred],
                                  scope=scope)
            pt.io.save_inference_model(
                str(tmp_path / "m"), ["x"], [pred], exe, main_program=prog,
                scope=scope, aot_feed_examples=[feed])
        return feed, np.asarray(expected)

    def test_serves_without_retrace(self, tmp_path, monkeypatch):
        import paddle_tpu as pt
        from paddle_tpu.core.executor import Executor
        from paddle_tpu.inference import Predictor

        feed, expected = self._save_model(tmp_path)
        assert (tmp_path / "m" / "__aot__" / "sig_0.json").exists()
        assert (tmp_path / "m" / "__aot__" / "sig_0.xla").exists()

        pred = Predictor(str(tmp_path / "m"), use_aot=True)
        assert pred.aot_signatures, "AOT bundle did not load"

        calls = {"n": 0}
        orig = Executor._compile

        def counting(self, *a, **k):
            calls["n"] += 1
            return orig(self, *a, **k)

        monkeypatch.setattr(Executor, "_compile", counting)
        (out,) = pred.run(feed)
        assert calls["n"] == 0, "AOT path re-traced the program"
        np.testing.assert_allclose(out, expected, atol=1e-5)
        # a different signature falls back to the retrace path and works
        feed2 = {"x": np.random.RandomState(1).randn(2, 8).astype("float32")}
        (out2,) = pred.run(feed2)
        assert calls["n"] == 1 and out2.shape == (2, 3)

    def test_fresh_process_no_retrace(self, tmp_path):
        """The artifact serves in a brand-new process (nothing shared with
        the saving process) without tracing."""
        import subprocess
        import sys

        feed, expected = self._save_model(tmp_path)
        np.save(tmp_path / "x.npy", feed["x"])
        np.save(tmp_path / "expected.npy", expected)
        script = f"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as pt
from paddle_tpu.core.executor import Executor
from paddle_tpu.inference import Predictor

pred = Predictor({str(tmp_path / 'm')!r}, use_aot=True)
assert pred.aot_signatures

# loading the artifact may compile load-ops; SERVING must not trace
def boom(self, *a, **k):
    raise AssertionError("re-traced in serving process")
Executor._compile = boom
(out,) = pred.run({{"x": np.load({str(tmp_path / 'x.npy')!r})}})
np.testing.assert_allclose(out, np.load({str(tmp_path / 'expected.npy')!r}),
                           atol=1e-5)
print("AOT_SERVE_OK")
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
        assert "AOT_SERVE_OK" in r.stdout, (r.stdout, r.stderr)

    def test_incompatible_bundle_falls_back(self, tmp_path):
        from paddle_tpu.inference import Predictor

        feed, expected = self._save_model(tmp_path)
        # corrupt the payload: loader must fall back to the retrace path
        p = tmp_path / "m" / "__aot__" / "sig_0.xla"
        p.write_bytes(b"not an executable")
        pred = Predictor(str(tmp_path / "m"), use_aot=True)
        assert not pred.aot_signatures
        (out,) = pred.run(feed)
        np.testing.assert_allclose(out, expected, atol=1e-5)


def test_aot_with_batchnorm_model_consistent(tmp_path):
    """A conv+BN model served via AOT must match the training-process
    prediction — guards the fold-vs-bundle scope interaction (the BN fold
    must not mutate params under a live AOT executable)."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.inference import Predictor

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[3, 8, 8], dtype="float32")
        c = layers.conv2d(x, num_filters=4, filter_size=3, padding=1)
        b = layers.batch_norm(c)
        pred = layers.fc(input=b, size=2, act="softmax")
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    feed = {"x": np.random.RandomState(0).randn(2, 3, 8, 8).astype("float32")}
    with pt.scope_guard(scope):
        exe.run(startup, scope=scope)
        infer = prog.clone(for_test=True)
        (expected,) = exe.run(infer, feed=feed, fetch_list=[pred],
                              scope=scope)
        pt.io.save_inference_model(str(tmp_path / "m"), ["x"], [pred], exe,
                                   main_program=prog, scope=scope,
                                   aot_feed_examples=[feed])
    p = Predictor(str(tmp_path / "m"), use_aot=True)
    assert p.aot_signatures
    (out,) = p.run(feed)
    np.testing.assert_allclose(out, np.asarray(expected), atol=1e-5)
    # retrace path on a different batch size agrees with a fresh predictor
    feed2 = {"x": np.random.RandomState(1).randn(3, 3, 8, 8).astype(
        "float32")}
    (o1,) = p.run(feed2)
    p2 = Predictor(str(tmp_path / "m"), use_aot=False)
    (o2,) = p2.run(feed2)
    np.testing.assert_allclose(o1, o2, atol=1e-5)
