"""Fused dropout-add epilogue (kernels/dropout_epilogue.py) + in-kernel
PRNG dropout paths.

The contract under test (ISSUE 4 acceptance):
  * statistical: keep-rate within a chi-square bound per implementation;
  * mask parity: forward and backward regenerate BIT-IDENTICAL keep-masks
    in each of the three implementations (Pallas kernel [interpret mode
    on CPU, compiled on TPU] and the pure-XLA fallback), and the
    interpret kernel matches the XLA fallback bit-for-bit (both hash the
    same (seed, flat index));
  * zero-cost-off: rate 0 compiles to the identical HLO as a plain add,
    and the models' graphs are unchanged by FLAGS.fused_dropout_add when
    dropout is off;
  * seed determinism across executor recompiles: the mask is a pure
    function of (program seed, run counter, rng_id) — a recompile (new
    fetch list -> new cache entry) with a checkpoint-restored RNG counter
    replays the mask bit-exactly (PR-3 fixture pattern).

The TPU hardware-PRNG variants (pltpu.prng_seed has no CPU/interpret
lowering; kernels/attention.py _use_hw_prng) are covered by the
skipif-tpu class at the bottom — run it on the chip with
PT_TEST_PLATFORM=tpu.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.flags import FLAGS
from paddle_tpu.kernels import dropout_epilogue, hash_rng

SEED = 12345

# implementation -> interpret argument for dropout_add on a CPU host:
# "kernel" runs the Pallas kernel in interpret mode, "xla" forces the
# pure-XLA fallback (interpret=False off-TPU fails _plan's backend check)
CPU_IMPLS = {"kernel": True, "xla": False}


def _seed():
    return jnp.asarray([SEED], jnp.uint32)


def _mask_of(out, residual):
    """Recover the keep-mask from dropout_add output (x strictly nonzero)."""
    return np.abs(np.asarray(out) - np.asarray(residual)) > 1e-7


class TestKeepRateChiSquare:
    @pytest.mark.parametrize("impl", sorted(CPU_IMPLS))
    @pytest.mark.parametrize("rate", [0.1, 0.5])
    def test_keep_rate_within_chi_square_bound(self, impl, rate):
        # 64 buckets of 2048 Bernoulli(1-rate) draws: chi2 ~ X^2_64,
        # 3-sigma bound 64 + 3*sqrt(128) ~ 98
        n_bucket, m = 64, 2048
        x = jnp.ones((n_bucket * m // 128, 128), jnp.float32)
        r = jnp.zeros_like(x)
        out = dropout_epilogue.dropout_add(
            x, r, rate, _seed(), interpret=CPU_IMPLS[impl])
        kept = _mask_of(out, r).reshape(n_bucket, m)
        obs = kept.sum(axis=1)
        exp = m * (1.0 - rate)
        var = m * (1.0 - rate) * rate
        chi2 = ((obs - exp) ** 2 / var).sum()
        assert chi2 < 110, (impl, rate, chi2)
        assert abs(kept.mean() - (1.0 - rate)) < 0.01

    def test_sites_decorrelated(self):
        # two stream seeds (two rng_ids): ~50% mask agreement
        key = jax.random.key(0, impl="rbg")
        x = jnp.ones((128, 128), jnp.float32)
        r = jnp.zeros_like(x)
        masks = []
        for rng_id in (1, 2):
            s = jnp.reshape(hash_rng.seed_from_key(key, rng_id), (1,))
            out = dropout_epilogue.dropout_add(x, r, 0.5, s, interpret=True)
            masks.append(_mask_of(out, r))
        agree = (masks[0] == masks[1]).mean()
        assert 0.45 < agree < 0.55, agree


class TestMaskParity:
    def test_interpret_kernel_matches_xla_bitwise(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 64, 128).astype("float32"))
        r = jnp.asarray(rng.randn(4, 64, 128).astype("float32"))
        outs = {
            impl: np.asarray(dropout_epilogue.dropout_add(
                x, r, 0.3, _seed(), interpret=interp))
            for impl, interp in CPU_IMPLS.items()
        }
        assert np.array_equal(outs["kernel"], outs["xla"])

    @pytest.mark.parametrize("impl", sorted(CPU_IMPLS))
    def test_fwd_bwd_regenerate_identical_mask(self, impl):
        """The gradient wrt x must be exactly scale on kept entries and
        exactly 0 on dropped ones — i.e. the backward regenerated the
        forward's mask bit-exactly; dres is the untouched cotangent."""
        rate = 0.4
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, 32, 128).astype("float32"))
        r = jnp.asarray(rng.randn(8, 32, 128).astype("float32"))
        interp = CPU_IMPLS[impl]

        out = dropout_epilogue.dropout_add(x, r, rate, _seed(),
                                           interpret=interp)
        fwd_mask = _mask_of(out, r)

        gx, gr = jax.grad(
            lambda x, r: jnp.sum(dropout_epilogue.dropout_add(
                x, r, rate, _seed(), interpret=interp)),
            (0, 1))(x, r)
        gx = np.asarray(gx)
        scale = 1.0 / (1.0 - rate)
        assert np.allclose(gx[fwd_mask], scale, atol=1e-5), impl
        assert np.allclose(gx[~fwd_mask], 0.0), impl
        assert np.allclose(np.asarray(gr), 1.0), impl

    def test_mixed_dtype_residual(self):
        # amp shape: bf16 activations, f32 residual — out/dx bf16, dres f32
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(8, 128).astype("float32")
                        ).astype(jnp.bfloat16)
        r = jnp.asarray(rng.randn(8, 128).astype("float32"))
        out = dropout_epilogue.dropout_add(x, r, 0.3, _seed(),
                                           interpret=True)
        assert out.dtype == jnp.bfloat16
        gx, gr = jax.grad(
            lambda x, r: jnp.sum(dropout_epilogue.dropout_add(
                x, r, 0.3, _seed(), interpret=True).astype(jnp.float32)),
            (0, 1))(x, r)
        assert gx.dtype == jnp.bfloat16 and gr.dtype == jnp.float32


class TestZeroCostOff:
    def test_rate0_hlo_identical_to_plain_add(self):
        x = jnp.zeros((64, 128), jnp.float32)
        r = jnp.ones((64, 128), jnp.float32)
        h_fused = jax.jit(
            lambda x, r: dropout_epilogue.dropout_add(x, r, 0.0, None)
        ).lower(x, r).as_text()
        h_add = jax.jit(lambda x, r: x + r).lower(x, r).as_text()
        assert h_fused == h_add

    def test_models_rate0_graph_unchanged_by_flag(self):
        """With dropout off the transformer/BERT builders must emit the
        SAME op sequence whether FLAGS.fused_dropout_add is on or off —
        the fused path costs exactly nothing when dropout is off."""
        from paddle_tpu.models import bert as B
        from paddle_tpu.models import transformer as T

        def ops(flag):
            FLAGS.fused_dropout_add = flag
            try:
                prog, startup = pt.Program(), pt.Program()
                with pt.program_guard(prog, startup):
                    T.transformer(
                        src_vocab_size=64, trg_vocab_size=64, max_length=16,
                        n_layer=1, n_head=2, d_key=8, d_value=8, d_model=16,
                        d_inner_hid=32, dropout_rate=0.0, src_seq_len=16,
                        trg_seq_len=16)
                    B.build_pretrain_net(vocab_size=64, seq_len=16,
                                         n_layer=1, n_head=2, d_model=16,
                                         d_ff=32, dropout_rate=0.0,
                                         with_optimizer=False)
                return [op.type for op in prog.global_block().ops]
            finally:
                FLAGS.reset("fused_dropout_add")

        on, off = ops(True), ops(False)
        assert on == off
        assert "dropout_add" not in on and "dropout" not in on

    def test_models_with_dropout_use_fused_op_under_flag(self):
        from paddle_tpu.models import transformer as T

        def ops(flag):
            FLAGS.fused_dropout_add = flag
            try:
                prog, startup = pt.Program(), pt.Program()
                with pt.program_guard(prog, startup):
                    T.transformer(
                        src_vocab_size=64, trg_vocab_size=64, max_length=16,
                        n_layer=1, n_head=2, d_key=8, d_value=8, d_model=16,
                        d_inner_hid=32, dropout_rate=0.1, src_seq_len=16,
                        trg_seq_len=16)
                return [op.type for op in prog.global_block().ops]
            finally:
                FLAGS.reset("fused_dropout_add")

        on, off = ops(True), ops(False)
        assert "dropout_add" in on
        assert "dropout_add" not in off
        # every residual dropout site fused: 3 sub-layers/enc + 4/dec... at
        # n_layer=1: enc 2 + dec 3 = 5 "dan" sites
        assert on.count("dropout_add") == 5


class TestOpInProgram:
    def test_fwd_bwd_and_is_test(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[64, 128], dtype="float32")
            r = layers.data(name="r", shape=[64, 128], dtype="float32")
            x.stop_gradient = False
            r.stop_gradient = False
            out = layers.dropout_add(x, r, 0.4)
            loss = layers.reduce_sum(out)
            pt.append_backward(loss)
        exe = pt.Executor()
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        xv = rng.randn(1, 64, 128).astype("float32")
        rv = rng.randn(1, 64, 128).astype("float32")
        o, gx, gr = (np.asarray(v) for v in exe.run(
            prog, feed={"x": xv, "r": rv},
            fetch_list=[out.name, "x@GRAD", "r@GRAD"], scope=scope))
        kept = np.abs(o - rv) > 1e-7
        scale = 1.0 / 0.6
        assert abs(kept.mean() - 0.6) < 0.05
        np.testing.assert_allclose(o[kept], xv[kept] * scale + rv[kept],
                                   atol=1e-5)
        np.testing.assert_allclose(o[~kept], rv[~kept], atol=1e-6)
        assert np.allclose(gx[kept], scale, atol=1e-5)
        assert np.allclose(gx[~kept], 0.0)
        assert np.allclose(gr, 1.0)
        # inference clone: plain add
        infer = prog.clone(for_test=True)
        (oi,) = exe.run(infer, feed={"x": xv, "r": rv},
                        fetch_list=[out.name], scope=scope)
        np.testing.assert_allclose(np.asarray(oi), xv + rv, atol=1e-6)

    def test_seed_determinism_across_recompiles(self, tmp_path):
        """PR-3 RNG fixture pattern: the mask is a pure function of
        (program seed, executor run counter, rng_id).  Save the RNG state,
        let the counter drift, resume, then rerun with a WIDER fetch list
        — a new compile-cache entry, i.e. a genuine recompile — and the
        dropout-add output must replay bit-exactly."""
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[16, 128], dtype="float32")
            r = layers.data(name="r", shape=[16, 128], dtype="float32")
            out = layers.dropout_add(x, r, 0.4)
            total = layers.reduce_sum(out)
        exe = pt.Executor()
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(3)
        feed = {"x": rng.randn(1, 16, 128).astype("float32"),
                "r": rng.randn(1, 16, 128).astype("float32")}

        mgr = pt.io.CheckpointManager(str(tmp_path), exe, interval_steps=1,
                                      main_program=prog, scope=scope)
        exe.run(prog, feed=feed, fetch_list=[out], scope=scope)
        mgr.on_step(0)  # snapshots the executor RNG fold-in counter
        (o_next,) = exe.run(prog, feed=feed, fetch_list=[out], scope=scope)

        # drift the counter further; masks keep changing per step
        (o_drift,) = exe.run(prog, feed=feed, fetch_list=[out], scope=scope)
        assert not np.array_equal(np.asarray(o_next), np.asarray(o_drift))

        assert mgr.resume() is not None
        # wider fetch list -> new cache key -> the program RECOMPILES;
        # the restored counter must regenerate o_next's mask bit-exactly
        o_replay, _ = exe.run(prog, feed=feed, fetch_list=[out, total],
                              scope=scope)
        assert np.array_equal(np.asarray(o_replay), np.asarray(o_next))


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="hardware-PRNG dropout needs a compiled TPU kernel "
           "(pltpu.prng_seed has no CPU/interpret lowering)")
class TestHardwarePrngTPU:
    """Compiled-TPU coverage of the pltpu.prng_seed/prng_random_bits
    paths — the bits differ from the hash fallback by design, so the
    contract here is per-implementation: fwd/bwd bit-parity, keep-rate,
    and call-to-call determinism."""

    def test_epilogue_fwd_bwd_mask_parity_and_rate(self):
        rate = 0.3
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(64, 256).astype("float32"))
        r = jnp.asarray(rng.randn(64, 256).astype("float32"))
        out = dropout_epilogue.dropout_add(x, r, rate, _seed())
        out2 = dropout_epilogue.dropout_add(x, r, rate, _seed())
        assert np.array_equal(np.asarray(out), np.asarray(out2))
        fwd_mask = _mask_of(out, r)
        assert abs(fwd_mask.mean() - (1.0 - rate)) < 0.02
        gx = np.asarray(jax.grad(
            lambda x: jnp.sum(dropout_epilogue.dropout_add(
                x, r, rate, _seed())))(x))
        scale = 1.0 / (1.0 - rate)
        assert np.allclose(gx[fwd_mask], scale, atol=1e-5)
        assert np.allclose(gx[~fwd_mask], 0.0)

    def test_flash_attention_hw_dropout_deterministic_and_finite(self):
        from paddle_tpu.kernels.attention import flash_attention

        d, t, rate = 64, 256, 0.2
        rng = np.random.RandomState(6)
        shape = (2, t, 2, d)
        q, k, v = (jnp.asarray(rng.randn(*shape).astype("float32"))
                   for _ in range(3))
        seed = _seed()

        def f(q, k, v):
            return flash_attention(q, k, v, None, scale=d ** -0.5,
                                   fmt="bthd", dropout_rate=rate,
                                   dropout_seed=seed)

        o1, o2 = f(q, k, v), f(q, k, v)
        assert np.array_equal(np.asarray(o1), np.asarray(o2))
        nodrop = flash_attention(q, k, v, None, scale=d ** -0.5, fmt="bthd")
        assert not np.allclose(np.asarray(o1), np.asarray(nodrop))
        g = jax.grad(lambda q, k, v: jnp.sum(f(q, k, v)), (0, 1, 2))(q, k, v)
        for a in g:
            assert np.all(np.isfinite(np.asarray(a)))

        # stop-gradient bias (the bundled models' shape): hw PRNG stays
        # enabled via trainable_bias=False — determinism + finite grads
        bias = jnp.zeros((2, 1, 1, t), jnp.float32)

        def fb(q, k, v):
            return flash_attention(q, k, v, bias, scale=d ** -0.5,
                                   fmt="bthd", dropout_rate=rate,
                                   dropout_seed=seed, trainable_bias=False)

        b1, b2 = fb(q, k, v), fb(q, k, v)
        assert np.array_equal(np.asarray(b1), np.asarray(b2))
        gb = jax.grad(lambda q, k, v: jnp.sum(fb(q, k, v)),
                      (0, 1, 2))(q, k, v)
        for a in gb:
            assert np.all(np.isfinite(np.asarray(a)))
