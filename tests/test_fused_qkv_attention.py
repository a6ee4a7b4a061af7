"""Self-attention as one op: fused_qkv_attention / flash_qkv_attention
(XLA projection dots round the bthd flash kernels; PERF.md PR 30 — the
Pallas kernel `fused_qkv_fwd`, its plan and FLAGS_fused_qkv_attention were
deleted in PR 30).

Covers:
  * numerical parity + gradcheck of flash_qkv_attention (interpret
    kernels) against the plain composition x@W + reference_attention +
    @W_out — fp32/bf16, causal/bias shapes, dropout on/off (hash masks are
    BIT-identical to the reference's); the backward, both as the grad op
    runs it (flash_qkv_attention_bwd on the forward's q, k, v, ctx, lse)
    and as autodiff of the forward gives it (the generic route);
  * op/program level: the op trains as the fc+split+fused_attention+fc
    chain it stands for (loss, every updated parameter, dropout
    trajectories included); parameter names as every build before PR 30
    gave them (checkpoint interop, transplant-tested); amp; is_test; a
    program saved before PR 30 (no Q/K/V slots) trains through the generic
    route to the same gradients;
  * which sites of multi_head_attention emit the op, and that no flag
    selects it any more;
  * a TPU-only class that arms on the driver's chip (compiled Mosaic
    kernels vs the plain reference + hw-PRNG dropout determinism).
"""

import collections
import importlib.util
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import framework as fw
from paddle_tpu.flags import FLAGS
from paddle_tpu.kernels.attention import (
    flash_qkv_attention,
    flash_qkv_attention_bwd,
    flash_qkv_attention_fwd,
    reference_attention,
)
from paddle_tpu.models import bert as B
from paddle_tpu.models import transformer as T


def _mk(rng, *shape, s=0.08):
    return jnp.asarray((rng.randn(*shape) * s).astype("float32"))


def _inputs(b=2, t=128, h=2, dh=64, dm=128, seed=0):
    rng = np.random.RandomState(seed)
    x = _mk(rng, b, t, dm, s=0.3)
    w_qkv = _mk(rng, dm, 3 * h * dh)
    w_out = _mk(rng, h * dh, dm)
    pad_bias = jnp.asarray(
        np.where(rng.rand(b, 1, 1, t) < 0.2, -1e9, 0.0).astype("float32"))
    return x, w_qkv, w_out, pad_bias


#: heads and widths of the sites models/bert.py and models/transformer.py
#: build at BERT-base and transformer-base (batch 2)
_BUILDER_SHAPES = {
    "bert": dict(b=2, t=128, h=12, dh=64, dm=768),
    "transformer": dict(b=2, t=256, h=8, dh=64, dm=512),
}
#: what _bias_norm takes at _inputs()'s b 2, h 2, t 128: none, fewer than
#: four dims, each of batch / head / query broadcast or full, a key
#: broadcast
_BIAS_SHAPES = [(), (128, 128), (2, 1, 1, 128), (1, 1, 1, 128),
                (2, 1, 128, 128), (1, 2, 1, 128), (2, 2, 128, 128),
                (2, 1, 128, 1)]


def _kernel_names(jaxpr):
    return dict(collections.Counter(
        re.findall(r"name=(\w*(?:_fwd|_bwd)\w*)", str(jaxpr))))


def _plain_qkv(x, w_qkv, w_out, bias, h, scale, causal, dropout_rate=0.0,
               seed=None):
    """The plain composition, no kernel and no [b, t, h, dh] einsum: one
    x @ W_qkv, slices, split-head transposes, reference_attention, merge,
    @ W_out."""
    b, t, _ = x.shape
    hd = w_qkv.shape[1] // 3
    qkv = x @ w_qkv
    q, k, v = (qkv[..., i * hd:(i + 1) * hd].reshape(b, t, h, hd // h)
               .transpose(0, 2, 1, 3) for i in range(3))
    ctx = reference_attention(q, k, v, bias, scale, causal, dropout_rate,
                              seed)
    return (ctx.transpose(0, 2, 1, 3).reshape(b, t, hd) @ w_out).astype(
        x.dtype)


def _sq(y):
    return jnp.sum(y.astype(jnp.float32) ** 2)


def _grads_op_and_plain(x, w_qkv, w_out, bias, h, scale, causal,
                        blocks=(64, 64), route="direct"):
    """(dx, dw_qkv, dw_out[, dbias]) of sum(y^2), the bias trainable:
    through flash_qkv_attention — `direct`: flash_qkv_attention_bwd on the
    forward's residuals, as the grad op runs it; `autodiff`: jax.grad of
    the forward, as the generic route runs it — and through the plain
    composition."""
    wrt = (0, 1, 2, 3) if bias is not None else (0, 1, 2)
    opts = dict(n_head=h, scale=scale, causal=causal, block_q=blocks[0],
                block_k=blocks[1], interpret=True)
    if route == "direct":
        y, *kept = flash_qkv_attention_fwd(x, w_qkv, w_out, bias, **opts)
        g = (2 * y.astype(jnp.float32)).astype(y.dtype)
        got = flash_qkv_attention_bwd(x, w_qkv, w_out, bias, *kept, g,
                                      **opts)[:len(wrt)]
    else:
        got = jax.grad(lambda *a: _sq(flash_qkv_attention(*a, **opts)),
                       wrt)(x, w_qkv, w_out, bias)
    want = jax.grad(lambda *a: _sq(_plain_qkv(*a, h, scale, causal)),
                    wrt)(x, w_qkv, w_out, bias)
    return got, want


def _assert_grads_close(got, want, dtype):
    """float32: 1e-4 of each gradient's largest entry; bf16: the 5 % of
    it that this file's bf16 comparisons take.  (1e-5 beside it: a bias
    broadcast along the keys has no gradient but rounding.)"""
    tol = 1e-4 if dtype == "float32" else 0.05
    for name, a, b in zip(("dx", "dw_qkv", "dw_out", "dbias"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        assert np.abs(a - b).max() <= tol * np.abs(b).max() + 1e-5, name


def _eqns(jaxpr, name):
    """The equations of primitive `name` in `jaxpr` and the jaxprs nested
    in it, the bodies of Pallas kernels left out."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


class TestKernels:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("with_bias", [False, True])
    def test_fwd_parity_fp32(self, causal, with_bias):
        x, w_qkv, w_out, bias = _inputs()
        bias = bias if with_bias else None
        got = flash_qkv_attention(
            x, w_qkv, w_out, bias, n_head=2, scale=0.125, causal=causal,
            block_q=64, block_k=64, interpret=True)
        ref = _plain_qkv(x, w_qkv, w_out, bias, 2, 0.125, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bias_shape", [
        (1, 1, 1, 128),    # broadcast padding mask
        (2, 1, 128, 128),  # per-batch causal+pad plane (the decoder's)
        (1, 2, 1, 128),    # per-head key bias
        (2, 2, 128, 128),  # fully-expanded
    ])
    def test_fwd_parity_bias_shapes(self, bias_shape):
        x, w_qkv, w_out, _ = _inputs()
        rng = np.random.RandomState(3)
        bias = jnp.asarray(
            np.where(rng.rand(*bias_shape) < 0.15, -1e9, 0.0)
            .astype("float32"))
        got = flash_qkv_attention(
            x, w_qkv, w_out, bias, n_head=2, scale=0.125,
            block_q=64, block_k=64, interpret=True)
        ref = _plain_qkv(x, w_qkv, w_out, bias, 2, 0.125, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("route", ["direct", "autodiff"])
    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("shape", sorted(_BUILDER_SHAPES))
    def test_gradcheck_vs_plain(self, shape, dtype, causal, route):
        """dx, dW_qkv, dW_out AND dbias (trainable bias) against jax.grad
        of the plain composition at the heads and widths the BERT and
        transformer builders give their sites: the backward (the bthd
        kernels on the forward's q, k, v, ctx and lse, the projection
        backward as XLA dots) is numerically the plain autodiff, by the
        grad op's route and by the generic one."""
        dims = _BUILDER_SHAPES[shape]
        x, w_qkv, w_out, bias = (
            a.astype(dtype) for a in _inputs(**dims, seed=4))
        bias = jnp.where(bias < 0, -1e4, 0.0).astype(dtype)
        h, scale = dims["h"], dims["dh"] ** -0.5
        got, want = _grads_op_and_plain(x, w_qkv, w_out, bias, h, scale,
                                        causal, blocks=(128, 128),
                                        route=route)
        _assert_grads_close(got, want, dtype)

    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    @pytest.mark.parametrize("bias_shape", _BIAS_SHAPES,
                             ids=lambda s: "x".join(map(str, s)) or "none")
    def test_gradcheck_bias_shapes(self, bias_shape, causal):
        """Every bias shape _bias_norm takes, trainable: the cotangent
        comes back in the caller's own shape, through the bthd route's
        recompute."""
        x, w_qkv, w_out, _ = _inputs()
        bias = None
        if bias_shape:
            rng = np.random.RandomState(3)
            bias = jnp.asarray((rng.randn(*bias_shape) * 0.5)
                               .astype("float32"))
        got, want = _grads_op_and_plain(x, w_qkv, w_out, bias, 2, 0.125,
                                        causal)
        if bias is not None:
            assert got[3].shape == bias.shape
        _assert_grads_close(got, want, "float32")

    @pytest.mark.parametrize("case", ["transformer", "bert", "causal",
                                      "bert_seq512"])
    def test_backward_runs_the_bthd_kernels_on_ctx_and_lse(self, case):
        """The traced forward holds one flash_bthd_fwd between four
        projection dots; the traced backward holds the two bthd backward
        kernels between eight dots (dctx, dW_out, three of dx, three of
        dW_qkv), no kernel of another family and NO projection of x: q,
        k, v come from the forward.  The two backward kernels ask for 32
        MiB of scoped VMEM where the default 16 was seen refused (a causal
        walk; more than 512 KiB held whole), and for nothing at the
        cells' own shapes."""
        from paddle_tpu.analysis.kernel_lint import _pretend_tpu

        dims = dict(_BUILDER_SHAPES["bert" if "bert" in case
                                    else "transformer"])
        if case == "bert_seq512":
            dims["t"] = 512
        x, w_qkv, w_out, _ = (a.astype(jnp.bfloat16)
                              for a in _inputs(**dims))
        opts = dict(n_head=dims["h"], scale=0.125, causal=case == "causal")
        with _pretend_tpu():  # traced only: nothing is compiled
            fwd = jax.make_jaxpr(lambda *a: flash_qkv_attention_fwd(
                *a, None, **opts))(x, w_qkv, w_out)
            kept = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                    for v in fwd.jaxpr.outvars]
            bwd = jax.make_jaxpr(
                lambda x, wq, wo, y, *kept: flash_qkv_attention_bwd(
                    x, wq, wo, None, *kept, y, **opts))(
                        x, w_qkv, w_out, *kept)
        assert _kernel_names(fwd) == {"flash_bthd_fwd": 1}
        assert _kernel_names(bwd) == {"flash_bthd_bwd_dq": 1,
                                      "flash_bthd_bwd_dkv": 1}
        assert len(_eqns(fwd.jaxpr, "dot_general")) == 4
        dots = _eqns(bwd.jaxpr, "dot_general")
        assert len(dots) == 8
        heads = (dims["b"], dims["t"], dims["h"], dims["dh"])
        # one dot alone gives [b, t, h, dh]: dctx, from g and W_out
        to_heads = [e for e in dots if e.outvars[0].aval.shape == heads]
        assert [sorted(v.aval.shape for v in e.invars) for e in to_heads] \
            == [sorted([x.shape, (dims["h"], dims["dh"], dims["dm"])])]
        raised = str(bwd).count(f"vmem_limit_bytes={32 * 1024 * 1024}")
        assert raised == (2 if case in ("causal", "bert_seq512") else 0)

    @pytest.mark.parametrize("case", ["hw_prng", "hash_flag", "no_dropout",
                                      "trainable_bias"])
    def test_every_dropout_mode_is_one_composition(self, case):
        """Whatever draws the masks — the hardware PRNG, the hash
        (FLAGS.tpu_prng_dropout off, or a trainable bias, which pins it),
        nothing — a site is the same three kernels: forward and backward
        are one family, so they draw the same tile from the same
        generator (until PR 30 a hardware-PRNG site had to leave the
        fused forward kernel for this)."""
        from paddle_tpu.analysis.kernel_lint import _pretend_tpu

        x, w_qkv, w_out, bias = _inputs()
        seed = jnp.asarray([5], jnp.uint32)
        rate = 0.0 if case == "no_dropout" else 0.1

        def loss(x, wq, wo):
            return jnp.sum(flash_qkv_attention(
                x, wq, wo, bias, n_head=2, scale=0.125, dropout_rate=rate,
                dropout_seed=seed,
                trainable_bias=case == "trainable_bias"))

        FLAGS.tpu_prng_dropout = case != "hash_flag"
        try:
            with _pretend_tpu():  # traced only: nothing is compiled
                jaxpr = jax.make_jaxpr(
                    jax.grad(loss, (0, 1, 2)))(x, w_qkv, w_out)
        finally:
            FLAGS.reset("tpu_prng_dropout")
        assert _kernel_names(jaxpr) == {
            "flash_bthd_fwd": 1, "flash_bthd_bwd_dq": 1,
            "flash_bthd_bwd_dkv": 1}
        # the three kernels agree on the generator
        assert str(jaxpr).count("prng_seed") == (3 if case == "hw_prng"
                                                 else 0)

    @pytest.mark.parametrize("route", ["direct", "autodiff"])
    def test_dropout_parity_and_grads(self, route):
        """In-kernel weights-dropout: the per-head hash masks are
        bit-identical to the plain reference's (same (seed, b*H+h,
        q*Tk+k) keying), so output AND gradients match it."""
        x, w_qkv, w_out, bias = _inputs()
        seed = jnp.asarray([77], jnp.uint32)
        opts = dict(n_head=2, scale=0.125, block_q=64, block_k=64,
                    interpret=True, dropout_rate=0.1, dropout_seed=seed,
                    trainable_bias=False)

        def lr(x, wq, wo):
            return _sq(_plain_qkv(x, wq, wo, bias, 2, 0.125, False, 0.1,
                                  seed))

        y, *kept = flash_qkv_attention_fwd(x, w_qkv, w_out, bias, **opts)
        np.testing.assert_allclose(float(_sq(y)),
                                   float(lr(x, w_qkv, w_out)), rtol=1e-5)
        if route == "direct":
            gf = flash_qkv_attention_bwd(x, w_qkv, w_out, bias, *kept,
                                         2 * y, **opts)
            assert gf[3] is None  # a stop-gradient mask
        else:
            gf = jax.grad(lambda *a: _sq(flash_qkv_attention(
                *a, bias, **opts)), (0, 1, 2))(x, w_qkv, w_out)
        gr = jax.grad(lr, (0, 1, 2))(x, w_qkv, w_out)
        for name, a, b in zip(("dx", "dw_qkv", "dw_out"), gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6, err_msg=name)

    def test_bf16(self):
        x, w_qkv, w_out, bias = _inputs()
        xb, wqb, wob = (a.astype(jnp.bfloat16) for a in (x, w_qkv, w_out))
        y, q, k, v, ctx, lse = flash_qkv_attention_fwd(
            xb, wqb, wob, bias, n_head=2, scale=0.125, block_q=64,
            block_k=64, interpret=True)
        assert {a.dtype for a in (y, q, k, v, ctx)} \
            == {jnp.dtype(jnp.bfloat16)}
        assert lse.dtype == jnp.float32
        ref = _plain_qkv(xb, wqb, wob, bias, 2, 0.125, False)
        f32 = np.asarray(y.astype(jnp.float32))
        r32 = np.asarray(ref.astype(jnp.float32))
        scale = np.abs(r32).max() + 1e-6
        assert np.abs(f32 - r32).max() < 0.05 * scale

    def test_plan_reject_runs_the_xla_reference(self):
        """d_head not a lane multiple: the bthd plan rejects, and the same
        composition runs flash_attention's XLA reference — the plain
        composition's numbers, no lse, and no direct backward (the grad op
        then takes the generic route)."""
        rng = np.random.RandomState(5)
        x = _mk(rng, 2, 16, 24, s=0.3)
        w_qkv = _mk(rng, 24, 3 * 2 * 8)   # d_head=8 -> reject
        w_out = _mk(rng, 16, 24)
        y, *kept = flash_qkv_attention_fwd(x, w_qkv, w_out, None, n_head=2,
                                           scale=0.35, interpret=True)
        assert kept[-1] is None
        want = _plain_qkv(x, w_qkv, w_out, None, 2, 0.35, False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        assert flash_qkv_attention_bwd(
            x, w_qkv, w_out, None, *kept, jnp.ones_like(y), n_head=2,
            scale=0.35, interpret=True) is None
        g = jax.grad(lambda x: jnp.sum(flash_qkv_attention(
            x, w_qkv, w_out, None, n_head=2, scale=0.35,
            interpret=True)))(x)
        assert g.shape == x.shape and bool(jnp.all(jnp.isfinite(g)))

    def test_packed_width_must_divide_into_heads(self):
        x, w_qkv, w_out, _ = _inputs()
        with pytest.raises(ValueError, match="not divisible by 3"):
            flash_qkv_attention(x, w_qkv[:, :-2], w_out, None, n_head=2)


def _build_bert(dropout=0.0, seq=32, opt=True, n_head=2):
    """Mini BERT MLM net (1 layer; at n_head 2 the head size is 64 and the
    bthd plan takes the site in interpret mode)."""
    fw._rng_id_counter[0] = 0
    prog, startup = pt.Program(), pt.Program()
    with fw.guard_unique_name():
        with pt.program_guard(prog, startup):
            loss, _ = B.build_pretrain_net(
                vocab_size=64, seq_len=seq, n_layer=1, n_head=n_head,
                d_model=128, d_ff=128, dropout_rate=dropout,
                use_flash=True, with_optimizer=opt, lr=1e-3)
    return prog, startup, loss


def _bert_feed(seq=32, seed=0):
    return B.make_batch(2, seq, 64, rng=np.random.RandomState(seed))


def _init_params(prog, scope, seed=7):
    r = np.random.RandomState(seed)
    for p in prog.all_parameters():
        v = np.asarray(scope.find_var(p.name))
        scope.set_var(p.name, (r.randn(*v.shape) * 0.05).astype(v.dtype))


def _fc_chain_mha(queries, attn_bias, d_key, d_value, d_model, n_head,
                  dropout_rate):
    """The chain the op stands for, as every build before r09 emitted a
    flash self-attention site (and multi_head_attention still emits the
    sites the op does not take): one packed qkv fc + split + bthd
    fused_attention + output fc, under the op's parameter names."""
    from paddle_tpu.core.framework import unique_name
    from paddle_tpu.layers.contrib import fused_attention
    from paddle_tpu.param_attr import ParamAttr

    qkv = layers.fc(input=queries, size=3 * d_key * n_head,
                    bias_attr=False, num_flatten_dims=2,
                    param_attr=ParamAttr(name=unique_name("attn_qkv_w")))
    q, k, v = layers.split(qkv, 3, dim=-1)

    def to_bthd(x, d):
        b, t, _ = x.shape
        return layers.reshape(x, [b, t, n_head, d])

    ctx = fused_attention(
        to_bthd(q, d_key), to_bthd(k, d_key), to_bthd(v, d_value),
        attn_bias, scale=d_key**-0.5, dropout_rate=dropout_rate,
        fmt="bthd",
    )
    b, t, h, d = ctx.shape
    ctx = layers.reshape(ctx, [b, t, h * d])
    return layers.fc(input=ctx, size=d_model, bias_attr=False,
                     num_flatten_dims=2,
                     param_attr=ParamAttr(name=unique_name("attn_out_w")))


def _mha_op(x, bias, dropout=0.1):
    return T.multi_head_attention(x, None, None, bias, 64, 64, 128,
                                  n_head=2, dropout_rate=dropout,
                                  use_flash=True)


def _mha_chain(x, bias, dropout=0.1):
    return _fc_chain_mha(x, bias, 64, 64, 128, 2, dropout)


_MHA = {"fused_qkv_attention": _mha_op, "fused_attention": _mha_chain}


def _build_mha_net(site, dropout=0.1, opt=True):
    """Tiny self-attention net round one site of `_MHA`: a padding bias
    from a fed mask, a layer norm so that the loss bends, SGD."""
    fw._rng_id_counter[0] = 0
    prog, startup = pt.Program(), pt.Program()
    with fw.guard_unique_name():
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[32, 128], dtype="float32")
            mask = layers.data(name="mask", shape=[32, 1],
                               dtype="float32")
            neg = layers.scale(layers.transpose(mask, [0, 2, 1]),
                               scale=1e9, bias=-1e9)
            bias = layers.reshape(neg, [-1, 1, 1, 32])
            bias.stop_gradient = True
            out = layers.layer_norm(_MHA[site](x, bias, dropout),
                                    begin_norm_axis=2)
            loss = layers.mean(layers.square(out + x))
            if opt:
                pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return prog, startup, loss


def _mha_feed(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "x": (rng.randn(2, 32, 128) * 0.2).astype("float32"),
        "mask": (rng.rand(2, 32, 1) > 0.2).astype("float32"),
    }


def _train_mha(site, dropout=0.0, amp=False, steps=3, params=None,
               is_test=False):
    """(losses, parameters) of `steps` SGD steps of the mini net from
    fixed weights (or `params`)."""
    prog, startup, loss = _build_mha_net(site, dropout, opt=not is_test)
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    _init_params(prog, scope)
    for name, val in (params or {}).items():
        scope.set_var(name, val)
    if amp:
        pt.amp.enable(prog)
    prog._is_test = is_test
    losses = [float(np.asarray(exe.run(
        prog, feed=_mha_feed(), fetch_list=[loss], scope=scope)[0])
        .reshape(-1)[-1]) for _ in range(steps)]
    return losses, {p.name: np.asarray(scope.find_var(p.name))
                    for p in prog.all_parameters()}


#: the parameters of the mini BERT and the mini transformer as every build
#: before PR 30 gave them, with the flag that PR 30 deleted on or off
#: (checkpoints name their tensors by these)
_PARAMS_BEFORE_PR30 = {
    "bert": {
        "attn_out_w_0": (128, 128), "attn_qkv_w_0": (128, 384),
        "fc_2.b_0": (128,), "fc_2.w_0": (128, 128), "fc_3.b_0": (128,),
        "fc_3.w_0": (128, 128), "fc_4.b_0": (64,), "fc_4.w_0": (128, 64),
        "layer_norm_0.b_0": (128,), "layer_norm_0.w_0": (128,),
        "layer_norm_1.b_0": (128,), "layer_norm_1.w_0": (128,),
        "layer_norm_2.b_0": (128,), "layer_norm_2.w_0": (128,),
        "pos_embedding": (32, 128), "sent_embedding": (2, 128),
        "word_embedding": (64, 128)},
    "transformer": {
        "attn_k_w_0": (128, 128), "attn_out_w_0": (128, 128),
        "attn_out_w_1": (128, 128), "attn_out_w_2": (128, 128),
        "attn_q_w_0": (128, 128), "attn_qkv_w_0": (128, 384),
        "attn_qkv_w_1": (128, 384), "attn_v_w_0": (128, 128),
        "ffn_in_b_0": (128,), "ffn_in_b_1": (128,),
        "ffn_in_w_0": (128, 128), "ffn_in_w_1": (128, 128),
        "ffn_out_b_0": (128,), "ffn_out_b_1": (128,),
        "ffn_out_w_0": (128, 128), "ffn_out_w_1": (128, 128),
        **{f"layer_norm_{i}.{p}_0": (128,) for i in range(5) for p in "bw"},
        "predict_b": (64,), "predict_w": (128, 64),
        "src_pos_enc_table": (32, 128), "src_word_emb_table": (64, 128),
        "trg_pos_enc_table": (32, 128), "trg_word_emb_table": (64, 128)},
}


class TestOpProgram:
    @pytest.mark.parametrize("case", [
        "plain",
        pytest.param("dropout", marks=pytest.mark.slow),
        pytest.param("amp", marks=pytest.mark.slow)])
    def test_trains_as_the_fc_chain(self, case):
        """Loss trajectory AND every updated parameter of three steps
        match the fc + split + fused_attention + fc chain's.  Dropout on:
        the in-kernel hash masks key on the same (seed, head,
        plane-index) tuples in both, so even the DROPPED trajectories
        agree on the CPU."""
        kw = dict(dropout=0.1 if case != "plain" else 0.0,
                  amp=case == "amp")
        lo, po = _train_mha("fused_qkv_attention", **kw)
        lc, pc = _train_mha("fused_attention", **kw)
        tol = dict(rtol=0.02, atol=0.02) if case == "amp" \
            else dict(rtol=1e-5, atol=1e-6)
        assert all(np.isfinite(lo)) and lo[-1] < lo[0]
        np.testing.assert_allclose(lo, lc, **tol)
        assert po.keys() == pc.keys()
        if case != "amp":
            for k in po:
                np.testing.assert_allclose(po[k], pc[k], rtol=5e-4,
                                           atol=1e-6, err_msg=k)
        if case == "dropout":
            # sanity: dropout actually differs from the no-dropout run
            assert abs(_train_mha("fused_qkv_attention")[0][-1]
                       - lo[-1]) > 1e-7

    @pytest.mark.parametrize("model", sorted(_PARAMS_BEFORE_PR30))
    def test_param_names_as_before_pr30(self, model):
        """Checkpoint interop: the builders create the parameter names and
        shapes they created before PR 30."""
        prog = _MODELS[model][0]()[0]
        assert {p.name: tuple(p.shape) for p in prog.all_parameters()} \
            == _PARAMS_BEFORE_PR30[model]

    def test_checkpoint_interop_with_the_fc_chain(self):
        """Train 2 steps through the op, transplant the checkpoint into
        the fc-chain program (and back), evaluate: identical losses — the
        packed [dm, 3hd]/[hd, dm] parameters are the same tensors either
        way."""
        _, params = _train_mha("fused_qkv_attention", steps=2)
        (op,), _ = _train_mha("fused_qkv_attention", steps=1,
                              params=params, is_test=True)
        (chain,), _ = _train_mha("fused_attention", steps=1, params=params,
                                 is_test=True)
        assert abs(op - chain) < 1e-5, (op, chain)

    def test_is_test_disables_dropout(self):
        a, b = _train_mha("fused_qkv_attention", dropout=0.4, steps=2,
                          is_test=True)[0]
        assert abs(a - b) < 1e-7  # deterministic: no dropout draws

    def test_is_test_program_keeps_no_residual(self):
        """A program without a grad op hands nothing of a site on: what
        the compiled step gives back is the fetch alone, so q, k, v, the
        context and the logsumexp die where the forward last reads them
        (the slots are written for a reader, not held for one)."""
        prog, startup, loss = _build_bert(opt=False)
        op, = [op for op in prog.global_block().ops
               if op.type == "fused_qkv_attention"]
        assert sorted(op.outputs) == ["Ctx", "K", "Lse", "Out", "Q", "V"]
        prog._is_test = True
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        lowered = exe.lower(prog, _bert_feed(), [loss], scope)
        outs = [tuple(o.shape) for o in jax.tree_util.tree_leaves(
            lowered.out_info)]
        assert all(len(s) < 3 for s in outs), outs

    def test_copy_census_reads_the_compiled_step(self):
        """tools/hlo_diag.py --copy-census on the mini attention net's
        compiled step: every copy falls to one of the four sites, and the
        op's projection dots are not the `mul` lowering's (its
        'projection' site stays empty)."""
        path = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "hlo_diag.py")
        spec = importlib.util.spec_from_file_location("_hlo_diag_mod", path)
        hd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(hd)
        prog, startup, loss = _build_mha_net("fused_qkv_attention")
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        rep = hd.analyze_copy_census(exe.lower(
            prog, _mha_feed(), [loss], scope).compile().as_text())
        assert set(rep["sites"]) == {"projection", "pallas", "entry",
                                     "other"}
        assert rep["sites"]["projection"]["mb"] == 0.0, rep
        assert "copy census by site" in hd.format_copy_census(rep)

    @pytest.mark.parametrize("site,n", [
        ("self_flash", 1), ("cross", 0), ("d_key_ne_d_value", 0),
        ("ring", 0), ("no_flash", 0)])
    def test_which_sites_emit_the_op(self, site, n):
        """A flash self-attention site always emits the one op (no flag
        selects it: `fused_qkv_attention` left FLAGS in PR 30); cross
        attention, d_key != d_value, the ring path and use_flash=False
        keep the fc chain."""
        assert "fused_qkv_attention" not in object.__getattribute__(
            FLAGS, "_defs")
        with pytest.raises(AttributeError):
            getattr(FLAGS, "fused_qkv_attention")
        prog, startup = pt.Program(), pt.Program()
        with fw.guard_unique_name():
            with pt.program_guard(prog, startup):
                x = layers.data(name="x", shape=[32, 128], dtype="float32")
                mem = layers.data(name="m", shape=[32, 128],
                                  dtype="float32")
                T.multi_head_attention(
                    x, mem if site == "cross" else None, None, None, 64,
                    32 if site == "d_key_ne_d_value" else 64, 128, n_head=2,
                    use_flash=site != "no_flash", use_ring=site == "ring")
        ops = [op.type for op in prog.global_block().ops]
        assert ops.count("fused_qkv_attention") == n
        if n:
            # the projections, the split and the output fc are in the op
            assert not {"mul", "split", "fused_attention"} & set(ops)
        else:
            assert "mul" in ops


# -- the residual hand-off (forward op -> registered grad op) ----------------

_ATTN_OPS = ("fused_qkv_attention", "fused_attention")


def _strip_residuals(prog, slots=None):
    """Make `prog` the program a build before the hand-off gave (and
    passes.py still gives): attention ops with `Out` alone, grad ops with
    the forward's inputs and Out@GRAD alone — every attention grad op then
    lowers through lower_generic_grad.  With slots=("Q", "K", "V"): the
    program a build before PR 30 gave, whose fused_qkv_attention ops kept
    Ctx and Lse alone."""
    from paddle_tpu.core import registry

    for op in prog.global_block().ops:
        fwd = op.type[:-len("_grad")] if op.type.endswith("_grad") \
            else op.type
        if fwd not in _ATTN_OPS:
            continue
        drop = [s for s in registry.get(fwd).residuals
                if slots is None or s in slots]
        if op.type == fwd:
            for slot in drop:
                if slot != "Out":
                    op.outputs.pop(slot, None)
        else:
            for slot in drop:
                op.inputs.pop(slot, None)
    return prog


def _build_transformer(dropout=0.0):
    """Mini encoder-decoder (1+1 layers): two fused-qkv self-attention
    sites plus one bthd cross-attention site, Adam inside."""
    fw._rng_id_counter[0] = 0
    prog, startup = pt.Program(), pt.Program()
    with fw.guard_unique_name():
        with pt.program_guard(prog, startup):
            loss, _, _ = T.transformer(
                src_vocab_size=64, trg_vocab_size=64, max_length=32,
                n_layer=1, n_head=2, d_key=64, d_value=64, d_model=128,
                d_inner_hid=128, dropout_rate=dropout, src_seq_len=32,
                trg_seq_len=32, use_flash=True)
            pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return prog, startup, loss


def _transformer_feed():
    return T.make_batch(2, 32, 32, 2, 64, 64, rng=np.random.RandomState(0))


_MODELS = {"bert": (_build_bert, _bert_feed),
           "transformer": (_build_transformer, _transformer_feed)}


def _step_with_grads(prog, startup, loss, feed, amp=False, steps=1):
    """`steps` train steps from fixed weights: (per-step [loss + every
    parameter's gradient], the parameters after them, what the compile of
    the step counted)."""
    from paddle_tpu import monitor

    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    _init_params(prog, scope)
    if amp:
        pt.amp.enable(prog)
    block = prog.global_block()
    fetch = [loss.name] + [p.name + "@GRAD" for p in prog.all_parameters()
                           if block.has_var(p.name + "@GRAD")]
    before = monitor.compile_phases()
    outs = [[np.asarray(v) for v in exe.run(prog, feed=feed,
                                            fetch_list=fetch, scope=scope)]
            for _ in range(steps)]
    counted = {k: monitor.compile_phases()[k] - before[k]
               for k in ("grad_direct", "grad_generic", "qkv_bwd_composed")}
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in prog.all_parameters()}
    return outs, params, counted


def _assert_same(got, want, tol=0.0):
    """Losses, gradients and parameters of two runs: bit for bit, or (a
    fused_qkv_attention site by its two routes: the grad op sums dx in
    float32 and stacks dW_qkv, autodiff of the forward adds as it goes)
    within `tol` of each array's largest entry."""
    (outs_a, params_a, _), (outs_b, params_b, _) = got, want
    pairs = [(a, b, "step") for step_a, step_b in zip(outs_a, outs_b)
             for a, b in zip(step_a, step_b)]
    assert params_a.keys() == params_b.keys()
    pairs += [(params_a[k], params_b[k], k) for k in params_a]
    for a, b, what in pairs:
        assert a.dtype == b.dtype, what
        if tol:
            a, b = a.astype("float32"), b.astype("float32")
            assert np.abs(a - b).max() <= tol * np.abs(b).max() + 1e-9, what
        else:
            np.testing.assert_array_equal(a, b, err_msg=what)


def _n_generic_by_nature(prog):
    """Grad ops of `prog` that have no registered lowering of their own."""
    from paddle_tpu.core import registry

    return sum(op.type.endswith("_grad") and registry.lookup(op.type) is None
               for op in prog.global_block().ops)


def _n_sites(prog, types=_ATTN_OPS):
    return sum(op.type in types for op in prog.global_block().ops)


@pytest.fixture
def clean_flight():
    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight

    assert not FLAGS.monitor
    flight.default_recorder().clear()
    yield
    FLAGS.reset("monitor")
    flight.default_recorder().clear()
    monitor.default_registry().reset()


class TestResidualGrad:
    @pytest.mark.parametrize("before", ["hand_off", "pr30"])
    @pytest.mark.parametrize("amp", [False, True], ids=["fp32", "amp"])
    @pytest.mark.parametrize("model", sorted(_MODELS))
    def test_gradients_equal_to_generic_route(self, model, amp, before):
        """One train step: every parameter's gradient (and the loss, and
        the parameters Adam leaves) through the residuals is what
        lower_generic_grad gives on the same program without the slots —
        as a build before the hand-off gave it (`Out` alone), and as a
        program saved before PR 30 holds it (Ctx and Lse, no Q / K / V:
        its fused_qkv_attention grad ops go the generic way, the
        cross-attention site still the direct one)."""
        build, feed = _MODELS[model]
        direct = _step_with_grads(*build(), feed(), amp=amp)
        prog, startup, loss = build()
        generic = _step_with_grads(
            _strip_residuals(prog, ("Q", "K", "V") if before == "pr30"
                             else None), startup, loss, feed(), amp=amp)
        _assert_same(direct, generic, tol=0.03 if amp else 2e-5)
        n, n_qkv = _n_sites(prog), _n_sites(prog, _ATTN_OPS[:1])
        assert (n, n_qkv) == {"bert": (1, 1), "transformer": (3, 2)}[model]
        # the counters of the compile: every attention grad op went the
        # direct way, and nothing but the ops that have no lowering of
        # their own went through lower_generic_grad
        by_nature = _n_generic_by_nature(prog)
        assert direct[2] == {"grad_direct": n, "grad_generic": by_nature,
                             "qkv_bwd_composed": n_qkv}
        still = n - n_qkv if before == "pr30" else 0
        assert generic[2] == {"grad_direct": still,
                              "grad_generic": by_nature + n - still,
                              "qkv_bwd_composed": 0}

    @pytest.mark.parametrize("model", sorted(_MODELS))
    def test_one_forward_kernel_per_site(self, model, clean_flight):
        """The traced train step holds each attention site's forward
        kernel once (the generic route holds it twice: once more, under a
        `jvp` name, for the residuals), and the `executor.compile` flight
        event says so."""
        from paddle_tpu.core.executor import latest_jitted_entry
        from paddle_tpu.monitor import flight

        def kernels(prog, startup, loss):
            FLAGS.monitor = True
            try:
                exe = pt.Executor(pt.CPUPlace())
                scope = pt.Scope()
                exe.run(startup, scope=scope)
                feed = _MODELS[model][1]()
                exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
            finally:
                FLAGS.reset("monitor")
            entry = latest_jitted_entry(exe)
            args = ([exe._to_device_array(prog, n, feed[n])
                     for n in sorted(feed)],
                    [scope.find_var(n) for n in entry.rw_state],
                    [scope.find_var(n) for n in entry.ro_state])
            if entry.needs_key:
                args += (jax.random.PRNGKey(0),)
            names = _kernel_names(entry.jitted.trace(*args).jaxpr)
            event = flight.default_recorder().events(
                kind="executor.compile")[-1]
            return names, event

        prog, startup, loss = _MODELS[model][0]()
        names, event = kernels(prog, startup, loss)
        n, n_qkv = _n_sites(prog), _n_sites(prog, _ATTN_OPS[:1])
        assert dict(names) == {"flash_bthd_fwd": n, "flash_bthd_bwd_dq": n,
                               "flash_bthd_bwd_dkv": n}
        assert event["grad_direct"] == n
        assert event["grad_generic"] == _n_generic_by_nature(prog)
        assert event["qkv_bwd_composed"] == n_qkv
        # and the generic route, which this test would not tell from the
        # direct one if it read nothing: the forward kernel again for each
        # site (in the jaxpr a third time, lower_generic_grad's probe of
        # the output structure, which XLA drops)
        prog, startup, loss = _MODELS[model][0]()
        names, event = kernels(_strip_residuals(prog), startup, loss)
        assert sum(v for k, v in names.items() if "_fwd" in k) >= 2 * n
        assert event["grad_direct"] == 0
        # a site that fell to the generic route is not counted
        assert event["qkv_bwd_composed"] == 0

    def test_amp_leaves_lse_float32(self, monkeypatch):
        """Under amp every float input of the attention ops and of their
        grad ops goes to bf16 — Q, K, V and Ctx among them — but Lse: the
        backward kernels get float32, the array the forward wrote."""
        from paddle_tpu import amp
        from paddle_tpu.kernels import attention as att

        lse = jnp.ones((2, 2, 32), jnp.float32)
        heads = jnp.ones((2, 32, 2, 64))
        ins = {"X": [jnp.ones((2, 32, 128))], "Q": [heads], "K": [heads],
               "V": [heads], "Ctx": [heads], "Lse": [lse],
               "Out@GRAD": [jnp.ones((2, 32, 128))]}
        cast = amp.apply_cast_policy("fused_qkv_attention_grad", ins)
        assert cast["Lse"][0] is lse
        assert {cast[s][0].dtype for s in ins if s != "Lse"} \
            == {jnp.dtype(jnp.bfloat16)}
        cast = amp.apply_cast_policy(
            "fused_attention_grad", {"Q": [heads], "Out": [heads],
                                     "Lse": [lse]})
        assert cast["Lse"][0] is lse
        assert cast["Out"][0].dtype == cast["Q"][0].dtype == jnp.bfloat16

        seen = []
        real_flash = att._flash_backward

        def spy_flash(q, k_, v, bias, seed, o, lse, g, *a, **k):
            seen.append((q.dtype, k_.dtype, v.dtype, o.dtype, lse.dtype,
                         g.dtype))
            return real_flash(q, k_, v, bias, seed, o, lse, g, *a, **k)

        monkeypatch.setattr(att, "_flash_backward", spy_flash)
        prog, startup, loss = _build_transformer()
        sites = [op for op in prog.global_block().ops
                 if op.type in _ATTN_OPS]
        names = [op.output(slot)[0] for op in sites
                 for slot in ("Q", "K", "V", "Ctx", "Lse")
                 if op.type == "fused_qkv_attention" or slot == "Lse"]
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        _init_params(prog, scope)
        pt.amp.enable(prog)
        fetched = dict(zip(names, exe.run(
            prog, feed=_transformer_feed(), fetch_list=names, scope=scope)))
        bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
        # the cross-attention site and the two fused-qkv sites, whose
        # backward hands the same kernels the forward's q, k, v and ctx
        # and dctx in x's dtype, and the forward's Lse as it is
        assert seen == [(bf16, bf16, bf16, bf16, f32, bf16)] * 3
        lse_names = {op.output("Lse")[0] for op in sites}
        assert len(lse_names) == 3 and len(names) == 3 + 2 * 4
        for name, v in fetched.items():
            assert jnp.asarray(v).dtype == (f32 if name in lse_names
                                            else bf16), name
            assert np.all(np.isfinite(np.asarray(v, dtype="float32")))

    @pytest.mark.parametrize("case", ["out_only", "plan_rejects"])
    def test_programs_without_residuals_train_as_before(self, case):
        """What the direct route does not take lowers as it always did:
        a fused_attention with `Out` alone (as passes.py inserts it, the
        grad op made AFTER the slots were dropped), and a head size the
        kernel plan rejects (the XLA reference writes no Lse)."""
        if case == "out_only":
            def build(strip_before_backward):
                prog, startup, loss = _build_mha_net("fused_attention",
                                                     dropout=0.0, opt=False)
                if strip_before_backward:
                    _strip_residuals(prog)
                with pt.program_guard(prog, startup):
                    pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
                return prog, startup, loss

            prog, startup, loss = build(True)
            grad_op, = [op for op in prog.global_block().ops
                        if op.type == "fused_attention_grad"]
            assert sorted(grad_op.inputs) == ["Bias", "K", "Out@GRAD", "Q",
                                              "V"]
            got = _step_with_grads(prog, startup, loss, _mha_feed())
            assert got[2]["grad_direct"] == 0
            want = _step_with_grads(*build(False), _mha_feed())
            assert want[2]["grad_direct"] == 1
        else:
            prog, startup, loss = _build_bert(n_head=4)
            assert _n_sites(prog) == 1
            got = _step_with_grads(prog, startup, loss, _bert_feed())
            # d_head 32: the forward wrote no Lse, so the registered grad
            # op found a residual unbound and went the generic way
            assert got[2] == {
                "grad_direct": 0, "qkv_bwd_composed": 0,
                "grad_generic": _n_generic_by_nature(prog) + 1}
            prog, startup, loss = _build_bert(n_head=4)
            want = _step_with_grads(_strip_residuals(prog), startup, loss,
                                    _bert_feed())
        _assert_same(got, want)

    @pytest.mark.parametrize("site", _ATTN_OPS)
    def test_dropout_trajectory_identical_to_generic_route(self, site):
        """Dropout on (the hash masks of the interpret route): the grad op
        derives the seed its forward used from the copied `rng_id`, so
        two steps through the residuals are the generic route's (bit for
        bit where both are one body, fused_attention's)."""
        def run(dropout, strip):
            prog, startup, loss = _build_mha_net(site, dropout)
            if strip:
                _strip_residuals(prog)
            return _step_with_grads(prog, startup, loss, _mha_feed(),
                                    steps=2)

        direct, generic = run(0.1, False), run(0.1, True)
        _assert_same(direct, generic,
                     tol=2e-5 if site == "fused_qkv_attention" else 0.0)
        assert direct[2]["grad_direct"] == 1
        assert generic[2]["grad_direct"] == 0
        nodrop = run(0.0, False)
        assert not np.array_equal(direct[0][1][0], nodrop[0][1][0])


# -- the cells' programs at tiny widths ---------------------------------------

_PERFBENCH = os.path.join(os.path.dirname(__file__), "..", "perfbench")
#: the three cells' own layer counts at widths a CPU traces in seconds
#: (head size 64, so that the kernel plans take the sites)
_TINY_CELLS = {
    "transformer_base_train": (
        dict(d_model=128, n_head=2, d_key=64, d_value=64, d_inner_hid=128,
             src_vocab_size=64, trg_vocab_size=64),
        dict(batch=2, src_len=32, trg_len=32), 12),
    "bert_base_train": (
        dict(hidden_size=128, num_attention_heads=2, intermediate_size=128,
             vocab_size=64),
        dict(batch=2, seq_len=32), 12),
    "joyai_flash_ep16_train": (
        dict(hidden_size=64, num_attention_heads=2, q_lora_rank=48,
             kv_lora_rank=32, qk_nope_head_dim=64, qk_rope_head_dim=64,
             v_head_dim=64, intermediate_size=96, moe_intermediate_size=32,
             n_routed_experts=4, router_experts=16, expert_offset=4,
             num_experts_per_tok=4, vocab_size=211),
        dict(batch=2, seq_len=32), 0),
}


@pytest.mark.parametrize("workload", sorted(_TINY_CELLS))
def test_compile_event_counts_qkv_bwd_composed(workload, clean_flight):
    """`qkv_bwd_composed` on the miss call's `executor.compile` event and
    in monitor.compile_phases(): every fused_qkv_attention site of the
    transformer (6 + 6) and BERT (12) cells' programs takes the composed
    backward; the MLA cell's program has no such site."""
    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight

    sys.path.insert(0, _PERFBENCH)
    try:
        import registry
        import traffic_gen
    finally:
        sys.path.remove(_PERFBENCH)
    widths, sizes, sites = _TINY_CELLS[workload]
    cell = registry.load_cell(workload)
    cfg = dict(cell.cfg, amp=False, **widths)
    traffic = dict(cell.traffic, steps_per_call=1, feed_pool=1, **sizes)
    prog, startup, loss = registry.load_module(
        cell.path(cfg["program"])).build(cfg, traffic)
    ops = [op.type for op in prog.global_block().ops]
    assert ops.count("fused_qkv_attention_grad") == sites
    scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    before = monitor.compile_phases()["qkv_bwd_composed"]
    FLAGS.monitor = True
    try:
        exe.run_steps(prog, feed=traffic_gen.train_feeds(traffic, cfg, 1)[0],
                      fetch_list=[loss], scope=scope)
    finally:
        FLAGS.reset("monitor")
    event = flight.default_recorder().events(kind="executor.compile")[-1]
    assert event["qkv_bwd_composed"] == sites
    assert monitor.compile_phases()["qkv_bwd_composed"] - before == sites


class TestRingBthd:
    def test_ring_model_path_has_no_transposes(self):
        """The CP model path on fmt='bthd': no transpose op anywhere in
        the attention block (the satellite contract: context parallelism
        must not re-introduce split-head transposes)."""
        fw._rng_id_counter[0] = 0
        prog, startup = pt.Program(), pt.Program()
        with fw.guard_unique_name():
            with pt.program_guard(prog, startup):
                x = layers.data(name="x", shape=[32, 128],
                                dtype="float32")
                out = T.multi_head_attention(
                    x, None, None, None, 64, 64, 128, n_head=2,
                    use_ring=True)
        ops = [op.type for op in prog.global_block().ops]
        assert "ring_attention" in ops
        assert "transpose2" not in ops and "transpose" not in ops


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Mosaic kernel paths need a TPU")
class TestFusedQkvTPU:
    """Arms on the driver's chip: the COMPILED composition (the bthd
    Mosaic kernels between XLA dots, not interpret mode) against the plain
    reference, and hw-PRNG dropout determinism."""

    def test_kernel_parity_compiled(self):
        rng = np.random.RandomState(0)
        b, t, h, dh, dm = 2, 256, 8, 64, 512
        x = jnp.asarray((rng.randn(b, t, dm) * 0.2).astype("float32")
                        ).astype(jnp.bfloat16)
        w_qkv = jnp.asarray((rng.randn(dm, 3 * h * dh) * 0.04)
                            .astype("float32")).astype(jnp.bfloat16)
        w_out = jnp.asarray((rng.randn(h * dh, dm) * 0.04)
                            .astype("float32")).astype(jnp.bfloat16)
        opts = dict(n_head=h, scale=dh ** -0.5, causal=True)

        def op(x, wq, wo):
            y, *kept = flash_qkv_attention_fwd(x, wq, wo, None, **opts)
            g = jnp.full_like(y, 1e-3)
            return (y,) + flash_qkv_attention_bwd(x, wq, wo, None, *kept, g,
                                                  **opts)[:3]

        def plain(x, wq, wo):
            y, vjp = jax.vjp(lambda *a: _plain_qkv(
                *a, None, h, dh ** -0.5, True), x, wq, wo)
            return (y,) + vjp(jnp.full_like(y, 1e-3))

        got = jax.jit(op)(x, w_qkv, w_out)
        want = jax.jit(plain)(x, w_qkv, w_out)
        for i, (a, b_) in enumerate(zip(got, want)):
            a = np.asarray(a.astype(jnp.float32))
            b_ = np.asarray(b_.astype(jnp.float32))
            assert np.abs(a - b_).max() < 0.05 * (np.abs(b_).max() + 1e-6), i

    def test_hw_prng_dropout_deterministic(self):
        """Same seed => bit-identical output (fwd/bwd tile regeneration
        is the whole correctness story of the hw-PRNG path)."""
        rng = np.random.RandomState(1)
        b, t, h, dh, dm = 2, 256, 8, 64, 512
        x = jnp.asarray((rng.randn(b, t, dm) * 0.2).astype("float32"))
        w_qkv = _mk(rng, dm, 3 * h * dh, s=0.04)
        w_out = _mk(rng, h * dh, dm, s=0.04)
        seed = jnp.asarray([99], jnp.uint32)
        f = jax.jit(lambda *a: flash_qkv_attention(
            *a, n_head=h, scale=dh**-0.5, dropout_rate=0.1,
            dropout_seed=seed))
        a = np.asarray(f(x, w_qkv, w_out))
        b_ = np.asarray(f(x, w_qkv, w_out))
        np.testing.assert_array_equal(a, b_)
