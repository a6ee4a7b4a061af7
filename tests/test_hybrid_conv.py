"""The hybrid convolution / attention decoder (models/hybrid_conv_decoder.py):
gated short-convolution blocks beside causal grouped-query attention
blocks by a published table, leading dense blocks, a sigmoid router with a
choice-only expert bias, a head tied to the embedding — at small widths on
the CPU with seeded random weights, against the benchmark's plain
reference (perfbench/configs/lfm2_8b_a1b_ep4_reference.py, loaded by its
path) and hand-written jax.numpy.

Tolerances: float32 programs against a float32 reference at the highest
matmul precision differ by summation order only (1e-5 relative on losses,
1e-4 of a leaf's norm on gradients); interpreted kernels against the XLA
composition the same (1e-5 absolute on O(1) results)."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers, monitor
from paddle_tpu.core import backward
from paddle_tpu.kernels import attention as A
from paddle_tpu.kernels import short_conv as SC
from paddle_tpu.layers import contrib
from paddle_tpu.models import hybrid_conv_decoder as M
from paddle_tpu.models import mla_moe_decoder as MM
from paddle_tpu.ops import llm_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
import registry  # noqa: E402  (perfbench's: finds a cell's files by name)

R = registry.load_module(os.path.join(
    ROOT, "perfbench", "configs", "lfm2_8b_a1b_ep4_reference.py"))
DOTS = R.B.Dots("f32")  # float32 products at the highest precision

#: the published table's first layers: conv + dense, attention + experts,
#: two conv + experts; 2 query heads over 1 key/value head of 64; this
#: "chip" holds experts 2..3 of 8; the table is longer than the stack
CFG = {
    "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 1,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 2,
    "router_experts": 8, "expert_offset": 2, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1, "num_hidden_layers": 4,
    "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "vocab_size": 211, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "initializer_range": 0.02, "router_bias_std": 0.01,
}
BATCH, SEQ = 2, 48  # 48 rows: three 16-row blocks of the kernels' walk


def _build(cfg=CFG, with_optimizer=False, amp=False, seq=SEQ):
    heads = cfg["num_attention_heads"]
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _ = M.build_train_net(
            vocab_size=cfg["vocab_size"], seq_len=seq, batch=BATCH,
            layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
            num_dense_layers=cfg["num_dense_layers"],
            d_model=cfg["hidden_size"], n_head=heads,
            n_kv_head=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // heads,
            conv_taps=cfg["conv_L_cache"],
            d_ff_dense=cfg["intermediate_size"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_experts=cfg["router_experts"], n_held=cfg["num_experts"],
            expert_offset=cfg["expert_offset"],
            top_k=cfg["num_experts_per_tok"], rope_theta=cfg["rope_theta"],
            rms_eps=cfg["norm_eps"], init_std=cfg["initializer_range"],
            bias_std=cfg["router_bias_std"], with_optimizer=with_optimizer,
            train_router=cfg.get("router_trained", True))
        grads = [] if with_optimizer else backward.append_backward(loss)
    if amp:
        pt.amp.enable(prog)
    return prog, startup, loss, grads


def _weights(cfg, seed=0):
    """The reference's leaves drawn as the benchmark draws them; the
    routers' ten times wider, so that the scores spread and a choice does
    not hang on rounding."""
    out = {}
    for i, (name, shape, kind, _) in enumerate(R.leaves(cfg, None)):
        key = jax.random.fold_in(jax.random.key(seed), i)
        if kind == "ones":  # off one, so that a norm's scale matters
            out[name] = 1.0 + 0.1 * jax.random.normal(key, shape)
        else:
            out[name] = float(kind.split(":")[1]) * jax.random.normal(
                key, shape) * (10.0 if "router_w" in name else 1.0)
    return out


def _feed(cfg, seed=1, seq=SEQ):
    rng = np.random.default_rng(seed)
    return {"ids": rng.integers(0, cfg["vocab_size"],
                                (BATCH, seq + 1, 1)).astype(np.int32),
            "loss_weight": rng.random((BATCH, seq, 1)).astype(np.float32)}


def _ref_loss(cfg, params, feed):
    block = {k: jnp.asarray(v) for k, v in feed.items()}
    return R.loss_sum(DOTS, cfg, params, block) / jnp.sum(
        block["loss_weight"])


def _run(prog, startup, params, feed, fetch):
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    assert [p.name for p in prog.global_block().all_parameters()] == list(
        params)
    for name, value in params.items():
        scope.set_var(name, value)
    return exe.run(prog, feed=feed, scope=scope, fetch_list=fetch)


@pytest.fixture(scope="module")
def trained_pair():
    params, feed = _weights(CFG), _feed(CFG)
    prog, startup, loss, grads = _build(CFG)
    outs = _run(prog, startup, params, feed, [loss] + [g for _, g in grads])
    got = {p.name: np.asarray(g) for (p, _), g in zip(grads, outs[1:])}
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: _ref_loss(CFG, p, feed))(params)
    return float(np.asarray(outs[0]).reshape(())), got, float(
        ref_loss), ref_grads


# (a) the program against the plain reference -------------------------------


def test_program_loss_follows_the_reference(trained_pair):
    loss, _, ref_loss, _ = trained_pair
    assert abs(loss - ref_loss) < 1e-5 * abs(ref_loss)


def test_program_parameters_are_the_reference_leaves_in_order():
    prog = _build()[0]
    params = prog.global_block().all_parameters()
    assert [(p.name, tuple(p.shape), bool(p.trainable)) for p in params] == [
        (n, tuple(s), t) for n, s, _, t in R.leaves(CFG, None)]
    # one vocabulary leaf: the head is the embedding
    assert [p.name for p in params if tuple(p.shape)[0] == 211] == [
        "embed_w"]


@pytest.mark.parametrize(
    "leaf", [n for n, _, _, t in R.leaves(CFG, None) if t])
def test_program_gradient_follows_the_reference(trained_pair, leaf):
    _, grads, _, ref_grads = trained_pair
    ref = np.asarray(ref_grads[leaf])
    scale = max(float(np.linalg.norm(ref)), 1e-6)
    assert np.linalg.norm(grads[leaf] - ref) < 1e-4 * scale, leaf


def test_tied_leaf_gradient_is_the_look_up_s_plus_the_head_s(trained_pair):
    """embed_w is read by `lookup_table` and by the logits' product; the
    program's one gradient for it is the sum of both parts, each of which
    the reference gives alone when the two uses are told apart."""
    _, grads, _, _ = trained_pair
    params, feed = _weights(CFG), _feed(CFG)
    ids = jnp.asarray(feed["ids"][..., 0])
    w = jnp.asarray(feed["loss_weight"][..., 0])

    def loss(e_lookup, e_head):
        x = R.hidden_states(DOTS, CFG, dict(params, embed_w=e_lookup),
                            ids[:, :-1])
        final = R.rms_norm(x, params["final_norm.scale"], CFG["norm_eps"])
        return R.B.weighted_cross_entropy_sum(
            DOTS.mm(final, e_head.T), ids[:, 1:], w) / jnp.sum(w)

    g_lookup, g_head = jax.grad(loss, argnums=(0, 1))(
        params["embed_w"], params["embed_w"])
    total = np.asarray(g_lookup + g_head)
    assert min(float(jnp.linalg.norm(g)) for g in (g_lookup, g_head)) \
        > 0.05 * np.linalg.norm(total)
    assert np.linalg.norm(grads["embed_w"] - total) \
        < 1e-4 * np.linalg.norm(total)
    ops = [op.type for op in _build()[0].global_block().ops]
    assert ops.count("lookup_table_grad") == 1
    assert ops.count("matmul_grad") == 1


def test_amp_step_trains_and_stays_near_float32():
    losses = {}
    for amp in (False, True):
        prog, startup, loss, _ = _build(with_optimizer=True, amp=amp)
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(startup, scope=scope)
        for name, value in _weights(CFG).items():
            scope.set_var(name, value)
        feed = {k: np.stack([v] * 3) for k, v in _feed(CFG).items()}
        (out,) = exe.run_steps(prog, feed=feed, fetch_list=[loss],
                               scope=scope)
        losses[amp] = np.asarray(out).reshape(-1)
    assert np.isfinite(losses[True]).all()
    assert losses[False][-1] < losses[False][0]  # Adam moves it down
    # bfloat16 activations: 2e-2 relative on a loss of about ln(211)
    np.testing.assert_allclose(losses[True], losses[False], rtol=2e-2)


# (b) the table of operators ------------------------------------------------


def test_layer_types_and_num_dense_layers_build_the_published_pattern():
    """LFM2-8B-A1B's first six layers: conv + dense, conv + dense,
    attention + experts, three conv + experts."""
    cfg = dict(CFG, num_hidden_layers=6, num_dense_layers=2, layer_types=[
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv"])
    prog = _build(cfg)[0]
    names = [p.name for p in prog.global_block().all_parameters()]
    kinds = []
    for i in range(6):
        mine = {n.split(".", 1)[1] for n in names
                if n.startswith(f"layer{i}.")}
        kinds.append(("conv" if "conv_w" in mine else "attention"
                      if "q_w" in mine else "?",
                      "dense" if "gate_up_w" in mine else "experts"
                      if "router_w" in mine else "?"))
        assert ("conv_w" in mine) != ("q_w" in mine)
        assert ("gate_up_w" in mine) != ("experts_down_w" in mine)
    assert kinds == [("conv", "dense"), ("conv", "dense"),
                     ("attention", "experts")] + [("conv", "experts")] * 3
    assert not [n for n in names if n.startswith("layer6")]
    ops = [op for op in prog.global_block().ops]
    types = [op.type for op in ops]
    assert types.count("short_conv") == 5
    assert types.count("moe_experts") == 4 and types.count("swiglu") == 2
    (attn,) = [op for op in ops if op.type == "fused_attention"]
    assert attn.attrs["causal"] is True and attn.attrs["fmt"] == "bhtd"
    assert "mask" not in attn.attrs
    ropes = [op for op in ops if op.type == "rope"]
    assert [(op.attrs["pairing"], op.attrs.get("period", 0))
            for op in ropes] == [("half", 0)] * 2
    routers = [op for op in ops if op.type == "moe_router"]
    assert {op.attrs["norm_eps"] for op in routers} == {1e-6}
    assert {op.attrs["n_experts"] for op in ops
            if op.type == "moe_experts"} == {8}


def test_builder_refuses_an_operator_it_does_not_know():
    with pytest.raises(ValueError, match="sliding"):
        _build(dict(CFG, layer_types=["conv", "sliding", "conv", "conv"]))


# (c) the gated short convolution -------------------------------------------


def _three_shifts(x, w):
    """C * (w2 z[t] + w1 z[t-1] + w0 z[t-2]), z = B * x, by hand."""
    d = w.shape[0]
    gate_b, gate_c, xx = x[..., :d], x[..., d:2 * d], x[..., 2 * d:]
    z = gate_b * xx
    z1 = jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    z2 = jnp.pad(z, ((0, 0), (2, 0), (0, 0)))[:, :-2]
    return gate_c * (w[:, 2] * z + w[:, 1] * z1 + w[:, 0] * z2)


def _conv_operands(b, t, d, seed=0, taps=3):
    k = jax.random.key(seed)
    return (jax.random.normal(k, (b, t, 3 * d), jnp.float32),
            jax.random.normal(jax.random.fold_in(k, 1), (d, taps)),
            jax.random.normal(jax.random.fold_in(k, 2), (b, t, d)))


def _conv_op(x, w, g=None):
    """The short_conv op (and, with a cotangent g, its grad op's X@GRAD
    and Filter@GRAD) through a program."""
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        out = contrib.short_conv(xv, taps=w.shape[1],
                                 param_attr=pt.ParamAttr(name="f"))
        fetch = [out]
        if g is not None:
            gv = layers.data(name="g", shape=list(g.shape), dtype="float32",
                             append_batch_size=False)
            loss = layers.reduce_sum(layers.elementwise_mul(out, gv))
            pairs = backward.append_backward(loss)
            fetch += [dict((p.name, gr) for p, gr in pairs)["f"],
                      prog.global_block().var("x@GRAD")]
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    scope.set_var("f", w)
    feed = {"x": np.asarray(x)}
    if g is not None:
        feed["g"] = np.asarray(g)
    return [np.asarray(v) for v in exe.run(prog, feed=feed, scope=scope,
                                           fetch_list=fetch)], prog


@pytest.mark.parametrize("t", [48, 45])
def test_short_conv_op_is_the_three_shift_form_and_its_grad_jax_s(t):
    """t 48 walks three blocks of the kernels (interpreted on the CPU); t
    45 is no whole 16-row tile, the plan rejects and the XLA composition
    runs: the same numbers."""
    x, w, g = _conv_operands(2, t, 128)
    (out, dw, dx), prog = _conv_op(x, w, g)
    np.testing.assert_allclose(out, _three_shifts(x, w), atol=1e-5)
    ref_dx, ref_dw = jax.vjp(_three_shifts, x, w)[1](g)
    np.testing.assert_allclose(dx, ref_dx, atol=2e-5)
    np.testing.assert_allclose(dw, ref_dw, rtol=1e-5, atol=1e-4)
    assert "short_conv_grad" in [op.type for op in prog.global_block().ops]


@pytest.mark.parametrize("b,t,d,taps", [(2, 80, 128, 3), (1, 160, 256, 3),
                                        (2, 48, 128, 4)])
def test_short_conv_kernels_follow_the_xla_composition(b, t, d, taps):
    """Forward and backward, at t that is no multiple of the largest
    block (80 = 5 x 16, 160 = 5 x 32, 48 = 3 x 16): blocks with a halo on
    both sides, on one side and, per batch row, on neither."""
    x, w, g = _conv_operands(b, t, d, seed=3, taps=taps)
    ok, rows, _, _ = SC._plan(x, w, True)
    assert ok and t % rows == 0 and t // rows >= 3
    np.testing.assert_allclose(SC.short_conv(x, w, interpret=True),
                               SC.reference_short_conv(x, w), atol=1e-5)
    dx, dw = SC.short_conv_bwd(x, w, g, interpret=True)
    ref_dx, ref_dw = SC.reference_short_conv_bwd(x, w, g)
    np.testing.assert_allclose(dx, ref_dx, atol=2e-5)
    np.testing.assert_allclose(dw, ref_dw, rtol=1e-5, atol=1e-4)
    if taps == 3:
        want_dx, want_dw = jax.vjp(_three_shifts, x, w)[1](g)
        np.testing.assert_allclose(ref_dx, want_dx, atol=2e-5)
        np.testing.assert_allclose(ref_dw, want_dw, rtol=1e-5, atol=1e-4)


def test_short_conv_kernels_keep_bfloat16_at_the_boundary():
    x, w, g = _conv_operands(1, 64, 128, seed=5)
    xb, gb = x.astype(jnp.bfloat16), g.astype(jnp.bfloat16)
    out = SC.short_conv(xb, w, interpret=True)
    dx, dw = SC.short_conv_bwd(xb, w, gb, interpret=True)
    assert (out.dtype, dx.dtype, dw.dtype) == (
        jnp.bfloat16, jnp.bfloat16, jnp.float32)
    want = _three_shifts(xb.astype(jnp.float32), w)
    # one rounding of an O(1) result to 8 bits of mantissa
    np.testing.assert_allclose(out.astype(jnp.float32), want, atol=0.05,
                               rtol=2 ** -8)


@pytest.mark.parametrize("interpret", [True, None])
def test_short_conv_is_causal_and_keeps_rows_apart(interpret):
    """The output at t does not move when inputs after t do; positions 0
    and 1 see zeros before the row's start; a batch row does not see its
    neighbour (`interpret` None: the XLA composition at t 45)."""
    t = 48 if interpret else 45
    x, w, _ = _conv_operands(2, t, 128, seed=7)

    def run(x):
        if interpret:
            return np.asarray(SC.short_conv(x, w, interpret=True))
        return np.asarray(SC.reference_short_conv(x, w))

    base = run(x)
    later = run(x.at[:, 20:].add(1.0))
    np.testing.assert_array_equal(later[:, :20], base[:, :20])
    assert np.abs(later[:, 20:23] - base[:, 20:23]).min() > 0
    d = 128
    gate_b, gate_c, xx = x[..., :d], x[..., d:2 * d], x[..., 2 * d:]
    z = gate_b * xx
    np.testing.assert_allclose(base[:, 0], gate_c[:, 0] * w[:, 2] * z[:, 0],
                               atol=1e-5)
    np.testing.assert_allclose(
        base[:, 1], gate_c[:, 1] * (w[:, 2] * z[:, 1] + w[:, 1] * z[:, 0]),
        atol=1e-5)
    other = run(x.at[0].add(1.0))
    np.testing.assert_array_equal(other[1], base[1])
    assert np.abs(other[0] - base[0]).max() > 0


def test_short_conv_sites_are_counted_where_an_executor_lowers_them():
    def counts():
        phases = monitor.compile_phases()
        return (phases["short_conv_sites_kernel"],
                phases["short_conv_sites_xla"])

    k0, x0 = counts()
    for seq, where in ((SEQ, 0), (40, 1)):  # 40 rows: no 16-row tiles
        prog, startup, loss, _ = _build(with_optimizer=True, seq=seq)
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(startup, scope=scope)
        feed = {k: v[None] for k, v in _feed(CFG, seq=seq).items()}
        exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
        now = counts()
        assert (now[0] - k0, now[1] - x0) == ((3, 0), (3, 3))[where]


# (d) causal grouped-query attention at heads of 64 ---------------------------


def test_causal_gqa_flash_kernels_at_heads_of_64():
    """4 query heads over 2 key/value heads of 64, causal, in two blocks
    a walk: the interpreted kernels against a masked softmax, forward and
    all three gradients."""
    k = jax.random.key(11)
    q = jax.random.normal(k, (2, 4, 256, 64))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (2, 2, 256, 64))
    v = jax.random.normal(jax.random.fold_in(k, 2), (2, 2, 256, 64))
    g = jax.random.normal(jax.random.fold_in(k, 3), (2, 4, 256, 64))
    ok, bq, bk, _ = A._plan(q, kk, 128, 128, True, "bhtd", v=v)
    assert ok and (bq, bk) == (128, 128)

    def masked_softmax(q, kk, v):
        kr, vr = (jnp.repeat(a, 2, axis=1) for a in (kk, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kr,
                       precision="highest") * 64 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vr,
                          precision="highest")

    def flash(q, kk, v):
        return A.flash_attention(q, kk, v, scale=64 ** -0.5, causal=True,
                                 block_q=128, block_k=128, interpret=True)

    np.testing.assert_allclose(flash(q, kk, v), masked_softmax(q, kk, v),
                               atol=2e-5)
    got = jax.vjp(flash, q, kk, v)[1](g)
    want = jax.vjp(masked_softmax, q, kk, v)[1](g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


# (e) the router ------------------------------------------------------------


def _router(x, w, bias=None, top_k=4, **attrs):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        idx, weight = contrib.moe_router(
            xv, w.shape[1], top_k, param_attr=pt.ParamAttr(name="w"),
            bias_attr=pt.ParamAttr(name="b") if bias is not None else False,
            **attrs)
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    scope.set_var("w", w)
    if bias is not None:
        scope.set_var("b", bias)
    got = exe.run(prog, feed={"x": x}, scope=scope, fetch_list=[idx, weight])
    return [np.asarray(a) for a in got], prog


def _router_operands():
    rng = np.random.default_rng(5)
    return (rng.standard_normal((2, 24, 32)).astype(np.float32),
            rng.standard_normal((32, 8)).astype(np.float32) * 0.5)


def test_expert_bias_enters_the_choice_only():
    """An expert the bias lifts is chosen by every token, and weighted by
    its own score among the chosen as if there were no bias; the weights
    are the chosen scores over their sum plus 1e-6."""
    x, w = _router_operands()
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0
    (idx, weight), prog = _router(x, w, bias, norm_eps=1e-6)
    scores = np.asarray(jax.nn.sigmoid(jnp.matmul(
        x.reshape(-1, 32), w, precision="highest")))
    assert (idx == 5).any(axis=1).all()
    np.testing.assert_array_equal(
        np.sort(idx, axis=1),
        np.sort(np.argsort(-(scores + bias), axis=1)[:, :4], axis=1))
    chosen = np.take_along_axis(scores, idx, axis=1)
    np.testing.assert_allclose(
        weight, chosen / (chosen.sum(axis=1, keepdims=True) + 1e-6),
        rtol=1e-6)
    (b,) = [p for p in prog.global_block().all_parameters() if p.name == "b"]
    assert not b.trainable and b.stop_gradient


def test_router_norm_eps_defaults_to_what_it_was_bit_for_bit():
    """A router that states no `norm_eps` (the latent-attention stack's
    and the block-diffusion stack's) computes chosen * (scale / (sum +
    1e-20)) as before the attribute existed; with one the sum carries it."""
    x, w = _router_operands()
    (idx, weight), prog = _router(x, w)
    (op,) = [op for op in prog.global_block().ops if op.type == "moe_router"]
    assert "norm_eps" not in op.attrs
    scores = jax.nn.sigmoid(jax.lax.dot_general(
        jnp.asarray(x).reshape(-1, 32), jnp.asarray(w),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST))
    chosen = jnp.take_along_axis(scores, jnp.asarray(idx), axis=1)
    was = chosen * (1.0 / (jnp.sum(chosen, axis=1, keepdims=True) + 1e-20))
    np.testing.assert_array_equal(weight, np.asarray(was))
    (_, with_eps), _ = _router(x, w, norm_eps=0.5)
    np.testing.assert_allclose(
        with_eps, np.asarray(chosen / (jnp.sum(chosen, axis=1, keepdims=True)
                                       + 0.5)), rtol=1e-6)
    # the two stacks in the benchmark state none
    net = MM._Net(d_model=32, n_experts=8, top_k=2, routed_scale=1.0,
                  bias_std=0.0, n_held=2, d_ff_expert=16, expert_offset=0,
                  n_shared=0, init_std=0.02)
    prog2, startup2 = pt.Program(), pt.Program()
    with pt.program_guard(prog2, startup2):
        MM.moe_ffn(net, layers.data(name="x", shape=[2, 24, 32],
                                    dtype="float32",
                                    append_batch_size=False), "m")
    assert ["norm_eps" in op.attrs for op in prog2.global_block().ops
            if op.type == "moe_router"] == [False]


# (f) the walk's chunk follows the share ---------------------------------------


@pytest.mark.parametrize("held,routed,pairs,parts,trips_at_mean", [
    (16, 256, 4096 * 8, 4, 1),   # joyai_flash_ep16_train: a sixteenth
    (16, 128, 8192 * 8, 4, 1),   # sdar_30b_a3b_ep8_train: an eighth
    (8, 32, 8192 * 4, 2, 1),     # lfm2_8b_a1b_ep4_train: a quarter
    (32, 32, 8192 * 4, 4, 4),    # every expert held: four trips
])
def test_chunk_of_the_walk_follows_the_share_held(held, routed, pairs, parts,
                                                  trips_at_mean):
    rows = pairs // parts
    assert llm_ops.chunk_rows(pairs, held, routed) == rows
    mean = pairs * held // routed
    assert llm_ops.rows_walked(mean, pairs, held, routed) \
        == trips_at_mean * rows
    # twice the mean load still takes one trip of a share's chunk
    if held != routed:
        assert llm_ops.rows_walked(min(2 * mean, rows), pairs, held,
                                   routed) == rows
        assert llm_ops.rows_walked(rows + 1, pairs, held, routed) == 2 * rows
    load = jnp.full((held,), mean // held, jnp.int32)
    walk = llm_ops._walk(load, jnp.arange(pairs, dtype=jnp.int32), routed)
    assert (walk[0], int(walk[3])) == (rows, trips_at_mean)


def test_the_expert_op_is_told_the_routers_width():
    """No second path for a caller that leaves the share out: the layer
    takes `n_experts` by position and the op reads it with no default."""
    import inspect

    from paddle_tpu.layers import contrib

    for fn, names in ((llm_ops.chunk_rows, ("held", "routed")),
                      (llm_ops.rows_walked, ("held", "routed")),
                      (llm_ops._walk, ("routed",)),
                      (contrib.moe_experts, ("n_experts",))):
        params = inspect.signature(fn).parameters
        assert all(params[n].default is inspect.Parameter.empty
                   for n in names), fn


def test_shares_add_up_to_the_uncut_expert_layer():
    """What the 4 chips of a 4-way expert-parallel layer give (no shared
    expert: nothing is computed alike on every chip but the router) is the
    uncut reference's layer output; the program's share is the
    reference's share."""
    cfg = dict(CFG, num_experts=8, router_experts=8, expert_offset=0)
    rng = np.random.default_rng(3)
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 8
    x = rng.standard_normal((BATCH, SEQ, d)).astype(np.float32)
    P = {"m.router_w": rng.standard_normal((d, e)).astype(np.float32) * 0.3,
         "m.router_bias": rng.standard_normal(e).astype(np.float32) * 0.1,
         "m.experts_gate_up_w": rng.standard_normal(
             (e, d, 2 * f)).astype(np.float32) * 0.1,
         "m.experts_down_w": rng.standard_normal(
             (e, f, d)).astype(np.float32) * 0.1}
    P = {k: jnp.asarray(v) for k, v in P.items()}
    whole = R.moe(DOTS, cfg, jnp.asarray(x), P, "m")

    def share_of(offset):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            xv = layers.data(name="x", shape=[BATCH, SEQ, d],
                             dtype="float32", append_batch_size=False)
            net = MM._Net(
                d_model=d, n_experts=e, top_k=cfg["num_experts_per_tok"],
                routed_scale=1.0, bias_std=0.0, n_held=2, d_ff_expert=f,
                expert_offset=offset, n_shared=0, init_std=0.02,
                router_norm_eps=1e-6)
            out = MM.moe_ffn(net, xv, "m")
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(startup, scope=scope)
        mine = {k: v[offset:offset + 2] if "experts" in k else v
                for k, v in P.items()}
        for k, v in mine.items():
            scope.set_var(k, v)
        (got,) = exe.run(prog, feed={"x": x}, scope=scope, fetch_list=[out])
        want = R.moe(DOTS, cfg, jnp.asarray(x), mine, "m", offset=offset)
        np.testing.assert_allclose(got, want, atol=2e-5)
        return np.asarray(got)

    parts = [share_of(o) for o in range(0, 8, 2)]
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    assert min(float(np.abs(p).max()) for p in parts) > 1e-3


# the router-flip diagnostic (tools/router_flips.py) -------------------------


def test_reference_routed_as_it_chose_itself_is_unchanged():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import router_flips as flips

    params, feed = _weights(CFG), _feed(CFG)
    own = flips.hybrid_conv_choices(R, DOTS, CFG, params,
                                    jnp.asarray(feed["ids"][..., 0]))
    assert len(own) == 3  # the layers after the dense block
    k = CFG["num_experts_per_tok"]
    for idx, margin in own:
        assert idx.shape == (BATCH, SEQ, k)
        assert margin.shape == (BATCH, SEQ)
        assert float(jnp.min(margin)) >= 0.0
    route_as = np.stack([np.asarray(idx) for idx, _ in own], axis=1)
    want = float(_ref_loss(CFG, params, feed))
    got = float(_ref_loss(CFG, params, dict(feed, route_as=route_as)))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    other = (route_as + 1) % CFG["router_experts"]
    moved = float(_ref_loss(CFG, params, dict(feed, route_as=other)))
    assert abs(moved - want) > 1e-5 * abs(want)
    # the program's routers, in the reference's order
    prog = _build()[0]
    routers = [op for op in prog.global_block().ops
               if op.type == "moe_router"]
    assert [op.input("W")[0] for op in routers] == [
        f"layer{i}.router_w" for i in (1, 2, 3)]
    assert flips.router_outputs(prog) == [
        op.output("TopkIdx")[0] for op in routers]


# (g) routes, counters, the benchmark's files ---------------------------------


def test_new_and_changed_ops_take_the_direct_grad_route():
    prog, startup, loss, _ = _build(with_optimizer=True)
    ops = [op.type for op in prog.global_block().ops]
    assert ops.count("short_conv_grad") == 3
    assert ops.count("fused_attention_grad") == 1
    assert ops.count("moe_router_grad") == 3
    assert ops.count("moe_experts_grad") == 3
    assert ops.count("swiglu_grad") == 1
    assert ops.count("rope_grad") == 2
    assert ops.count("rms_norm_grad") == 2 * 4 + 2 + 1
    # every one of them from its forward's residuals: the flash kernels
    # run interpreted at this size, so fused_attention wrote its Lse
    direct = sum(ops.count(t) for t in (
        "short_conv_grad", "fused_attention_grad", "moe_router_grad",
        "moe_experts_grad", "swiglu_grad", "rope_grad", "rms_norm_grad"))
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    before = monitor.compile_phases()
    feed = {k: v[None] for k, v in _feed(CFG).items()}
    exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    after = monitor.compile_phases()
    assert after["grad_direct"] - before["grad_direct"] == direct


def test_device_counters_carry_the_quarter_share_s_walk():
    prog = _build(with_optimizer=True)[0]
    assert set(prog._device_counters) == {
        "moe_local_pairs", "moe_max_over_mean", "moe_rows_walked"}


def test_cell_files_state_the_cut():
    cell = registry.load_cell("lfm2_8b_a1b_ep4_train")
    cfg, traffic = cell.cfg, cell.traffic
    # published widths; the three cuts are in `reduced`
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["router_experts"],
            cfg["num_experts_per_tok"], cfg["conv_L_cache"],
            cfg["rope_theta"], cfg["norm_eps"]) == (
        2048, 32, 8, 7168, 1792, 32, 4, 3, 1000000, 1e-5)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 8, 16384)
    assert len(cfg["layer_types"]) == 24  # the published table, whole
    assert R.layer_types(cfg) == ["conv", "conv", "full_attention", "conv",
                                  "conv", "conv"]
    assert (traffic["batch"], traffic["ids_len"] - traffic["seq_len"],
            traffic["steps_per_call"], traffic["learning_rate"],
            traffic["feed_pool"]) == (2, 1, 8, 1e-4, 4)
    assert "weights_seed" in cfg and "data_seed" in traffic
    leaves = R.leaves(cfg, traffic)
    assert sum(int(np.prod(s)) for _, s, _, _ in leaves) == 568_647_936


def test_program_file_builds_the_cell_s_program():
    cell = registry.load_cell("lfm2_8b_a1b_ep4_train")
    tiny = dict(cell.cfg, hidden_size=128, num_attention_heads=2,
                num_key_value_heads=1, intermediate_size=96,
                moe_intermediate_size=32, num_experts=2, router_experts=8,
                vocab_size=211, amp=True)
    traffic = dict(cell.traffic, seq_len=48, ids_len=49)
    program = registry.load_module(cell.path(cell.cfg["program"]))
    prog, _, _ = program.build(tiny, traffic)
    types = [op.type for op in prog.global_block().ops]
    assert types.count("short_conv") == 5
    assert types.count("fused_attention") == 1
    assert types.count("moe_experts") == 4
    assert [(p.name, tuple(p.shape)) for p in
            prog.global_block().all_parameters()] == [
        (n, tuple(s)) for n, s, _, _ in R.leaves(tiny, traffic)]
    with pytest.raises(ValueError, match="seq_len"):
        program.build(tiny, dict(traffic, ids_len=50))
