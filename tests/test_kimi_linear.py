"""Kimi Linear's pieces (kernels/kda.py, the plain form of
kernels/short_conv.py, the "kda" and "mla" rows of
models/hybrid_conv_decoder.py) at small widths on the CPU with seeded
random weights, against the token-by-token recurrence written here, against
hand-written jax.numpy and against the benchmark's plain reference
(perfbench/configs/kimi_linear_48b_a3b_ep32_reference.py, loaded by its
path).

Tolerances: the chunked form against the recurrence in float32 differs by
summation order only (2e-6 of the largest output or gradient); float32
programs against the float32 reference at the highest matmul precision the
same (1e-5 relative on losses, 1e-4 on gradients of the larger of a leaf's
norm and the median leaf's, perfbench/compare.py's rule: the decay's
A_log and dt_bias carry gradients a thousandth of the median's, summed
over every channel and token with cancellation);
interpreted kernels against the XLA composition 1e-5 absolute on O(1)
results."""

import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers, monitor
from paddle_tpu.core import backward
from paddle_tpu.kernels import kda as K
from paddle_tpu.kernels import short_conv as SC
from paddle_tpu.layers import contrib
from paddle_tpu.models import hybrid_conv_decoder as M
from paddle_tpu.models import mla_moe_decoder as MM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
import registry  # noqa: E402  (perfbench's: finds a cell's files by name)

R = registry.load_module(os.path.join(
    ROOT, "perfbench", "configs", "kimi_linear_48b_a3b_ep32_reference.py"))
PROGRAM = registry.load_module(os.path.join(
    ROOT, "perfbench", "configs", "kimi_linear_48b_a3b_ep32_program.py"))
DOTS = R.B.Dots("f32")  # float32 products at the highest precision

#: published layers 1-5 at small widths: KDA + dense, two KDA + experts,
#: MLA + experts, KDA + experts; 2 KDA heads of 16, 2 MLA heads (d_qk 12,
#: d_v 8); this "chip" holds experts 2..3 of 8 and a shared expert
CFG = {
    "hidden_size": 64, "num_attention_heads": 2, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_experts": 2, "router_experts": 8,
    "expert_offset": 2, "num_experts_per_token": 4,
    "routed_scaling_factor": 2.446, "num_shared_experts": 1,
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "linear_attn_config": {"full_attn_layers": [4, 8], "head_dim": 16,
                           "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "vocab_size": 211, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "initializer_range": 0.02, "router_bias_std": 0.01,
    "kda_gate_rank": 8, "a_log_std": 2.0,
}
BATCH, SEQ = 2, 48  # 48 rows: one chunk of the op's 128, padded


def _recurrence(q, k, v, g, beta):
    """The published recurrence a token at a time, float32: q, k, g [b, t,
    h*dk], v [b, t, h*dv], beta [b, t, h] -> o [b, t, h*dv]."""
    b, t, heads = beta.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads

    def split(x, d):
        return jnp.moveaxis(x.reshape(b, t, heads, d), 1, 0)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k = l2(split(q, dk)) * dk ** -0.5, l2(split(k, dk))

    def step(s, xs):  # s [b, h, dk, dv]
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None]
        s = s - bt[..., None, None] * kt[..., :, None] * jnp.einsum(
            "bhk,bhkv->bhv", kt, s)[..., None, :]
        s = s + bt[..., None, None] * kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s)

    _, o = jax.lax.scan(step, jnp.zeros((b, heads, dk, dv)),
                        (q, k, split(v, dv), split(g, dk),
                         jnp.moveaxis(beta, 1, 0)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, heads * dv)


def _kda_operands(b, t, heads, dk, dv, gate_scale, seed=0):
    """q, k, v, the log-decay g = -gate_scale * softplus(N(0, 1)) and
    beta = sigmoid(N(0, 1)), float32."""
    key = jax.random.key(seed)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    return (normal(0, (b, t, heads * dk)), normal(1, (b, t, heads * dk)),
            normal(2, (b, t, heads * dv)),
            -gate_scale * jax.nn.softplus(normal(3, (b, t, heads * dk))),
            jax.nn.sigmoid(normal(4, (b, t, heads))))


def _close(got, want, rel=2e-6):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= rel * scale + 1e-7


# (a) the kda op against the recurrence -------------------------------------

CASES = [(80, 64, 0.3), (150, 128, 0.3), (45, 32, 0.3), (80, 64, 8.0)]


@pytest.mark.parametrize("t,chunk,gate_scale", CASES)
def test_chunked_composition_follows_the_recurrence(t, chunk, gate_scale):
    """Forward and all five gradients, at t no whole number of chunks, at
    chunks of 64, 128 and 32, and at a gate whose log-decay within a chunk
    passes 88 (gate_scale 8); the backward reads the forward's factors."""
    args = _kda_operands(2, t, 2, 32, 16, gate_scale)
    want, vjp = jax.vjp(_recurrence, *args)
    out, states, factors = K.reference_kda(*args, chunk=chunk)
    _close(out, want)
    assert states.shape == (2, 2, -(-t // chunk), 16, 32)
    assert factors.shape == (2, 2, -(-t // chunk), chunk, chunk)
    do = jax.random.normal(jax.random.key(9), want.shape)
    got = K.reference_kda_bwd(*args, states, factors, do, chunk=chunk)
    for name, a, r in zip(("dq", "dk", "dv", "dg", "dbeta"), got, vjp(do)):
        assert a.shape == r.shape, name
        _close(a, r, rel=5e-6)


def test_decay_past_88_in_a_chunk_would_overflow_the_factorised_form():
    q, k, v, g, beta = _kda_operands(1, 64, 1, 32, 16, 8.0)
    cum = jnp.cumsum(g[0], axis=0)  # one chunk of 64
    assert float(jnp.min(cum)) < -88.0
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))  # exp(-G) overflows
    out, _, _ = K.reference_kda(q, k, v, g, beta, chunk=64)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, _recurrence(q, k, v, g, beta))


@pytest.mark.parametrize("chunk", [64, 128])
def test_kernels_follow_the_composition(chunk):
    """The Pallas pair (interpreted) on 128-lane heads, t no whole chunk:
    the backward reads the factors the forward kernel wrote."""
    args = _kda_operands(1, 80, 2, 128, 128, 0.5, seed=1)
    out, states, factors = K.kda(*args, chunk=chunk, interpret=True)
    want_out, want_states, want_factors = K.reference_kda(*args, chunk=chunk)
    _close(out, want_out, rel=1e-5)
    _close(states, want_states, rel=1e-5)
    _close(factors, want_factors, rel=1e-5)
    do = jax.random.normal(jax.random.key(3), (1, 80, 256))
    got = K.kda_bwd(*args, states, factors, do, chunk=chunk, interpret=True)
    want = K.reference_kda_bwd(*args, want_states, want_factors, do,
                               chunk=chunk)
    for a, r in zip(got, want):
        _close(a, r, rel=1e-5)


def _one_chunk(c, dk, dv, gate_scale, seed):
    """q, k, v, g [c, d] and beta [c, 1] of one chunk, float32."""
    q, k, v, g, beta = _kda_operands(1, c, 1, dk, dv, gate_scale, seed)
    return q[0], k[0], v[0], g[0], beta[0]


@pytest.mark.parametrize("vector", [False, True])
def test_factors_are_the_inverse_below_and_aqk_transposed_above(vector):
    """The forward's packed [C, C]: strictly below the diagonal T = (I +
    Diag(b) Akk)^-1 (its unit diagonal implied), on and above it Aqk^T,
    both against a plain dense form; unpacked, T's diagonal is one."""
    c, dk, dv = 32, 16, 8
    q, k, v, g, beta = _one_chunk(c, dk, dv, 0.3, seed=6)
    _, _, packed = K.chunk_fwd(q, k, v, g, beta, jnp.zeros((dv, dk)),
                               dk ** -0.5, vector=vector)
    qn = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + K.L2_EPS)
    kn = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + K.L2_EPS)
    gam = np.cumsum(np.asarray(g, np.float64), axis=0)
    decay = np.exp(np.minimum(gam[:, None, :] - gam[None, :, :], 0.0))
    akk = np.tril(np.einsum("ic,jc,ijc->ij", kn, kn, decay), -1)
    aqk = np.tril(np.einsum("ic,jc,ijc->ij", qn * dk ** -0.5, kn, decay))
    t = np.linalg.inv(np.eye(c) + np.asarray(beta) * akk)
    packed = np.asarray(packed)
    np.testing.assert_allclose(np.tril(packed, -1), np.tril(t, -1),
                               atol=2e-6)
    np.testing.assert_allclose(np.triu(packed), aqk.T, atol=2e-6)
    t_read, aqk_t = K._unpack(jnp.asarray(packed))
    assert np.all(np.diag(np.asarray(t_read)) == 1.0)
    assert np.all(np.triu(np.asarray(t_read), 1) == 0.0)
    np.testing.assert_array_equal(np.asarray(aqk_t), np.triu(packed))


@pytest.mark.parametrize("vector", [False, True])
def test_backward_makes_no_inverse_and_akk_alone(monkeypatch, vector):
    """chunk_bwd traced with `_unit_lower_inverse`, `_factors` and `gram`
    counted: no inverse and one Gram, Akk's (strict); the forward makes
    both of its Gram matrices and the diagonal sub-blocks' inverse in one
    walk (`_factors`), then the inverse below them, and calls no `gram`."""
    made = []

    def counted(name, fn):
        def wrapped(*a, **kw):
            made.append((name, kw.get("strict")))
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(K, "gram", counted("gram", K.gram))
    monkeypatch.setattr(K, "_factors", counted("factors", K._factors))
    monkeypatch.setattr(K, "_unit_lower_inverse",
                        counted("inverse", K._unit_lower_inverse))
    c, dk, dv = 32, 16, 8
    q, k, v, g, beta = _one_chunk(c, dk, dv, 0.3, seed=7)
    h0 = jnp.zeros((dv, dk))
    jax.make_jaxpr(functools.partial(K.chunk_fwd, vector=vector))(
        q, k, v, g, beta, h0, dk ** -0.5)
    assert made == [("factors", None), ("inverse", None)]
    made.clear()
    jax.make_jaxpr(functools.partial(K.chunk_bwd, vector=vector))(
        q, k, v, g, beta, h0, jnp.zeros((c, c)), v, h0, dk ** -0.5)
    assert made == [("gram", True)]


@pytest.mark.parametrize("vector", [False, True])
def test_backward_streams_no_full_product_per_sub_block(vector):
    """chunk_bwd at C = 128: the transposed decay products walk the
    output's row blocks (one [16, C] x [C, d] product a sub-block), so no
    more than 16 of its products have a 128-row result."""
    c = dk = dv = 128
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(functools.partial(K.chunk_bwd, vector=vector))(
        f32((c, dk)), f32((c, dk)), f32((c, dv)), f32((c, dk)), f32((c, 1)),
        f32((dv, dk)), f32((c, c)), f32((c, dv)), f32((dv, dk)), dk ** -0.5)
    rows = [e.outvars[0].aval.shape[0] for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "dot_general"]
    assert 0 < rows.count(c) <= 16
    assert set(rows) == {K.SUB, c}


def _traced_counts(fn, *shapes):
    """(rows of each product's result, number of `exp`s) of fn traced on
    float32 operands of these shapes (the scale dk^-1/2 last)."""
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(fn)(*map(f32, shapes[:-1]), shapes[-1])
    eqns = jaxpr.jaxpr.eqns
    return ([e.outvars[0].aval.shape[0] for e in eqns
             if e.primitive.name == "dot_general"],
            sum(e.primitive.name == "exp" for e in eqns))


@pytest.mark.parametrize("vector", [False, True])
def test_forward_makes_twelve_full_products_and_stacks_the_grams(vector):
    """chunk_fwd at C = 128: the diagonal sub-blocks' inverse comes out of
    the Grams' walk, so 12 products have a 128-row result (18 before); the
    two Grams' rows of a sub-block meet the shared decayed k in one
    32-row product (seven, where there were fourteen of 16 rows); the
    kernels' form makes each decay tile once for both Grams."""
    c = dk = dv = 128
    rows, exps = _traced_counts(
        functools.partial(K.chunk_fwd, vector=vector), (c, dk), (c, dk),
        (c, dv), (c, dk), (c, 1), (dv, dk), dk ** -0.5)
    assert 0 < rows.count(c) <= 12
    assert rows.count(2 * K.SUB) == c // K.SUB - 1
    assert set(rows) == {2 * K.SUB, c}
    if not vector:  # a tile a column of each sub-block, 2 a stacked product
        assert exps == c + 2 * (c // K.SUB - 1) + 3


@pytest.mark.parametrize("vector", [False, True])
def test_backward_products_and_exps_are_unchanged(vector):
    """chunk_bwd at C = 128 keeps its products (14 full, 35 of 16 rows) and
    its `exp`s: the forward's one walk leaves the backward as it was."""
    c = dk = dv = 128
    rows, exps = _traced_counts(
        functools.partial(K.chunk_bwd, vector=vector), (c, dk), (c, dk),
        (c, dv), (c, dk), (c, 1), (dv, dk), (c, c), (c, dv), (dv, dk),
        dk ** -0.5)
    assert (rows.count(c), rows.count(K.SUB), len(rows)) == (14, 35, 49)
    assert exps == (115 if vector else 715)


@pytest.mark.parametrize("vector", [False, True])
@pytest.mark.parametrize("gate_scale", [0.3, 8.0])
@pytest.mark.parametrize("c", [32, 64, 128])
def test_one_walk_is_both_grams_and_the_diagonal_inverse(c, gate_scale,
                                                         vector):
    """`_factors` against dense float64 on the same float32 G: Akk and Aqk
    as sums over every pair, D^-1 as np.linalg.inv of each diagonal
    sub-block of I + Diag(b) Akk; at gate_scale 8 the log-decay passes 88
    in the chunk and all three stay finite."""
    dk = 32
    q, k, _, g, beta = _one_chunk(c, dk, 8, gate_scale, seed=c)
    kn = np.asarray(k, np.float64)
    kn = kn / np.sqrt(np.sum(kn * kn, -1, keepdims=True) + K.L2_EPS)
    qs = np.asarray(q, np.float64)
    qs = qs / np.sqrt(np.sum(qs * qs, -1, keepdims=True) + K.L2_EPS)
    qs = qs * dk ** -0.5
    gam = np.cumsum(np.asarray(g), axis=0, dtype=np.float32)
    assert (float(gam.min()) < -88.0) == (gate_scale == 8.0)
    g64 = gam.astype(np.float64)
    decay = np.exp(np.minimum(g64[:, None, :] - g64[None, :, :], 0.0))
    akk = np.tril(np.einsum("ic,jc,ijc->ij", kn, kn, decay), -1)
    aqk = np.tril(np.einsum("ic,jc,ijc->ij", qs, kn, decay))
    d = np.eye(c) + np.asarray(beta, np.float64) * akk
    dinv = np.zeros((c, c))
    for lo in range(0, c, K.SUB):
        blk = slice(lo, lo + K.SUB)
        dinv[blk, blk] = np.linalg.inv(d[blk, blk])
    got = jax.jit(K._factors, static_argnums=4)(
        jnp.asarray(kn, jnp.float32), jnp.asarray(qs, jnp.float32),
        jnp.asarray(beta), jnp.asarray(gam), vector)
    for name, a, want in zip(("akk", "aqk", "dinv"), got, (akk, aqk, dinv)):
        a = np.asarray(a)
        assert np.all(np.isfinite(a)), name
        assert float(np.max(np.abs(a - want))) <= 1e-6 * float(
            np.max(np.abs(want))), name


@pytest.mark.parametrize("vector", [False, True])
@pytest.mark.parametrize("gate_scale", [0.3, 8.0])
@pytest.mark.parametrize("c", [32, 64, 128])
def test_transposed_decay_product_is_the_sum_over_all_pairs(c, gate_scale,
                                                            vector):
    """decay_mm_t against a dense float64 sum over every pair i >= j of
    p_ij exp(G_i - G_j) x_i, on the same float32 G; at gate_scale 8 the
    log-decay passes 88 in the chunk and the result stays finite."""
    key = jax.random.key(c)

    def normal(i, shape):
        return np.asarray(jax.random.normal(jax.random.fold_in(key, i),
                                            shape), np.float64)

    d = 32
    p, x = np.tril(normal(0, (c, c))), normal(1, (c, d))
    g = -gate_scale * np.logaddexp(0.0, normal(2, (c, d)))
    gam = np.cumsum(g, axis=0).astype(np.float32)
    assert (float(gam.min()) < -88.0) == (gate_scale == 8.0)
    g64 = gam.astype(np.float64)
    decay = np.exp(np.minimum(g64[:, None, :] - g64[None, :, :], 0.0))
    want = np.einsum("ij,ijc,ic->jc", p, decay, x)
    got = np.asarray(K.decay_mm_t(jnp.asarray(p, jnp.float32),
                                  jnp.asarray(x, jnp.float32),
                                  jnp.asarray(gam), vector))
    assert np.all(np.isfinite(got))
    assert float(np.max(np.abs(got - want))) <= 1e-6 * float(
        np.max(np.abs(want)))


def test_plan_takes_the_kernels_on_whole_lane_heads_alone():
    q = jax.ShapeDtypeStruct((2, 4096, 4096), jnp.bfloat16)
    beta = jax.ShapeDtypeStruct((2, 4096, 32), jnp.float32)
    assert K._plan(q, q, beta, 64, True) == (True, True)
    assert not K._plan(q, q, beta, 48, True)[0]  # no chunk the kernels take
    narrow = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16)
    assert not K._plan(narrow, narrow, beta, 64, True)[0]  # heads of 64
    assert not K._plan(q, q, beta, 64, None)[0]  # off the TPU: XLA


def test_kda_is_causal_and_keeps_rows_apart():
    q, k, v, g, beta = _kda_operands(2, 70, 2, 16, 16, 0.5)
    base, _, _ = K.reference_kda(q, k, v, g, beta, chunk=32)
    later = v.at[:, 40:].add(1.0).at[1].add(1.0)
    moved, _, _ = K.reference_kda(q, k, later, g, beta, chunk=32)
    np.testing.assert_allclose(moved[0, :40], base[0, :40], atol=1e-7)
    assert float(jnp.max(jnp.abs(moved[0, 40:] - base[0, 40:]))) > 1e-3


def _kda_program(args, do):
    """The kda op and its grad op through a program: (out, dq, dk, dv, dg,
    dbeta), the program, the compile totals the miss call added."""
    names = ("q", "k", "v", "g", "beta")
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        ins = []
        for n, a in zip(names, args):
            var = layers.data(name=n, shape=list(a.shape), dtype="float32",
                              append_batch_size=False)
            var.stop_gradient = False
            ins.append(var)
        out = contrib.kda(*ins)
        dov = layers.data(name="do", shape=list(do.shape), dtype="float32",
                          append_batch_size=False)
        backward.append_backward(
            layers.reduce_sum(layers.elementwise_mul(out, dov)))
        fetch = [out] + [prog.global_block().var(n + "@GRAD")
                         for n in names]
    feed = dict(zip(names, map(np.asarray, args)), do=np.asarray(do))
    before = monitor.compile_phases()
    got = pt.Executor().run(prog, feed=feed, scope=pt.Scope(),
                            fetch_list=fetch)
    after = monitor.compile_phases()
    return [np.asarray(x) for x in got], prog, {
        k: after[k] - before[k] for k in after}


def test_kda_op_and_its_grad_op_through_a_program():
    """150 rows: a whole chunk of the op's 128 and one padded."""
    args = _kda_operands(2, 150, 2, 16, 16, 0.5, seed=4)
    do = jax.random.normal(jax.random.key(5), (2, 150, 32))
    got, prog, added = _kda_program(args, do)
    want, vjp = jax.vjp(_recurrence, *args)
    _close(got[0], want)
    for a, r in zip(got[1:], vjp(do)):
        _close(a, r, rel=5e-6)
    ops = [op.type for op in prog.global_block().ops]
    assert ops.count("kda") == 1 and ops.count("kda_grad") == 1
    # kda_grad from the forward's operands, nothing run twice (the generic
    # two are the loss's product and sum); the XLA composition
    assert added["grad_direct"] == 1 and added["grad_generic"] == 2
    assert (added["kda_sites_xla"], added["kda_sites_kernel"]) == (1, 0)
    assert added["kda_bwd_sites_kept"] == 1


def test_kda_gate_is_the_published_decay_and_beta():
    prog, startup = pt.Program(), pt.Program()
    rng = np.random.default_rng(0)
    f = rng.standard_normal((2, 5, 8)).astype(np.float32)
    b = rng.standard_normal((2, 5, 2)).astype(np.float32)
    with pt.program_guard(prog, startup):
        fv = layers.data(name="f", shape=[2, 5, 8], dtype="float32",
                         append_batch_size=False)
        bv = layers.data(name="b", shape=[2, 5, 2], dtype="float32",
                         append_batch_size=False)
        g, beta = contrib.kda_gate(fv, bv, pt.ParamAttr(name="a_log"),
                                   pt.ParamAttr(name="dt_bias"))
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    a_log = np.array([0.5, -1.0], np.float32)
    dt = rng.standard_normal(8).astype(np.float32)
    scope.set_var("a_log", a_log)
    scope.set_var("dt_bias", dt)
    got_g, got_b = exe.run(prog, feed={"f": f, "b": b}, scope=scope,
                           fetch_list=[g, beta])
    want = -np.repeat(np.exp(a_log), 4) * np.log1p(np.exp(f + dt))
    np.testing.assert_allclose(got_g, want, rtol=1e-6)
    np.testing.assert_allclose(got_b, 1 / (1 + np.exp(-b)), rtol=1e-6)


# (b) the plain form of the short convolution --------------------------------


def _silu_shifts(x, w):
    """silu(sum_j w[:, j] x[t - 3 + j]) by hand, four taps."""
    t = x.shape[1]
    pad = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    c = sum(w[:, j] * pad[:, j:j + t] for j in range(4))
    return c * jax.nn.sigmoid(c)


def _plain_conv_op(x, w, g):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        out = contrib.short_conv(xv, taps=4, gated=False,
                                 param_attr=pt.ParamAttr(name="f"))
        gv = layers.data(name="g", shape=list(g.shape), dtype="float32",
                         append_batch_size=False)
        pairs = backward.append_backward(
            layers.reduce_sum(layers.elementwise_mul(out, gv)))
        fetch = [out, dict((p.name, gr) for p, gr in pairs)["f"],
                 prog.global_block().var("x@GRAD")]
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    scope.set_var("f", w)
    got = exe.run(prog, feed={"x": np.asarray(x), "g": np.asarray(g)},
                  scope=scope, fetch_list=fetch)
    return [np.asarray(v) for v in got], prog


@pytest.mark.parametrize("t", [48, 45])
def test_plain_conv_op_is_silu_of_four_shifts_and_its_grad_jax_s(t):
    """t 48 walks three 16-row blocks of the kernels (interpreted); t 45 is
    no whole tile and runs the XLA composition: the same numbers."""
    key = jax.random.key(2)
    x = jax.random.normal(key, (2, t, 128))
    w = 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (128, 4))
    g = jax.random.normal(jax.random.fold_in(key, 2), (2, t, 128))
    (out, dw, dx), prog = _plain_conv_op(x, w, g)
    np.testing.assert_allclose(out, _silu_shifts(x, w), atol=1e-5)
    ref_dx, ref_dw = jax.vjp(_silu_shifts, x, w)[1](g)
    np.testing.assert_allclose(dx, ref_dx, atol=2e-5)
    np.testing.assert_allclose(dw, ref_dw, rtol=1e-5, atol=1e-4)
    conv = [op for op in prog.global_block().ops if op.type == "short_conv"]
    assert conv[0].attr("activation") == "silu"


@pytest.mark.parametrize("b,t,d,taps", [(2, 64, 128, 4), (1, 96, 256, 3),
                                        (1, 160, 384, 4)])
def test_plain_conv_kernels_follow_the_xla_composition(b, t, d, taps):
    key = jax.random.key(7)
    x = jax.random.normal(key, (b, t, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, taps))
    g = jax.random.normal(jax.random.fold_in(key, 2), (b, t, d))
    np.testing.assert_allclose(SC.short_conv_silu(x, w, interpret=True),
                               SC.reference_short_conv_silu(x, w), atol=1e-5)
    for a, r in zip(SC.short_conv_silu_bwd(x, w, g, interpret=True),
                    SC.reference_short_conv_silu_bwd(x, w, g)):
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-4)


def test_plain_plan_halves_the_time_block_where_the_channels_are_wide():
    """[q | k | v] of 12,288 channels: the backward's three blocks fit the
    scoped VMEM at 64 rows; LFM2's gated [B | C | x] keeps its 128."""
    def plan(shape, d, gated):
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        w = jax.ShapeDtypeStruct((d, 4), jnp.float32)
        return SC._plan(x, w, True, gated=gated)

    assert plan((2, 4096, 12288), 12288, False)[:3] == (True, 64, 512)
    assert plan((2, 4096, 6144), 2048, True)[:3] == (True, 128, 512)


# (c) latent attention without a low-rank query path or rotary turn ---------


def test_mla_nope_is_plain_softmax_attention():
    cfg = dict(CFG)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rng = np.random.default_rng(5)
    x = rng.standard_normal((BATCH, SEQ, d)).astype(np.float32)
    names = ["m.q_w", "m.kv_a_w", "m.kv_a_norm.scale", "m.kv_b_w", "m.o_w"]
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=[BATCH, SEQ, d], dtype="float32",
                         append_batch_size=False)
        net = MM._Net(d_model=d, n_head=h, q_lora_rank=None,
                      kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"], mla_rope=False,
                      rms_eps=cfg["rms_norm_eps"], init_std=0.02,
                      rope_theta=1e4)
        out = MM.mla_attention(net, xv, "m")
    params = prog.global_block().all_parameters()
    assert [p.name for p in params] == names
    types = [op.type for op in prog.global_block().ops]
    assert "rope" not in types and "split" in types
    P = {p.name: (rng.standard_normal(p.shape) * 0.3).astype(np.float32)
         for p in params}
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    for k, v in P.items():
        scope.set_var(k, v)
    (got,) = exe.run(prog, feed={"x": x}, scope=scope, fetch_list=[out])
    want = R.mla(DOTS, cfg, jnp.asarray(x),
                 {k: jnp.asarray(v) for k, v in P.items()}, "m")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_mla_with_its_low_rank_query_and_rotary_turn_is_unchanged():
    """J's latent attention: a q low-rank path and two rotary turns, the
    parameters in their old order."""
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=[2, 16, 32], dtype="float32",
                         append_batch_size=False)
        net = MM._Net(d_model=32, n_head=2, q_lora_rank=8, kv_lora_rank=8,
                      qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
                      rms_eps=1e-6, init_std=0.02, rope_theta=1e4)
        MM.mla_attention(net, xv, "m")
    assert [p.name for p in prog.global_block().all_parameters()] == [
        "m.q_a_w", "m.q_a_norm.scale", "m.q_b_w", "m.kv_a_w",
        "m.kv_a_norm.scale", "m.kv_b_w", "m.o_w"]
    types = [op.type for op in prog.global_block().ops]
    assert types.count("rope") == 2 and types.count("concat") == 2


# (d) the 5-layer stack against the plain reference --------------------------


def _build(cfg=CFG, with_optimizer=False, seq=SEQ):
    traffic = {"seq_len": seq, "batch": BATCH, "learning_rate": 1e-4}
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _ = M.build_train_net(
            **PROGRAM.net_kwargs(cfg, traffic),
            with_optimizer=with_optimizer)
        grads = [] if with_optimizer else backward.append_backward(loss)
    return prog, startup, loss, grads


def _weights(cfg, seed=0):
    """The reference's leaves drawn as the benchmark draws them; norm
    scales and the zero-started biases off their start, so that they
    matter, and the routers ten times wider, so that the scores spread."""
    out = {}
    for i, (name, shape, kind, _) in enumerate(R.leaves(cfg, None)):
        key = jax.random.fold_in(jax.random.key(seed), i)
        noise = jax.random.normal(key, shape)
        if kind == "ones":
            out[name] = 1.0 + 0.1 * noise
        elif kind == "zeros":
            out[name] = 0.1 * noise
        else:
            out[name] = float(kind.split(":")[1]) * noise * (
                10.0 if "router_w" in name else 1.0)
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


@pytest.fixture(scope="module")
def trained_pair():
    params, feed = _weights(CFG), _feed(CFG)
    prog, startup, loss, grads = _build(CFG)
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    for name, value in params.items():
        scope.set_var(name, value)
    outs = exe.run(prog, feed=feed, scope=scope,
                   fetch_list=[loss] + [g for _, g in grads])
    got = {p.name: np.asarray(g) for (p, _), g in zip(grads, outs[1:])}
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: _ref_loss(CFG, p, feed))(params)
    return float(np.asarray(outs[0]).reshape(())), got, float(
        ref_loss), ref_grads


def test_program_loss_follows_the_reference(trained_pair):
    loss, _, ref_loss, _ = trained_pair
    assert abs(loss - ref_loss) < 1e-5 * abs(ref_loss)


def test_program_parameters_are_the_reference_leaves_in_order():
    params = _build()[0].global_block().all_parameters()
    assert [(p.name, tuple(p.shape), bool(p.trainable)) for p in params] == [
        (n, tuple(s), t) for n, s, _, t in R.leaves(CFG, None)]
    # the head is a leaf of its own
    assert [p.name for p in params if 211 in tuple(p.shape)] == [
        "embed_w", "head_w"]


@pytest.mark.parametrize(
    "leaf", [n for n, _, _, t in R.leaves(CFG, None) if t])
def test_program_gradient_follows_the_reference(trained_pair, leaf):
    _, grads, _, ref_grads = trained_pair
    ref = np.asarray(ref_grads[leaf])
    median = float(np.median([np.linalg.norm(np.asarray(g))
                              for g in ref_grads.values()]))
    scale = max(float(np.linalg.norm(ref)), median)
    assert np.linalg.norm(grads[leaf] - ref) < 1e-4 * scale, leaf


def test_layer_table_is_the_published_one_indexed_lists():
    cell = registry.load_cell("kimi_linear_ep32_train")
    assert PROGRAM.layer_types(cell.cfg) == R.layer_types(cell.cfg) == [
        "kda", "kda", "kda", "mla", "kda"]
    whole = dict(cell.cfg, num_hidden_layers=27)
    kinds = R.layer_types(whole)
    assert (kinds.count("kda"), kinds.count("mla")) == (20, 7)
    assert [i + 1 for i, k in enumerate(kinds) if k == "mla"] == [
        4, 8, 12, 16, 20, 24, 27]


def test_new_ops_take_the_direct_grad_route_and_the_sites_are_counted():
    prog, startup, loss, _ = _build(with_optimizer=True)
    ops = [op.type for op in prog.global_block().ops]
    assert ops.count("kda") == ops.count("kda_grad") == 4
    assert ops.count("short_conv_grad") == 4
    assert ops.count("kda_gate_grad") == 4  # the generic vjp: elementwise
    assert "rope" not in ops
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    before = monitor.compile_phases()
    feed = {k: v[None] for k, v in _feed(CFG).items()}
    exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    added = {k: v - before[k] for k, v in monitor.compile_phases().items()}
    assert added["kda_sites_xla"] == 4 and added["kda_sites_kernel"] == 0
    assert added["kda_bwd_sites_kept"] == 4  # every backward read its factors
    # every grad op registered with its forward's residuals took them (MLA's
    # heads of 12 take the attention's XLA reference here, which keeps no
    # Lse: its grad op is the generic one)
    direct = ("kda_grad", "short_conv_grad", "rms_norm_grad", "swiglu_grad",
              "moe_router_grad", "moe_experts_grad")
    assert added["grad_direct"] == sum(ops.count(t) for t in direct)


def test_shares_add_up_to_the_uncut_expert_layer():
    """What the 32 chips of a 32-way expert-parallel layer of 256 experts
    give, 8 experts each, with the shared expert (computed alike on every
    chip) counted once, is the uncut reference's layer output; the
    program's share is the reference's share."""
    e, held = 256, 8
    cfg = dict(CFG, num_experts=e, router_experts=e, expert_offset=0,
               num_experts_per_token=8)
    rng = np.random.default_rng(3)
    d, f = 16, 8
    x = rng.standard_normal((BATCH, 24, d)).astype(np.float32)
    P = {"m.router_w": rng.standard_normal((d, e)).astype(np.float32) * 0.3,
         "m.router_bias": rng.standard_normal(e).astype(np.float32) * 0.1,
         "m.experts_gate_up_w": rng.standard_normal(
             (e, d, 2 * f)).astype(np.float32) * 0.3,
         "m.experts_down_w": rng.standard_normal(
             (e, f, d)).astype(np.float32) * 0.3,
         "m.shared.gate_up_w": rng.standard_normal(
             (d, 2 * f)).astype(np.float32) * 0.3,
         "m.shared.down_w": rng.standard_normal(
             (f, d)).astype(np.float32) * 0.3}
    P = {k: jnp.asarray(v) for k, v in P.items()}
    whole = R.moe(DOTS, cfg, jnp.asarray(x), P, "m")
    shared = R.swiglu(DOTS, jnp.asarray(x), P["m.shared.gate_up_w"],
                      P["m.shared.down_w"])

    def share_of(offset):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            xv = layers.data(name="x", shape=[BATCH, 24, d],
                             dtype="float32", append_batch_size=False)
            net = MM._Net(
                d_model=d, n_experts=e, top_k=8, routed_scale=2.446,
                bias_std=0.0, n_held=held, d_ff_expert=f,
                expert_offset=offset, n_shared=1, init_std=0.02)
            out = MM.moe_ffn(net, xv, "m")
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(startup, scope=scope)
        mine = {k: v[offset:offset + held] if "experts" in k else v
                for k, v in P.items()}
        for k, v in mine.items():
            scope.set_var(k, v)
        (got,) = exe.run(prog, feed={"x": x}, scope=scope, fetch_list=[out])
        want = R.routed(DOTS, cfg, jnp.asarray(x), mine, "m",
                        offset=offset) + shared
        np.testing.assert_allclose(got, want, atol=2e-5)
        return np.asarray(got) - np.asarray(shared)

    parts = [share_of(o) for o in range(0, e, held)]
    assert len(parts) == 32
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-4)
    assert sum(float(np.abs(p).max()) > 1e-3 for p in parts) >= 24


# the router-flip diagnostic (tools/router_flips.py) -------------------------


def test_reference_routed_as_it_chose_itself_is_unchanged():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import router_flips as flips

    params, feed = _weights(CFG), _feed(CFG)
    own = flips.kimi_linear_choices(R, DOTS, CFG, params,
                                    jnp.asarray(feed["ids"][..., 0]))
    assert len(own) == 4  # the layers after the dense block
    k = CFG["num_experts_per_token"]
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
        f"layer{i}.router_w" for i in range(1, 5)]


# the KDA kernel bench (tools/kda_bench.py) ---------------------------------


def test_kda_bench_reads_both_chunks_against_the_composition(tmp_path):
    """Interpreted on the CPU: one line a chunk, no device time (the trace
    has no TPU plane), the kernels' results beside the composition's
    (o and the three bf16 gradients to a bf16 step, dg and dbeta f32)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import kda_bench

    out = tmp_path / "kb.jsonl"
    assert kda_bench.main(["--batch", "1", "--seq", "128", "--heads", "1",
                           "--reps", "1", "--check", "--out",
                           str(out)]) == 0
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert [x["chunk"] for x in lines] == [64, 128]
    for x in lines:
        assert x["kda_fwd_ms"] is None and x["kda_bwd_ms"] is None
        assert x["fwd_ms"] > 0 and x["bwd_ms"] > 0
        diff = x["max_diff"]
        assert max(diff[n] for n in ("o", "dq", "dk", "dv")) <= 2 ** -6
        assert max(diff["dg"], diff["dbeta"]) <= 1e-5
