"""The MLA + expert-layer + MTP decoder (models/mla_moe_decoder.py), its
ops (ops/llm_ops.py) and kernels (kernels/grouped_matmul.py, the flash
family with a value head size of its own) at small widths on the CPU, with
seeded random weights, against the benchmark's plain reference
(perfbench/configs/joyai_llm_flash_ep16_reference.py, loaded by its path)
and against hand-written numpy.

Tolerances: float32 programs against a float32 reference at the highest
matmul precision differ by summation order only (1e-5 relative on losses,
1e-4 of a leaf's norm on gradients); interpreted kernels against per-expert
loops the same."""

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
from paddle_tpu.kernels import grouped_matmul as G
from paddle_tpu.layers import contrib
from paddle_tpu.models import mla_moe_decoder as M
from paddle_tpu.ops import llm_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
import registry  # noqa: E402  (perfbench's: finds a cell's files by name)

R = registry.load_module(os.path.join(
    ROOT, "perfbench", "configs", "joyai_llm_flash_ep16_reference.py"))
DOTS = R.B.Dots("f32")  # float32 products at the highest precision

#: a dense + 2 MoE + MTP stack; d_qk 128, d_v 64; this "chip" holds
#: experts 4..7 of 16
CFG = {
    "hidden_size": 64, "num_attention_heads": 2, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
    "v_head_dim": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "router_experts": 16, "expert_offset": 4,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
    "n_shared_experts": 1, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "vocab_size": 211, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "initializer_range": 0.02, "router_bias_std": 0.01,
    "mtp_loss_weight": 0.3,
}
BATCH, SEQ = 2, 32


def _build(cfg=CFG, with_optimizer=False, amp=False, **over):
    prog, startup = pt.Program(), pt.Program()
    dense = cfg["first_k_dense_replace"]
    with pt.program_guard(prog, startup):
        loss, hidden = M.build_train_net(
            vocab_size=cfg["vocab_size"], seq_len=SEQ, batch=BATCH,
            d_model=cfg["hidden_size"], n_head=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], n_dense=dense,
            n_moe=cfg["num_hidden_layers"] - dense,
            d_ff_dense=cfg["intermediate_size"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_experts=cfg["router_experts"], n_held=cfg["n_routed_experts"],
            expert_offset=cfg["expert_offset"],
            top_k=cfg["num_experts_per_tok"],
            routed_scale=cfg["routed_scaling_factor"],
            n_shared=cfg["n_shared_experts"],
            n_mtp=cfg["num_nextn_predict_layers"],
            mtp_weight=cfg["mtp_loss_weight"], rope_theta=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"], init_std=cfg["initializer_range"],
            bias_std=cfg["router_bias_std"],
            with_optimizer=with_optimizer, **over)
        grads = [] if with_optimizer else backward.append_backward(loss)
    if amp:
        pt.amp.enable(prog)
    return prog, startup, loss, grads


def _weights(cfg, seed=0):
    """The reference's leaves drawn as the benchmark draws them."""
    out = {}
    for i, (name, shape, kind, _) in enumerate(R.leaves(cfg, None)):
        key = jax.random.fold_in(jax.random.key(seed), i)
        if kind == "ones":  # off one, so that a norm's scale matters
            out[name] = 1.0 + 0.1 * jax.random.normal(key, shape)
        else:
            out[name] = float(kind.split(":")[1]) * jax.random.normal(
                key, shape)
    return out


def _feed(cfg, seed=1, weights=None):
    rng = np.random.default_rng(seed)
    n_mtp = cfg["num_nextn_predict_layers"]
    ids = rng.integers(0, cfg["vocab_size"],
                       (BATCH, SEQ + 1 + n_mtp, 1)).astype(np.int32)
    if weights is None:
        weights = rng.random((BATCH, SEQ, 1)).astype(np.float32)
    return {"ids": ids, "loss_weight": weights}


def _ref_loss(cfg, params, feed):
    block = {k: jnp.asarray(v) for k, v in feed.items()}
    return R.loss_sum(DOTS, cfg, params, block) / jnp.sum(
        block["loss_weight"])


def _program_loss_and_grads(cfg, params, feed):
    prog, startup, loss, grads = _build(cfg)
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    assert [p.name for p in prog.global_block().all_parameters()] == list(
        params)
    for name, value in params.items():
        scope.set_var(name, value)
    outs = exe.run(prog, feed=feed, scope=scope,
                   fetch_list=[loss] + [g for _, g in grads])
    return float(np.asarray(outs[0]).reshape(())), {
        p.name: np.asarray(g) for (p, _), g in zip(grads, outs[1:])}


@pytest.fixture(scope="module")
def trained_pair():
    params, feed = _weights(CFG), _feed(CFG)
    loss, grads = _program_loss_and_grads(CFG, params, feed)
    trainable = {n for n, _, _, t in R.leaves(CFG, None) if t}
    ref_loss, ref_grads = jax.value_and_grad(
        lambda tp: _ref_loss(CFG, {**params, **tp}, feed))(
            {k: params[k] for k in trainable})
    return loss, grads, float(ref_loss), ref_grads


# (a) ------------------------------------------------------------------------


def test_program_loss_follows_the_reference(trained_pair):
    loss, _, ref_loss, _ = trained_pair
    assert abs(loss - ref_loss) < 1e-5 * abs(ref_loss)


def test_program_parameters_are_the_reference_leaves_in_order():
    prog = _build()[0]
    params = prog.global_block().all_parameters()
    leaves = R.leaves(CFG, None)
    assert [(p.name, tuple(p.shape), bool(p.trainable)) for p in params] == [
        (n, tuple(s), t) for n, s, _, t in leaves]
    assert [n for n, _, _, t in leaves if not t] == [
        "layer1.router_bias", "layer2.router_bias",
        "mtp0.block.router_bias"]


@pytest.mark.parametrize("leaf", [n for n, _, _, t in R.leaves(CFG, None)
                                  if t])
def test_program_gradient_follows_the_reference(trained_pair, leaf):
    _, grads, _, ref_grads = trained_pair
    ref = np.asarray(ref_grads[leaf])
    scale = max(float(np.linalg.norm(ref)), 1e-6)
    assert np.linalg.norm(grads[leaf] - ref) < 1e-4 * scale, leaf


def test_untrained_bias_gets_no_gradient(trained_pair):
    assert not [k for k in trained_pair[1] if k.endswith("router_bias")]


# (b) ------------------------------------------------------------------------


def test_shares_add_up_to_the_uncut_expert_layer():
    """What the 4 chips of a 4-way expert-parallel layer give, the shared
    expert counted once, is the uncut reference's layer output; and the
    program's share is the reference's share."""
    cfg = dict(CFG, n_routed_experts=16, expert_offset=0)
    rng = np.random.default_rng(3)
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 16
    x = rng.standard_normal((BATCH, SEQ, d)).astype(np.float32)
    P = {"m.router_w": rng.standard_normal((d, e)).astype(np.float32) * 0.3,
         "m.router_bias": rng.standard_normal(e).astype(np.float32) * 0.05,
         "m.experts_gate_up_w": rng.standard_normal(
             (e, d, 2 * f)).astype(np.float32) * 0.1,
         "m.experts_down_w": rng.standard_normal(
             (e, f, d)).astype(np.float32) * 0.1,
         "m.shared.gate_up_w": rng.standard_normal(
             (d, 2 * f)).astype(np.float32) * 0.1,
         "m.shared.down_w": rng.standard_normal(
             (f, d)).astype(np.float32) * 0.1}
    P = {k: jnp.asarray(v) for k, v in P.items()}
    whole = R.moe(DOTS, cfg, jnp.asarray(x), P, "m")
    shared = R.swiglu(DOTS, jnp.asarray(x), P["m.shared.gate_up_w"],
                      P["m.shared.down_w"])

    def share_of(offset):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            xv = layers.data(name="x", shape=[BATCH, SEQ, d],
                             dtype="float32", append_batch_size=False)
            net = M._Net(
                d_model=d, n_experts=e, top_k=cfg["num_experts_per_tok"],
                routed_scale=2.5, bias_std=0.0, n_held=4,
                d_ff_expert=f, expert_offset=offset, n_shared=1,
                init_std=0.02)
            out = M.moe_ffn(net, xv, "m")
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(startup, scope=scope)
        for k, v in P.items():
            if "experts" in k:
                v = v[offset:offset + 4]
            scope.set_var(k, v)
        (got,) = exe.run(prog, feed={"x": x}, scope=scope, fetch_list=[out])
        mine = dict(P, **{k: P[k][offset:offset + 4]
                          for k in P if "experts" in k})
        want = R.moe(DOTS, dict(cfg, n_routed_experts=4), jnp.asarray(x),
                     mine, "m", offset=offset)
        np.testing.assert_allclose(got, want, atol=2e-5)
        return np.asarray(got)

    parts = [share_of(o) - np.asarray(shared) for o in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), whole,
                               atol=5e-5)
    assert float(np.abs(parts[1]).max()) > 1e-3  # a share is not nothing


# (c) ------------------------------------------------------------------------


@pytest.mark.parametrize("dqk,dv", [(192, 128), (128, 64), (64, 128)])
def test_flash_kernels_with_a_value_head_size_of_their_own(dqk, dv):
    rng = np.random.default_rng(0)
    b, h, t = 2, 2, 256
    q, k = (jnp.asarray(rng.standard_normal((b, h, t, dqk)), jnp.float32)
            for _ in range(2))
    v, g = (jnp.asarray(rng.standard_normal((b, h, t, dv)), jnp.float32)
            for _ in range(2))
    opts = dict(scale=dqk ** -0.5, causal=True, block_q=128, block_k=128,
                interpret=True)
    out, lse = A.flash_attention_fwd(q, k, v, None, **opts)
    assert lse is not None and out.shape == (b, h, t, dv)
    ref, vjp = jax.vjp(lambda q, k, v: A.reference_attention(
        q, k, v, None, opts["scale"], True), q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    got = A.flash_attention_bwd(q, k, v, None, out, lse, g, **opts)
    for mine, theirs in zip(got[:3], vjp(g)):
        assert mine.shape == theirs.shape
        np.testing.assert_allclose(mine, theirs, atol=1e-4)


def test_whole_head_flash_plan_keeps_one_head_size():
    # the lane-dense bthd plan keeps one head size and takes 128-lane
    # groups: d_qk 128 with d_v 64 is refused, 128 with 128 taken; MLA's
    # d_qk 192 makes no group and runs the reference (the bhtd plan takes
    # it, with d_v 128)
    q = jax.ShapeDtypeStruct((2, 256, 4, 128), jnp.float32)
    v = jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.float32)
    assert not A._plan(q, q, 128, 128, True, "bthd", v)[0]
    assert A._plan(q, q, 128, 128, True, "bthd", q)[0]
    q192 = jax.ShapeDtypeStruct((2, 256, 4, 192), jnp.float32)
    assert not A._plan(q192, q192, 128, 128, True, "bthd", q192)[0]
    qh = jax.ShapeDtypeStruct((2, 4, 256, 192), jnp.float32)
    vh = jax.ShapeDtypeStruct((2, 4, 256, 128), jnp.float32)
    assert A._plan(qh, qh, 128, 128, True, "bhtd", vh)[0]


# (d) ------------------------------------------------------------------------

LOADS = {"empty_expert": [5, 0, 40, 3], "one_expert_takes_all": [0, 0, 64, 0],
         "even": [16, 16, 16, 16], "skewed": [1, 2, 3, 90],
         "nothing_routed_here": [0, 0, 0, 0], "buffer_full": [128, 0, 0, 0],
         "last_expert_empty": [30, 34, 64, 0]}


def _loop(lhs, rhs, sizes):
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    at = 0
    for g, s in enumerate(sizes):
        out[at:at + s] = lhs[at:at + s] @ rhs[g]
        at += s
    return out


def _loop_dw(lhs, dout, sizes):
    out = np.zeros((len(sizes), lhs.shape[1], dout.shape[1]), np.float32)
    at = 0
    for g, s in enumerate(sizes):
        out[g] = lhs[at:at + s].T @ dout[at:at + s]
        at += s
    return out


@pytest.mark.parametrize("route", ["kernel", "xla"])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_grouped_matmul_against_a_loop_over_experts(load, route,
                                                    monkeypatch):
    sizes = LOADS[load]
    rng = np.random.default_rng(1)
    m, k, n = 128, 32, 48
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((4, k, n)).astype(np.float32)
    dout = rng.standard_normal((m, n)).astype(np.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    if route == "kernel":  # a row tile that experts straddle
        monkeypatch.setattr(G, "ROW_TILE", 16)
        fwd = jax.jit(lambda a, b, c: G.grouped_matmul(a, b, c,
                                                       interpret=True))
        dx = jax.jit(lambda a, b, c: G.grouped_matmul(
            a, b, c, transpose_rhs=True, interpret=True))
        dw = jax.jit(lambda a, b, c: G.grouped_matmul_dw(a, b, c,
                                                         interpret=True))
    else:
        fwd = G.reference_grouped_matmul
        dx = lambda a, b, c: G.reference_grouped_matmul(a, b, c, True)  # noqa: E731
        dw = G.reference_grouped_matmul_dw
    live = sum(sizes)  # the rows past them are not written: the caller's
    np.testing.assert_allclose(fwd(lhs, rhs, gs)[:live],
                               _loop(lhs, rhs, sizes)[:live], atol=1e-4)
    np.testing.assert_allclose(
        dx(dout, rhs, gs)[:live],
        _loop(dout, np.swapaxes(rhs, 1, 2), sizes)[:live], atol=1e-4)
    np.testing.assert_allclose(dw(lhs, dout, gs), _loop_dw(lhs, dout, sizes),
                               atol=1e-4)


def test_grouped_matmul_plan_rejects_what_mosaic_cannot_tile(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert G._plan(32768, 2048, 1536, None) == (True, (256, 512, 512), False)
    assert G._plan(32768, 768, 2048, None)[1] == (256, 384, 512)
    assert not G._plan(32768, 2048, 100, None)[0]  # no 128-multiple divides
    assert not G._plan(100, 2048, 1536, None)[0]


# the walk over the sorted pairs (ops/llm_ops.py moe_experts) -------------------

#: 64 tokens x 4 choices among experts 0..15 of a router 32 wide, of which
#: 4..7 are held (an eighth); with a row tile of 16 a chunk is 64 of the 256
#: sorted pairs
WT, WK, WD, WF, WHELD, WOFF, WROUTED = 64, 4, 32, 16, 4, 4, 32


def _walk_idx(case):
    """TopkIdx [64, 4] that sends the sorted pairs where the case wants."""
    rng = np.random.default_rng(5)
    absent = np.array([0, 1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 15])
    idx = absent[rng.integers(0, len(absent), (WT, WK))]
    if case == "exactly_one_chunk":  # 64 pairs held: one full chunk
        idx[:, 0] = WOFF + np.arange(WT) % WHELD
    elif case == "boundary_inside_a_group":  # 40 + 50 rows: 64 cuts expert 5
        idx[:40, 0], idx[:50, 1] = 4, 5
    elif case == "every_pair_held":  # four trips
        idx = WOFF + np.stack([rng.permutation(WHELD) for _ in range(WT)])
    elif case == "all_on_one_expert":  # one group over four chunks
        idx[:] = 6
    elif case == "a_token_in_two_chunks":  # rows 0..63 and 64..127
        idx[:, 0], idx[:, 1] = 4, 7
    else:
        assert case == "no_pair_held"
    return idx.astype(np.int32)


WALKS = {"no_pair_held": 0, "exactly_one_chunk": 1,
         "boundary_inside_a_group": 2, "every_pair_held": 4,
         "all_on_one_expert": 4, "a_token_in_two_chunks": 2}


def _walk_reference(x, idx, w, wgu, wd):
    """The loop over the held experts, every token through each, float32
    at the highest precision."""
    hi = jax.lax.Precision.HIGHEST
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(WHELD):
        h = jnp.dot(x, wgu[e], precision=hi)
        y = jnp.dot(jax.nn.silu(h[:, :WF]) * h[:, WF:], wd[e], precision=hi)
        out = out + y * jnp.sum(jnp.where(idx == WOFF + e, w, 0.0),
                                axis=1)[:, None]
    return out


def _run_walk(case, weight_grad=True, executor=pt.Executor, prepare=None,
              x00=None):
    """(the program, fetched {out, x@GRAD, w@GRAD?, wgu@GRAD, wd@GRAD,
    load}, the reference's out and {x@GRAD, ..}).  `executor` makes the
    executor, `prepare(program)` runs on the built program, `x00` is put
    at x[0, 0]."""
    rng = np.random.default_rng(6)
    idx = _walk_idx(case)
    x = rng.standard_normal((WT, WD)).astype(np.float32)
    if x00 is not None:
        x[0, 0] = x00
    feed = {"x": x,
            "idx": idx,
            "w": rng.random((WT, WK)).astype(np.float32) + 0.1,
            "g": rng.standard_normal((WT, WD)).astype(np.float32)}
    wgu = rng.standard_normal((WHELD, WD, 2 * WF)).astype(np.float32) * 0.3
    wd = rng.standard_normal((WHELD, WF, WD)).astype(np.float32) * 0.3
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=[WT, WD], dtype="float32",
                         append_batch_size=False)
        iv = layers.data(name="idx", shape=[WT, WK], dtype="int32",
                         append_batch_size=False)
        wv = layers.data(name="w", shape=[WT, WK], dtype="float32",
                         append_batch_size=False)
        gv = layers.data(name="g", shape=[WT, WD], dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient, wv.stop_gradient = False, not weight_grad
        out, load = contrib.moe_experts(
            xv, iv, wv, WHELD, WF, WROUTED, expert_offset=WOFF,
            gate_up_attr=pt.ParamAttr(name="wgu"),
            down_attr=pt.ParamAttr(name="wd"))
        backward.append_backward(layers.reduce_sum(
            layers.elementwise_mul(out, gv)))
    if prepare is not None:
        prepare(prog)
    scope, exe = pt.Scope(), executor()
    exe.run(startup, scope=scope)
    scope.set_var("wgu", jnp.asarray(wgu))
    scope.set_var("wd", jnp.asarray(wd))
    names = ["x@GRAD", "wgu@GRAD", "wd@GRAD"] + ["w@GRAD"] * weight_grad
    got = exe.run(prog, feed=feed, scope=scope,
                  fetch_list=[out, load] + names)
    got = dict(zip(["out", "load"] + names, map(np.asarray, got)))
    ref_out, vjp = jax.vjp(
        lambda x, w, a, b: _walk_reference(x, jnp.asarray(idx), w, a, b),
        *map(jnp.asarray, (feed["x"], feed["w"], wgu, wd)))
    return prog, got, np.asarray(ref_out), dict(zip(
        ["x@GRAD", "w@GRAD", "wgu@GRAD", "wd@GRAD"],
        map(np.asarray, vjp(jnp.asarray(feed["g"])))))


def _near(got, want, what):
    scale = max(float(np.linalg.norm(want)), 1e-6)
    assert np.linalg.norm(got - want) < 1e-4 * scale, what


@pytest.mark.parametrize("case", sorted(WALKS))
def test_expert_walk_follows_the_loop_over_experts(case, monkeypatch):
    """The op and its grad at loads that take no trip, one, a chunk
    boundary inside an expert's rows, every chunk.  The interpreter leaves
    NaN in what a kernel does not write, so a dead row that leaked through
    a product instead of a select would show; H, which on the chip starts
    as whatever the memory held, starts as NaN too."""
    monkeypatch.setattr(G, "ROW_TILE", 16)
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))
    assert llm_ops.chunk_rows(WT * WK, WHELD, WROUTED) == 64
    _, got, ref_out, ref = _run_walk(case)
    live = int(got["load"].sum())
    assert -(-live // 64) == WALKS[case]
    _near(got["out"], ref_out, "out")
    for name, want in ref.items():
        assert np.all(np.isfinite(got[name])), name
        _near(got[name], want, name)
    if case == "no_pair_held":
        assert not got["out"].any() and not got["wgu@GRAD"].any() \
            and not got["wd@GRAD"].any()


@pytest.mark.parametrize("weight_grad", [True, False])
def test_expert_walk_with_and_without_the_weights_gradient(weight_grad,
                                                           monkeypatch):
    """A stack that hands no gradient on through the combine weights
    (`TopkWeight.stop_gradient`) gets the same dX and dW and no
    TopkWeight@GRAD."""
    monkeypatch.setattr(G, "ROW_TILE", 16)
    prog, got, _, ref = _run_walk("boundary_inside_a_group", weight_grad)
    (grad_op,) = [op for op in prog.global_block().ops
                  if op.type == "moe_experts_grad"]
    assert any(grad_op.output("TopkWeight@GRAD")) == weight_grad
    for name in got:
        if name.endswith("@GRAD"):
            _near(got[name], ref[name], name)


@pytest.mark.parametrize("live,trips", [(0, 0), (1, 1), (2048, 1),
                                        (2049, 2), (8192, 4)])
def test_one_rule_says_how_many_rows_a_load_walks(live, trips):
    """`rows_walked` is the op's trip count and the model's counter: on
    whole numbers, on the op's traced load, and through a ceiling over a
    program's float variables (what `_publish_load` hands in)."""
    pairs, held, routed = 8192, 2, 16
    assert llm_ops.chunk_rows(pairs, held, routed) == 2048
    assert llm_ops.rows_walked(live, pairs, held, routed) == trips * 2048
    load = jnp.asarray([live // 2, live - live // 2], jnp.int32)
    assert int(llm_ops._walk(load, jnp.arange(pairs, dtype=jnp.int32),
                             routed)[3]) == trips
    assert llm_ops.rows_walked(
        np.float32(live), pairs, held, routed,
        lambda a, b: np.ceil(a / b)) == trips * 2048


@pytest.mark.parametrize("check", ["check_nan_inf", "locate"])
def test_finite_checks_pass_over_the_rows_of_h_nothing_wrote(check,
                                                             monkeypatch):
    """H holds whatever its memory held past the walked chunks and in a
    chunk's dead tail (here NaN: 90 live rows of the 128 walked, of 256):
    the op registers the slot as `unfilled`, so a healthy step passes the
    executor's check_nan_inf and gets no row for H from the numerics
    tier's `locate` level, which the watchdog's replay uses; a NaN that
    is really there is still pinned on the op, through Out."""
    from paddle_tpu.analysis import numerics as anum
    from paddle_tpu.core import registry
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.monitor import numerics as mnum

    assert registry.unfilled_slots("moe_experts") == ("H",)
    monkeypatch.setattr(G, "ROW_TILE", 16)
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))
    if check == "check_nan_inf":
        how = dict(executor=lambda: pt.Executor(check_nan_inf=True))
    else:
        how = dict(prepare=lambda prog: anum.instrument_program(prog,
                                                                "locate"))
        monkeypatch.setattr(FLAGS, "monitor", True)

    def rows(prog):
        (op,) = [op for op in prog.global_block().ops
                 if op.type == "moe_experts"]
        by_var = {r["var"]: r["stat"]["nonfinite"]
                  for r in mnum._last_stats["rows"]}
        assert op.output("H")[0] not in by_var
        return op, by_var

    prog, got, ref_out, _ = _run_walk("boundary_inside_a_group", **how)
    _near(got["out"], ref_out, "out")
    if check == "locate":
        assert not any(rows(prog)[1].values())
    # the same step with a NaN in a routed token's input
    if check == "check_nan_inf":
        with pytest.raises(FloatingPointError, match="moe_experts"):
            _run_walk("boundary_inside_a_group", x00=np.nan, **how)
    else:
        prog, *_ = _run_walk("boundary_inside_a_group", x00=np.nan, **how)
        op, by_var = rows(prog)
        assert by_var[op.output("Out")[0]] > 0


def _traced_arrays(jaxpr, in_loop, out):
    """(in a `while`?, primitive, result aval) of every equation, through
    the nested jaxprs; a `while` itself counts as outside its own body."""
    def nested(v):
        return hasattr(v, "eqns") or hasattr(v, "jaxpr")

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = [getattr(v, "jaxpr", v) for v in jax.tree.leaves(
            eqn.params, is_leaf=nested) if nested(v)]
        if name == "pallas_call":
            subs = []
        for sub in subs:
            _traced_arrays(sub, in_loop or name == "while", out)
        if not subs or name == "while":
            out.extend((in_loop, name, v.aval, [i.aval for i in eqn.invars])
                       for v in eqn.outvars)
    return out


def test_expert_walk_makes_no_array_of_all_the_pairs_outside_its_loop():
    """The bound ISSUE 32 buys, pinned on the jaxpr of moe_experts + grad
    at J's ratios (8 choices, a chunk a quarter of the pairs): the grouped
    matmuls run inside the loop on a chunk's rows, and OUTSIDE the loop no
    float array with a row for every pair is computed at all -- H is
    allocated there (never filled) and comes out of the loop; the float32
    product over all the pairs and the selects over H and Y are gone.
    Inside, a trip makes one such array a pass: the gather of the chunk's
    own rows into the pairs' order, with the convert and the select that
    discards the pairs of other chunks, summed over a token's choices at
    once."""
    t, k, d, f, g = 256, 8, 128, 64, 4
    n, chunk = t * k, llm_ops.chunk_rows(t * k, g, 8 * g)
    assert chunk == n // 4

    class Ctx:
        op = type("Op", (), {"output": staticmethod(lambda slot: ["w"])})

        @staticmethod
        def attr(name, default=None):
            return {"n_experts": 8 * g}.get(name, default)

    def layer(x, idx, w, wgu, wd, dout):
        ins = {"X": [x], "TopkIdx": [idx], "TopkWeight": [w],
               "WGateUp": [wgu], "WDown": [wd]}
        out = llm_ops.lower_moe_experts(Ctx, ins)
        grads = llm_ops.lower_moe_experts_grad(Ctx, dict(
            ins, H=out["H"], Load=out["Load"], Order=out["Order"],
            **{"Out@GRAD": [dout]}))
        return out["Out"], grads

    bf = jnp.bfloat16
    jaxpr = jax.make_jaxpr(layer)(
        jnp.zeros((t, d), bf), jnp.zeros((t, k), jnp.int32),
        jnp.zeros((t, k), jnp.float32), jnp.zeros((g, d, 2 * f), bf),
        jnp.zeros((g, f, d), bf), jnp.zeros((t, d), bf))
    arrays = _traced_arrays(jaxpr.jaxpr, False, [])

    def whole(aval):  # a float array with a row for every pair
        return (len(aval.shape) >= 2 and aval.shape[0] == n
                and aval.shape[1] > 1
                and jnp.issubdtype(aval.dtype, jnp.floating))

    kernels = [a for a in arrays if a[1] == "pallas_call"]
    assert len(kernels) == 6 and all(in_loop for in_loop, *_ in kernels)
    for _, _, aval, operands in kernels:
        assert aval.shape[0] in (chunk, g)
        assert not [o for o in operands if o.shape and o.shape[0] == n]
    outside = [name for in_loop, name, aval, _ in arrays
               if not in_loop and whole(aval)]
    assert sorted(outside) == ["empty", "empty", "while"]  # H, either way
    inside = [name for in_loop, name, aval, _ in arrays
              if in_loop and whole(aval)]
    # (the broadcast is the select's scalar zero)
    assert set(inside) == {"dynamic_update_slice", "gather",
                           "convert_element_type", "select_n",
                           "broadcast_in_dim"}
    assert inside.count("dynamic_update_slice") == 1  # H
    # the forward's and the backward's
    assert inside.count("gather") == inside.count("select_n") == 2


# (e) ------------------------------------------------------------------------


def _router(x, w, bias, amp=False, top_k=2, scale=2.5):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        if amp:  # the activation arrives in bfloat16, as in a trained net
            xv = layers.cast(xv, "bfloat16")
        idx, weight = contrib.moe_router(
            xv, w.shape[1], top_k, scale=scale,
            param_attr=pt.ParamAttr(name="w"),
            bias_attr=pt.ParamAttr(name="b"))
        scores = prog.global_block().ops[-1].output("Scores")[0]
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    scope.set_var("w", jnp.asarray(w))
    scope.set_var("b", jnp.asarray(bias))
    return exe.run(prog, feed={"x": x}, scope=scope,
                   fetch_list=[idx, weight, scores], return_numpy=False)


def test_router_bias_enters_the_choice_and_not_the_weights():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((6, 8)).astype(np.float32)
    w = rng.standard_normal((8, 5)).astype(np.float32)
    s = 1 / (1 + np.exp(-(x.astype(np.float64) @ w)))
    idx0, w0, _ = map(np.asarray, _router(x, w, np.zeros(5, np.float32)))
    np.testing.assert_array_equal(np.sort(idx0, 1),
                                  np.sort(np.argsort(-s, 1)[:, :2], 1))
    bias = np.array([0, 0, 0, 0, 10], np.float32)  # expert 4, whatever it scores
    idx, weight, _ = map(np.asarray, _router(x, w, bias))
    assert (idx[:, 0] == 4).all()
    chosen = np.take_along_axis(s, idx.astype(np.int64), 1)
    np.testing.assert_allclose(
        weight, 2.5 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weight.sum(1), 2.5, rtol=1e-5)
    np.testing.assert_allclose(w0.sum(1), 2.5, rtol=1e-5)


def test_router_scores_are_float32_under_amp():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((4, 16)).astype(np.float32)
    w = rng.standard_normal((16, 6)).astype(np.float32)
    prog_out = _router(x, w, np.zeros(6, np.float32), amp=True)
    idx, weight, scores = prog_out
    assert scores.dtype == jnp.float32 and weight.dtype == jnp.float32
    assert idx.dtype == jnp.int32
    xb = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    want = 1 / (1 + np.exp(-(xb.astype(np.float64) @ w)))
    # float32 at the highest precision: not one bf16 pass (4e-3 here)
    np.testing.assert_allclose(np.asarray(scores), want, atol=2e-6)


# (f) ------------------------------------------------------------------------


@pytest.mark.parametrize("theta", [10000.0, 32000000.0])
def test_interleaved_rope_is_a_complex_rotation(theta):
    rng = np.random.default_rng(5)
    b, t, h, d = 2, 16, 3, 8
    x = rng.standard_normal((b, t, h, d)).astype(np.float32)
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=[b, t, h, d], dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        out = contrib.rope(xv, theta=theta)
        (gx,) = backward.calc_gradient(layers.reduce_sum(
            layers.elementwise_mul(out, out)), [xv])
    got, grad = pt.Executor().run(prog, feed={"x": x}, scope=pt.Scope(),
                                  fetch_list=[out, gx])
    z = x.astype(np.float64)[..., 0::2] + 1j * x.astype(np.float64)[..., 1::2]
    angle = np.arange(t)[:, None] * theta ** (-np.arange(0, d, 2) / d)
    turned = z * np.exp(1j * angle)[None, :, None, :]
    want = np.stack([turned.real, turned.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(R.rope(jnp.asarray(x), theta)),
                               want, atol=1e-5)
    # a rotation keeps the norm: d(sum out^2)/dx = 2 x
    np.testing.assert_allclose(grad, 2 * x, atol=1e-5)


# (g) ------------------------------------------------------------------------


def test_mtp_labels_are_the_ids_shifted_by_two():
    params, feed = _weights(CFG), _feed(CFG, weights=np.ones(
        (BATCH, SEQ, 1), np.float32))
    ids = jnp.asarray(feed["ids"][..., 0])
    states, seq = R.hidden_states(DOTS, CFG, params, ids)
    assert seq == SEQ

    def ce(state, labels):
        logits = np.asarray(DOTS.mm(state, params["head_w"]), np.float64)
        lse = np.log(np.exp(logits).sum(-1))
        return (lse - np.take_along_axis(
            logits, np.asarray(labels)[..., None], -1)[..., 0]).mean()

    want = ce(states[0], ids[:, 1:SEQ + 1]) + 0.3 * ce(
        states[1], ids[:, 2:SEQ + 2])
    got, _ = _program_loss_and_grads(CFG, params, feed)
    assert abs(got - want) < 1e-5 * want
    # the last id is the MTP head's last label and nobody's input
    moved = dict(feed, ids=feed["ids"].copy())
    moved["ids"][:, -1] = (moved["ids"][:, -1] + 1) % CFG["vocab_size"]
    assert abs(_program_loss_and_grads(CFG, params, moved)[0] - got) > 1e-6


@pytest.mark.parametrize("leaf", ["embed_w", "head_w"])
def test_shared_embedding_and_head_gradients_sum_both_heads(trained_pair,
                                                            leaf):
    params, feed = _weights(CFG), _feed(CFG)

    def part(mtp_weight, main_weight):
        def loss(w):
            p = dict(params, **{leaf: w})
            block = {k: jnp.asarray(v) for k, v in feed.items()}
            ids, wts = block["ids"][..., 0], block["loss_weight"][..., 0]
            states, seq = R.hidden_states(DOTS, CFG, p, ids)
            return (main_weight * R.B.weighted_cross_entropy_sum(
                DOTS.mm(states[0], p["head_w"]), ids[:, 1:seq + 1], wts)
                + mtp_weight * R.B.weighted_cross_entropy_sum(
                    DOTS.mm(states[1], p["head_w"]), ids[:, 2:seq + 2],
                    wts)) / jnp.sum(wts)
        return np.asarray(jax.grad(loss)(params[leaf]))

    main, mtp = part(0.0, 1.0), part(0.3, 0.0)
    got = trained_pair[1][leaf]
    scale = np.linalg.norm(main + mtp)
    assert np.linalg.norm(got - (main + mtp)) < 1e-4 * scale
    assert np.linalg.norm(mtp) > 1e-3 * scale  # both heads reach the leaf


# (h) ------------------------------------------------------------------------

NEW_GRAD_OPS = ("rms_norm_grad", "rope_grad", "swiglu_grad",
                "moe_router_grad", "moe_experts_grad",
                "fused_attention_grad")


def test_new_ops_take_the_direct_grad_route():
    """Every grad op of the new ops reads its forward's residuals: none
    re-runs a forward kernel under lower_generic_grad."""
    prog, startup, loss, _ = _build(with_optimizer=True)
    ops = [op.type for op in prog.global_block().ops]
    direct = sum(ops.count(t) for t in NEW_GRAD_OPS)
    # 4 blocks' worth: 3 layers + the MTP block
    assert ops.count("moe_experts_grad") == 3
    assert ops.count("fused_attention_grad") == 4
    assert ops.count("rope_grad") == 8 and ops.count("swiglu_grad") == 4
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    before = monitor.compile_phases()
    feed = {k: v[None] for k, v in _feed(CFG).items()}
    exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    after = monitor.compile_phases()
    assert after["grad_direct"] - before["grad_direct"] == direct
    from paddle_tpu.core import registry

    generic = sum(t.endswith("_grad") and registry.lookup(t) is None
                  for t in ops)  # no lowering of their own
    assert after["grad_generic"] - before["grad_generic"] == generic


def test_amp_step_trains_and_stays_near_float32():
    feed = {k: np.stack([v] * 2) for k, v in _feed(CFG).items()}
    losses = {}
    for amp in (False, True):
        prog, startup, loss, _ = _build(with_optimizer=True, amp=amp, lr=1e-3)
        prog.random_seed = startup.random_seed = 11
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(startup, scope=scope)
        for name, value in _weights(CFG).items():
            scope.set_var(name, value)
        (out,) = exe.run_steps(prog, feed=feed, fetch_list=[loss],
                               scope=scope)
        losses[amp] = np.asarray(out).reshape(-1)
    assert losses[False][1] < losses[False][0]
    np.testing.assert_allclose(losses[True], losses[False], rtol=2e-3)


# the router-flip diagnostic (tools/router_flips.py) ---------------------------


def _router_flips():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import router_flips

    return router_flips


def test_reference_routed_as_it_chose_itself_is_unchanged():
    """`route_as` is the diagnostic's door into the reference: handing the
    reference its own choice changes nothing, another choice does."""
    flips = _router_flips()
    params, feed = _weights(CFG), _feed(CFG)
    ids = jnp.asarray(feed["ids"][..., 0])
    own = flips.reference_choices(R, DOTS, CFG, params, ids)
    assert len(own) == 3  # two expert layers and the MTP module's
    k = CFG["num_experts_per_tok"]
    for idx, margin in own:
        assert idx.shape == (BATCH, SEQ, k) and margin.shape == (BATCH, SEQ)
        assert float(jnp.min(margin)) >= 0.0
    route_as = np.stack([np.asarray(idx) for idx, _ in own], axis=1)
    want = float(_ref_loss(CFG, params, feed))
    got = float(_ref_loss(CFG, params, dict(feed, route_as=route_as)))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    other = (route_as + 1) % CFG["router_experts"]
    moved = float(_ref_loss(CFG, params, dict(feed, route_as=other)))
    assert abs(moved - want) > 1e-5 * abs(want)


def test_count_flips_counts_pairs_and_the_held_ones():
    flips = _router_flips()
    mine = np.array([[0, 1], [2, 3], [4, 5]])
    theirs = np.array([[1, 0], [2, 7], [4, 5]])  # order does not matter
    got = flips.count_flips(mine, theirs, np.array([0.5, 1e-4, 0.3]),
                            n_experts=8, offset=2, held=2)
    assert (got["tokens"], got["tokens_flipped"]) == (3, 1)
    assert (got["pairs"], got["pairs_flipped"]) == (6, 1)
    # held here: experts 2 and 3; the reference sends one pair to them,
    # and expert 3's pair is the program's alone
    assert (got["held_pairs"], got["held_pairs_flipped"]) == (1, 1)
    assert got["margin_median_flipped"] == got["margin_max_flipped"] == 1e-4
    same = flips.count_flips(mine, mine, np.ones(3), 8, 2, 2)
    assert same["pairs_flipped"] == 0
    assert same["margin_median_flipped"] is None


def test_router_outputs_come_in_the_reference_order():
    flips = _router_flips()
    prog, _, _, _ = _build()
    names = flips.router_outputs(prog)
    routers = [op for op in prog.global_block().ops
               if op.type == "moe_router"]
    assert [op.input("W")[0] for op in routers] == [
        "layer1.router_w", "layer2.router_w", "mtp0.block.router_w"]
    assert names == [op.output("TopkIdx")[0] for op in routers]


# counters, lint, smoke ---------------------------------------------------------


def test_device_counters_ride_the_flight_event_only_while_tracing():
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.monitor import flight

    prog, startup, loss, _ = _build(with_optimizer=True)
    assert set(prog._device_counters) == {
        "moe_local_pairs", "moe_max_over_mean", "moe_rows_walked"}
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    feed = {k: np.stack([v] * 2) for k, v in _feed(CFG).items()}

    def call():
        n = len(flight.default_recorder().events(kind="executor.run_steps"))
        (out,) = exe.run_steps(prog, feed=feed, fetch_list=[loss],
                               scope=scope)
        assert np.asarray(out).shape[0] == 2  # the user's fetch alone
        return flight.default_recorder().events(
            kind="executor.run_steps")[n:]

    call()  # the miss
    # tracing off: the first-calls record has the call, nothing read back
    (event,) = call()
    assert event["first_calls"] == 1 and "counters" not in event
    FLAGS.monitor = True
    try:
        (event,) = call()
    finally:
        FLAGS.reset("monitor")
    counters = event["counters"]
    tokens, k = BATCH * SEQ, CFG["num_experts_per_tok"]
    # 3 expert layers, each at most every pair and about a quarter of them
    assert 0 < counters["moe_local_pairs"] <= 3 * tokens * k
    assert abs(counters["moe_local_pairs"] - 3 * tokens * k / 4) \
        < 3 * tokens * k / 8
    assert 1.0 <= counters["moe_max_over_mean"] < 4.0
    # the op's own chunk rule: 64 x 4 pairs a layer are one chunk at this
    # size (a quarter is no whole row tile), so each layer walks it once
    assert llm_ops.chunk_rows(tokens * k, CFG["n_routed_experts"],
                              CFG["router_experts"]) == tokens * k
    assert counters["moe_rows_walked"] == 3 * tokens * k


def test_kernel_named_rule_reads_a_conditional_between_two_names():
    import ast

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import lint_rules

    tree = ast.parse(
        'pl.pallas_call(k, name="a_bwd_dx" if t else "a_fwd")\n'
        'pl.pallas_call(k, name=NAMES[role])\n')
    assert lint_rules.pallas_call_names(tree) == [
        (1, "a_bwd_dx"), (1, "a_fwd"), (2, None)]
    path = os.path.join(ROOT, "paddle_tpu", "kernels", "grouped_matmul.py")
    names = [n for _, n in lint_rules.pallas_call_names(
        ast.parse(open(path).read()))]
    assert names == ["moe_gmm_bwd_dx", "moe_gmm_fwd", "moe_gmm_bwd_dw"]
    assert lint_rules.check_file(path, lint_rules.declared_flags()) == []


def test_chip_smoke_moe_leg_tiny():
    sys.path.insert(0, ROOT)
    import chip_smoke

    sizes = dict(chip_smoke.MOE_SMALL, vocab_size=97, seq_len=32, d_model=32,
                 q_lora_rank=16, kv_lora_rank=16, qk_nope_dim=64,
                 qk_rope_dim=64, v_head_dim=64, n_head=2, d_ff_dense=48,
                 d_ff_expert=16, lr=1e-2)
    rep = chip_smoke.moe_leg(sizes=sizes, scan_steps=2, calls=2,
                             interpret=True)
    assert rep["ok"], rep["failures"]
    assert rep["loss_last"] < rep["loss_first"]
