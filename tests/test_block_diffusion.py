"""The block-diffusion decoder (models/block_diffusion_decoder.py): grouped-
query attention with per-head QK norm under the [noisy ; clean] mask, the
softmax router, half-split rotary positions that restart, and the masked-
diffusion loss, at small widths on the CPU with seeded random weights,
against the benchmark's plain reference (perfbench/configs/
sdar_30b_a3b_ep8_reference.py, loaded by its path) and hand-written numpy.

Tolerances: float32 programs against a float32 reference at the highest
matmul precision differ by summation order only (1e-5 relative on losses,
1e-4 of a leaf's norm on gradients); interpreted kernels against the XLA
reference the same (2e-5 absolute on O(1) contexts, 1e-4 on gradients)."""

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
from paddle_tpu.layers import contrib
from paddle_tpu.models import block_diffusion_decoder as M
from paddle_tpu.models import mla_moe_decoder as MM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
import registry  # noqa: E402  (perfbench's: finds a cell's files by name)

R = registry.load_module(os.path.join(
    ROOT, "perfbench", "configs", "sdar_30b_a3b_ep8_reference.py"))
DOTS = R.B.Dots("f32")  # float32 products at the highest precision

#: 2 layers, 4 query heads over 2 key/value heads of 64; this "chip" holds
#: experts 4..7 of 8; rows of 32 tokens in blocks of 4
CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 64, "moe_intermediate_size": 32, "num_experts": 4,
    "router_experts": 8, "expert_offset": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 2, "vocab_size": 211, "mask_token_id": 210,
    "block_length": 4, "noise_level": 0.5, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "initializer_range": 0.02,
}
BATCH, SEQ = 2, 32


def _build(cfg=CFG, with_optimizer=False, amp=False):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, hidden = M.build_train_net(
            vocab_size=cfg["vocab_size"], seq_len=SEQ, batch=BATCH,
            block_length=cfg["block_length"],
            noise_level=cfg["noise_level"],
            mask_token_id=cfg["mask_token_id"], d_model=cfg["hidden_size"],
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            n_layer=cfg["num_hidden_layers"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_experts=cfg["router_experts"], n_held=cfg["num_experts"],
            expert_offset=cfg["expert_offset"],
            top_k=cfg["num_experts_per_tok"], rope_theta=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"], init_std=cfg["initializer_range"],
            with_optimizer=with_optimizer,
            train_router=cfg.get("router_trained", True))
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


def _feed(cfg, seed=1, weights=None, noise=None):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg["mask_token_id"],
                       (BATCH, SEQ, 1)).astype(np.int32)
    if noise is None:
        noise = (rng.random((BATCH, SEQ, 1)) < 0.5).astype(np.float32)
    if weights is None:
        weights = rng.random((BATCH, SEQ, 1)).astype(np.float32)
    return {"ids": ids, "noise": noise, "loss_weight": weights}


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


def _program_loss_and_grads(cfg, params, feed):
    prog, startup, loss, grads = _build(cfg)
    outs = _run(prog, startup, params, feed,
                [loss] + [g for _, g in grads])
    return float(np.asarray(outs[0]).reshape(())), {
        p.name: np.asarray(g) for (p, _), g in zip(grads, outs[1:])}


@pytest.fixture(scope="module")
def trained_pair():
    params, feed = _weights(CFG), _feed(CFG)
    loss, grads = _program_loss_and_grads(CFG, params, feed)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: _ref_loss(CFG, p, feed))(params)
    return loss, grads, float(ref_loss), ref_grads


# (a) the program against the plain reference -------------------------------


def test_program_loss_follows_the_reference(trained_pair):
    loss, _, ref_loss, _ = trained_pair
    assert abs(loss - ref_loss) < 1e-5 * abs(ref_loss)


def test_program_parameters_are_the_reference_leaves_in_order():
    prog = _build()[0]
    params = prog.global_block().all_parameters()
    assert [(p.name, tuple(p.shape), bool(p.trainable)) for p in params] == [
        (n, tuple(s), t) for n, s, _, t in R.leaves(CFG, None)]
    assert not [p.name for p in params if "bias" in p.name]


@pytest.mark.parametrize("leaf", [n for n, _, _, _ in R.leaves(CFG, None)])
def test_program_gradient_follows_the_reference(trained_pair, leaf):
    _, grads, _, ref_grads = trained_pair
    ref = np.asarray(ref_grads[leaf])
    scale = max(float(np.linalg.norm(ref)), 1e-6)
    assert np.linalg.norm(grads[leaf] - ref) < 1e-4 * scale, leaf


# the share whose routers are not trained (the benchmark's cell) -------------

#: on one chip of eight only the held experts return an output, so a
#: trained router pulls tokens onto the chip (PERF.md, section 6, PR 31)
FIXED = dict(CFG, router_trained=False)


@pytest.fixture(scope="module")
def fixed_router_pair():
    params, feed = _weights(FIXED), _feed(FIXED)
    loss, grads = _program_loss_and_grads(FIXED, params, feed)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: _ref_loss(FIXED, p, feed))(params)
    return loss, grads, float(ref_loss), ref_grads


def test_untrained_router_leaves_the_forward_as_it_was(trained_pair,
                                                        fixed_router_pair):
    assert fixed_router_pair[0] == trained_pair[0]
    assert abs(fixed_router_pair[0] - fixed_router_pair[2]) < 1e-5 * abs(
        fixed_router_pair[2])


@pytest.mark.parametrize("leaf", [n for n, _, _, _ in R.leaves(CFG, None)])
def test_untrained_router_gradient_follows_the_reference(
        trained_pair, fixed_router_pair, leaf):
    _, grads, _, ref_grads = fixed_router_pair
    ref = np.asarray(ref_grads[leaf])
    if leaf.endswith("router_w"):
        # no trained leaf: the program makes no gradient for it, and the
        # reference's combine weights are constants
        assert leaf not in grads and not ref.any()
        return
    scale = max(float(np.linalg.norm(ref)), 1e-6)
    assert np.linalg.norm(grads[leaf] - ref) < 1e-4 * scale, leaf
    if leaf in ("layer0.ffn_norm.scale", "layer1.ffn_norm.scale"):
        # the norm before a router no longer hears from it
        assert np.linalg.norm(grads[leaf] - trained_pair[1][leaf]) > \
            1e-3 * scale


def test_untrained_router_has_no_grad_op_and_stays_where_it_was():
    prog, startup, loss, _ = _build(FIXED, with_optimizer=True)
    block = prog.global_block()
    ops = [op.type for op in block.ops]
    assert ops.count("moe_router") == 2 and "moe_router_grad" not in ops
    assert ops.count("moe_experts_grad") == 2
    routers = [p.name for p in block.all_parameters()
               if p.name.endswith("router_w")]
    assert [(n, t) for n, _, _, t in R.leaves(FIXED, None)
            if n.endswith("router_w")] == [(n, False) for n in routers]
    trained = {op.input("Param")[0] for op in block.ops if op.type == "adam"}
    assert not trained & set(routers)
    assert len(trained) == len(block.all_parameters()) - len(routers) == 23
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    # host copies: the call donates what the scope holds
    start = {k: np.asarray(v) for k, v in _weights(FIXED).items()}
    for name, value in start.items():
        scope.set_var(name, jnp.asarray(value))
    feed = {k: np.stack([v] * 3) for k, v in _feed(FIXED).items()}
    exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    for name, value in start.items():
        moved = np.abs(np.asarray(scope.find_var(name)) - value).max()
        assert (moved == 0) == (name in routers), name


# (b) the mask ---------------------------------------------------------------


def _rules(seq, block):
    """The four rules, written out pair by pair."""
    vis = np.zeros((2 * seq, 2 * seq), bool)
    for i in range(2 * seq):
        for j in range(2 * seq):
            bi, bj = (i % seq) // block, (j % seq) // block
            if i < seq and j < seq:
                vis[i, j] = bj == bi
            elif i < seq:
                vis[i, j] = bj < bi
            elif j >= seq:
                vis[i, j] = bj <= bi
    return vis


#: (L, B, block_q, block_k): tiles that are a whole number of blocks, a
#: tile inside one block, and tiles of different sizes
MASK_CASES = [(16, 4, 8, 8), (16, 4, 16, 8), (16, 16, 8, 8), (16, 4, 8, 16),
              (256, 4, 128, 128), (256, 16, 64, 128), (256, 16, 128, 64),
              (256, 256, 64, 64)]


@pytest.mark.parametrize("seq,block,bq,bk", MASK_CASES)
def test_kernel_visibility_is_the_four_rules(seq, block, bq, bk):
    """q = 0 makes every visible key weigh alike, and v = the identity
    hands the weights out: out[i, j] > 0 iff row i saw key j."""
    t = 2 * seq
    width = max(t, 64)
    q = jnp.zeros((1, 2, t, 64), jnp.float32)
    k = jnp.ones((1, 1, t, 64), jnp.float32)
    v = jnp.eye(t, width, dtype=jnp.float32)[None, None]
    out, lse = A.flash_attention_fwd(
        q, k, v, None, scale=1.0, block_q=bq, block_k=bk, interpret=True,
        mask=(block, seq))
    assert lse is not None  # the kernels ran, not the fallback
    want = _rules(seq, block)
    got = np.asarray(out)[0, :, :, :t] > 0
    np.testing.assert_array_equal(got[0], want)
    np.testing.assert_array_equal(got[1], want)
    assert want.any(axis=1).all()  # no row without a key
    np.testing.assert_allclose(
        np.asarray(out)[0, 0, :, :t],
        want / want.sum(axis=1, keepdims=True), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(A._bd_plane(t, t, (block, seq))),
                                  want)


@pytest.mark.parametrize("seq,block,bq,bk", MASK_CASES + [
    (2048, 4, 512, 512)])
def test_walks_visit_the_tiles_that_hold_a_visible_pair(seq, block, bq, bk):
    want = _rules(seq, block) if seq <= 256 else np.asarray(
        R.block_diffusion_mask(seq, block))
    t = 2 * seq
    tiles = want.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
    for i in range(t // bq):
        a0, na, c0, nc = A._bd_key_tiles(i * bq, bq, bk, block, seq)
        walked = list(range(a0, a0 + na)) + list(range(c0, c0 + nc))
        assert walked == list(np.flatnonzero(tiles[i])), i
    for j in range(t // bk):
        n0, nn, c0, nc = A._bd_query_tiles(j * bk, bq, bk, block, seq)
        walked = list(range(n0, n0 + nn)) + list(range(c0, c0 + nc))
        assert walked == list(np.flatnonzero(tiles[:, j])), j
    assert A.bd_tiles_visited(bq, bk, block, seq) == (
        int(tiles.sum()), tiles.size)


def test_visited_share_at_the_cell_s_size():
    visited, total = A.bd_tiles_visited(512, 512, 4, 2048)
    assert (visited, total) == (24, 64)  # 37.5 %; 25.05 % is visible
    vis = np.asarray(R.block_diffusion_mask(2048, 4))
    assert vis.sum() == 2048 * 4 + 2048 * 2048


def test_masked_walk_counts_its_tiles_inside_an_executor_call():
    from paddle_tpu.monitor import flight

    q = jnp.zeros((1, 2, 64, 64), jnp.float32)
    before = monitor.compile_phases()
    A.flash_attention(q, q, q, interpret=True, block_q=16, block_k=16,
                      mask=(4, 32))  # outside a call: not counted
    assert monitor.compile_phases() == before
    with flight.executor_call():
        A.flash_attention(q, q, q, interpret=True, block_q=16, block_k=16,
                          mask=(4, 32))
    after = monitor.compile_phases()
    want = A.bd_tiles_visited(16, 16, 4, 32)
    assert (after["attn_tiles_visited"] - before["attn_tiles_visited"],
            after["attn_tiles_total"] - before["attn_tiles_total"]) == want


#: (L, B, block_q, block_k) of the tile masks: blocks of 1, 4 and 16 at
#: tiles of 16, 32 and 64; tiles of two sizes; a block that is no multiple
#: of a tile, and blocks that hold whole tiles
TILE_CASES = [(64, b, t, t) for b in (1, 4, 16) for t in (16, 32, 64)] + [
    (64, 4, 32, 16), (64, 16, 16, 32), (48, 24, 16, 16), (64, 64, 16, 32)]


@pytest.mark.parametrize("seq,block,bq,bk", TILE_CASES)
def test_tile_mask_from_a_column_and_a_row_is_the_plane(seq, block, bq, bk):
    """Every tile's mask, built from the rows' blocks as a column and the
    keys' as a row, is the plane's (`_bd_visible` pair by pair): all true
    where the mask cannot cut the tile, all false where it sees nothing."""
    t = 2 * seq
    plane = np.asarray(A._bd_plane(t, t, (block, seq)))
    tiles = plane.reshape(t // bq, bq, t // bk, bk)
    for i in range(t // bq):
        for j in range(t // bk):
            np.testing.assert_array_equal(
                A._tile_mask(i * bq, j * bk, bq, bk, 0, (block, seq)),
                tiles[i, :, j], err_msg=f"tile ({i}, {j})")


def _kernels(q, k, v, g, **opts):
    """out, lse, dq, dk, dv of the interpreted kernels."""
    out, lse = A.flash_attention_fwd(q, k, v, None, **opts)
    assert lse is not None  # the kernels ran, not the fallback
    return (out, lse) + A.flash_attention_bwd(q, k, v, None, out, lse, g,
                                              **opts)[:3]


@pytest.mark.parametrize("seq,block,bq,bk", [
    (64, 4, 16, 16), (64, 16, 16, 32), (48, 24, 16, 16), (64, 64, 32, 16),
    (32, 4, 32, 32)])
def test_tile_masks_change_no_bit(seq, block, bq, bk, monkeypatch):
    """out, lse, dQ, dK, dV: the reference's within the module's
    tolerances, and bit for bit what the kernels gave before PR 36, with
    `_bd_visible` on planes of positions (the last case: tiles of half of
    t, every visited tile cut)."""
    rng = np.random.default_rng(seq + block + bq)
    t, mask = 2 * seq, (block, seq)
    q, g = (jnp.asarray(rng.standard_normal((1, 4, t, 64)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((1, 2, t, 64)), jnp.float32)
            for _ in range(2))
    opts = dict(scale=0.125, block_q=bq, block_k=bk, interpret=True,
                mask=mask)
    got = _kernels(q, k, v, g, **opts)
    ref, vjp = jax.vjp(lambda q, k, v: A.reference_attention(
        q, k, v, None, 0.125, mask=mask), q, k, v)
    np.testing.assert_allclose(got[0], ref, atol=2e-5)
    for mine, theirs in zip(got[2:], vjp(g)):
        np.testing.assert_allclose(mine, theirs, atol=1e-4)

    def pair_by_pair(q_lo, k_lo, block_q, block_k, causal_offset, mask):
        shape = (block_q, block_k)
        return A._bd_visible(
            q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1), *mask)

    monkeypatch.setattr(A, "_tile_mask", pair_by_pair)
    for mine, before in zip(got, _kernels(q, k, v, g, **opts)):
        np.testing.assert_array_equal(mine, before)


def test_plan_rejects_a_mask_it_cannot_tile():
    q = jax.ShapeDtypeStruct((2, 4, 64, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((2, 2, 64, 64), jnp.float32)
    assert A._plan(q, k, 16, 16, True, "bhtd", k, (4, 32))[0]
    assert not A._plan(q, k, 16, 16, True, "bhtd", k, (4, 16))[0]  # t != 2L
    assert not A._plan(q, k, 64, 16, True, "bhtd", k, (4, 32))[0]  # straddles
    assert not A._plan(q, k, 16, 16, True, "bhtd", k, (5, 32))[0]
    qt = jax.ShapeDtypeStruct((2, 64, 4, 64), jnp.float32)
    kt = jax.ShapeDtypeStruct((2, 64, 2, 64), jnp.float32)
    assert not A._plan(qt, qt, 16, 16, True, "bthd", qt, (4, 32))[0]
    assert not A._plan(qt, kt, 16, 16, True, "bthd", kt)[0]  # one head count
    k3 = jax.ShapeDtypeStruct((2, 3, 64, 64), jnp.float32)
    assert not A._plan(q, k3, 16, 16, True, "bhtd", k3)[0]
    with pytest.raises(ValueError, match="two masks"):
        A.flash_attention(jnp.zeros(q.shape), jnp.zeros(k.shape),
                          jnp.zeros(k.shape), causal=True, mask=(4, 32))


def test_rejected_mask_falls_back_to_the_same_numbers():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 4, 64, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.float32)
            for _ in range(2))
    kern = A.flash_attention(q, k, v, scale=0.125, interpret=True,
                             block_q=16, block_k=16, mask=(4, 32))
    out, lse = A.flash_attention_fwd(q, k, v, None, scale=0.125,
                                     block_q=64, block_k=64, interpret=True,
                                     mask=(4, 32))
    assert lse is None  # a 64-row tile straddles the halves: XLA fallback
    np.testing.assert_allclose(out, kern, atol=2e-5)


# (c) grouped heads ----------------------------------------------------------


@pytest.mark.parametrize("masking", ["none", "causal", "block_diffusion"])
def test_flash_kernels_with_grouped_heads(masking):
    """16 query heads over 2 key/value heads (groups of 8, as 32 over 4)."""
    rng = np.random.default_rng(0)
    b, h, hk, t, d = 2, 16, 2, 256, 64
    q, g = (jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((b, hk, t, d)), jnp.float32)
            for _ in range(2))
    causal = masking == "causal"
    mask = (16, t // 2) if masking == "block_diffusion" else None
    opts = dict(scale=d ** -0.5, causal=causal, block_q=64, block_k=64,
                interpret=True, mask=mask)
    out, lse = A.flash_attention_fwd(q, k, v, None, **opts)
    assert lse is not None and out.shape == (b, h, t, d)
    # the reference on K and V repeated by hand, 8 times each
    k_rep, v_rep = (jnp.repeat(a, h // hk, axis=1) for a in (k, v))
    ref, vjp = jax.vjp(lambda q, k, v: A.reference_attention(
        q, k, v, None, opts["scale"], causal, mask=mask), q, k_rep, v_rep)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    np.testing.assert_allclose(
        A.reference_attention(q, k, v, None, opts["scale"], causal,
                              mask=mask), ref, atol=1e-6)
    dq, dk, dv, _ = A.flash_attention_bwd(q, k, v, None, out, lse, g, **opts)
    rq, rk, rv = vjp(g)
    np.testing.assert_allclose(dq, rq, atol=1e-4)
    assert dk.shape == k.shape and dv.shape == v.shape
    # dK / dV are sums over the group's 8 query heads
    np.testing.assert_allclose(
        dk, rk.reshape(b, hk, h // hk, t, d).sum(axis=2), atol=2e-4)
    np.testing.assert_allclose(
        dv, rv.reshape(b, hk, h // hk, t, d).sum(axis=2), atol=2e-4)


def test_flash_attention_is_differentiable_with_grouped_masked_heads():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 4, 64, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.float32)
            for _ in range(2))

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    got = jax.grad(loss(lambda q, k, v: A.flash_attention(
        q, k, v, scale=0.125, block_q=16, block_k=16, interpret=True,
        mask=(4, 32))), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: A.reference_attention(
        q, k, v, None, 0.125, mask=(4, 32))), (0, 1, 2))(q, k, v)
    for mine, theirs in zip(got, want):
        np.testing.assert_allclose(mine, theirs, atol=1e-4)


# (e) the softmax router -----------------------------------------------------


def _router(x, w, amp=False, top_k=2, grad_of=None):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        xin = layers.cast(xv, "bfloat16") if amp else xv
        idx, weight = contrib.moe_router(
            xin, w.shape[1], top_k, param_attr=pt.ParamAttr(name="w"),
            bias_attr=False, scoring="softmax")
        op = prog.global_block().ops[-1]
        scores = op.output("Scores")[0]
        assert "Bias" not in op.inputs and op.attrs["scoring"] == "softmax"
        fetch = [idx, weight, scores]
        if grad_of is not None:
            cot = layers.data(name="cot", shape=list(grad_of.shape),
                              dtype="float32", append_batch_size=False)
            total = layers.reduce_sum(layers.elementwise_mul(weight, cot))
            wvar = prog.global_block().var("w")
            fetch += backward.calc_gradient(total, [xv, wvar])
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    scope.set_var("w", jnp.asarray(w))
    feed = {"x": x} if grad_of is None else {"x": x, "cot": grad_of}
    return exe.run(prog, feed=feed, scope=scope, fetch_list=fetch,
                   return_numpy=False)


def _plain_router(x, w, top_k):
    p = jax.nn.softmax(jnp.matmul(x, w, precision="highest"), axis=-1)
    chosen, idx = jax.lax.top_k(p, top_k)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True), p


def test_softmax_router_weights_sum_to_one_over_the_chosen():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((6, 8)).astype(np.float32)
    w = rng.standard_normal((8, 5)).astype(np.float32)
    idx, weight, scores = map(np.asarray, _router(x, w))
    ridx, rweight, rscores = map(np.asarray, _plain_router(x, w, 2))
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(weight, rweight, rtol=1e-5)
    np.testing.assert_allclose(scores, rscores, atol=1e-6)
    np.testing.assert_allclose(weight.sum(1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(scores.sum(1), 1.0, rtol=1e-6)


def test_softmax_router_gradient_is_the_plain_formula_s():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((6, 8)).astype(np.float32)
    w = rng.standard_normal((8, 5)).astype(np.float32)
    cot = rng.standard_normal((6, 2)).astype(np.float32)
    *_, dx, dw = _router(x, w, grad_of=cot)
    rdx, rdw = jax.grad(lambda x, w: jnp.sum(
        _plain_router(x, w, 2)[1] * cot), (0, 1))(jnp.asarray(x),
                                                  jnp.asarray(w))
    np.testing.assert_allclose(dx, rdx, atol=1e-5)
    np.testing.assert_allclose(dw, rdw, atol=1e-5)


def test_softmax_router_scores_are_float32_under_amp():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((4, 16)).astype(np.float32)
    w = rng.standard_normal((16, 6)).astype(np.float32)
    idx, weight, scores = _router(x, w, amp=True)
    assert scores.dtype == jnp.float32 and weight.dtype == jnp.float32
    xb = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    logits = xb.astype(np.float64) @ w
    want = np.exp(logits - logits.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    # float32 at the highest precision: not one bf16 pass (4e-3 here)
    np.testing.assert_allclose(np.asarray(scores), want, atol=2e-6)


# (f) rotary positions -------------------------------------------------------


@pytest.mark.parametrize("theta,period", [(10000.0, 0), (1000000.0, 8)])
def test_half_split_rope_is_a_complex_rotation(theta, period):
    rng = np.random.default_rng(5)
    b, t, h, d = 2, 16, 3, 8
    x = rng.standard_normal((b, t, h, d)).astype(np.float32)
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        xv = layers.data(name="x", shape=[b, t, h, d], dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        out = contrib.rope(xv, theta=theta, pairing="half", period=period)
        (gx,) = backward.calc_gradient(layers.reduce_sum(
            layers.elementwise_mul(out, out)), [xv])
    got, grad = pt.Executor().run(prog, feed={"x": x}, scope=pt.Scope(),
                                  fetch_list=[out, gx])
    z = x.astype(np.float64)[..., :d // 2] + 1j * x.astype(
        np.float64)[..., d // 2:]
    pos = np.arange(t) % period if period else np.arange(t)
    angle = pos[:, None] * theta ** (-np.arange(0, d, 2) / d)
    turned = z * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([turned.real, turned.imag], -1)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if period:  # the two halves of a row are turned alike
        np.testing.assert_allclose(
            np.asarray(R.rope(jnp.asarray(x), theta, period)), want,
            atol=1e-5)
        again = pt.Executor().run(
            prog, feed={"x": np.concatenate([x[:, :8], x[:, :8]], 1)},
            scope=pt.Scope(), fetch_list=[out])[0]
        np.testing.assert_array_equal(again[:, :8], again[:, 8:])
    # a rotation keeps the norm: d(sum out^2)/dx = 2 x
    np.testing.assert_allclose(grad, 2 * x, atol=1e-5)


def test_rope_refuses_a_pairing_it_does_not_know():
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data(name="x", shape=[1, 4, 1, 8], dtype="float32",
                         append_batch_size=False)
        out = contrib.rope(xv, pairing="quarter")
        with pytest.raises(Exception, match="unknown pairing"):
            pt.Executor().run(
                pt.default_main_program(),
                feed={"x": np.zeros((1, 4, 1, 8), np.float32)},
                scope=pt.Scope(), fetch_list=[out])


# (g) the share ties to the model -------------------------------------------


def test_shares_add_up_to_the_uncut_expert_layer():
    """What the 8 chips of an 8-way expert-parallel layer give (no shared
    expert: nothing is computed alike on every chip but the router) is the
    uncut reference's layer output; the program's share is the
    reference's share."""
    cfg = dict(CFG, num_experts=16, router_experts=16, expert_offset=0)
    rng = np.random.default_rng(3)
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 16
    x = rng.standard_normal((BATCH, SEQ, d)).astype(np.float32)
    P = {"m.router_w": rng.standard_normal((d, e)).astype(np.float32) * 0.3,
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
                scoring="softmax", router_bias=False)
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

    parts = [share_of(o) for o in range(0, 16, 2)]
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    assert min(float(np.abs(p).max()) for p in parts) > 1e-3


# (h) the loss ---------------------------------------------------------------


def test_only_masked_noisy_positions_contribute_at_weight_one_over_t():
    params = _weights(CFG)
    feed = _feed(CFG)
    prog, startup, loss, _ = _build()
    (got,) = _run(prog, startup, params, feed, [loss])
    # by hand: the reference's logits, CE where noise is 1, times 1 / t
    ids, noise, w = (jnp.asarray(feed[k][..., 0])
                     for k in ("ids", "noise", "loss_weight"))
    x = R.hidden_states(DOTS, CFG, params, ids, noise)
    logits = jnp.matmul(R.rms_norm(x[:, :SEQ], params["final_norm.scale"],
                                   1e-6), params["head_w"],
                        precision="highest")
    ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, ids[..., None], -1)[..., 0]
    want = float(jnp.sum(w * noise * ce / 0.5) / jnp.sum(w))
    assert abs(float(np.asarray(got).reshape(())) - want) < 1e-5 * want
    # nothing masked: nothing to predict
    clean = dict(feed, noise=np.zeros_like(feed["noise"]))
    (none,) = _run(prog, startup, params, clean, [loss])
    assert float(np.asarray(none).reshape(())) == 0.0


def test_loss_weight_zeros_drop_rows():
    params = _weights(CFG)
    feed = _feed(CFG, weights=np.ones((BATCH, SEQ, 1), np.float32))
    half = dict(feed, loss_weight=feed["loss_weight"].copy())
    half["loss_weight"][1] = 0
    other = dict(half, ids=half["ids"].copy())
    other["ids"][1] = (other["ids"][1] + 7) % CFG["mask_token_id"]
    prog, startup, loss, _ = _build()
    a, b = (float(np.asarray(_run(prog, startup, params, f, [loss])[0])
                  .reshape(())) for f in (half, other))
    assert a == b  # the dropped row's tokens do not matter
    assert abs(a - float(_ref_loss(CFG, params, half))) < 1e-5 * a


def test_clean_queries_of_the_last_layer_get_no_gradient():
    """The head reads the noisy half, so the last layer's clean rows feed
    nothing: their queries' gradient is nought; their keys and values are
    read by the noisy rows, and theirs is not."""
    prog, startup, loss, _ = _build()
    grad_ops = [op for op in prog.global_block().ops
                if op.type == "fused_attention_grad"]
    last = grad_ops[0]  # the backward runs the last layer first
    assert last.attrs["mask"] == "block_diffusion"
    assert (last.attrs["block_length"], last.attrs["clean_offset"]) == (
        4, SEQ)
    names = [last.output(slot)[0] for slot in ("Q@GRAD", "K@GRAD")]
    dq, dk = _run(prog, startup, _weights(CFG), _feed(CFG), names)
    assert dq.shape == (BATCH, 4, 2 * SEQ, 64)
    assert dk.shape == (BATCH, 2, 2 * SEQ, 64)
    assert np.abs(dq[:, :, SEQ:]).max() == 0.0
    assert np.abs(dq[:, :, :SEQ]).max() > 0.0
    assert np.abs(dk[:, :, SEQ:]).max() > 0.0
    first = grad_ops[-1]  # an earlier layer's clean rows do feed the loss
    (dq0,) = _run(prog, startup, _weights(CFG), _feed(CFG),
                  [first.output("Q@GRAD")[0]])
    assert np.abs(dq0[:, :, SEQ:]).max() > 0.0


# the router-flip diagnostic (tools/router_flips.py) -------------------------


def test_reference_routed_as_it_chose_itself_is_unchanged():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import router_flips as flips

    params, feed = _weights(CFG), _feed(CFG)
    ids, noise = (jnp.asarray(feed[k][..., 0]) for k in ("ids", "noise"))
    own = flips.block_diffusion_choices(R, DOTS, CFG, params, ids, noise)
    assert len(own) == 2  # every layer is an expert layer
    k = CFG["num_experts_per_tok"]
    for idx, margin in own:
        assert idx.shape == (BATCH, 2 * SEQ, k)
        assert margin.shape == (BATCH, 2 * SEQ)
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
        "layer0.router_w", "layer1.router_w"]
    assert flips.router_outputs(prog) == [
        op.output("TopkIdx")[0] for op in routers]


# spans, counters, routes ----------------------------------------------------


def test_new_and_changed_ops_take_the_direct_grad_route():
    prog, startup, loss, _ = _build(with_optimizer=True)
    ops = [op.type for op in prog.global_block().ops]
    assert ops.count("fused_attention_grad") == 2
    assert ops.count("moe_router_grad") == 2
    assert ops.count("moe_experts_grad") == 2
    assert ops.count("rope_grad") == 4
    assert ops.count("rms_norm_grad") == 2 * 4 + 1
    # on the CPU no flash kernel ran, so fused_attention wrote no Lse and
    # its two grad ops go the generic way; on the chip they are direct too
    direct = sum(ops.count(t) for t in (
        "moe_router_grad", "moe_experts_grad", "rope_grad",
        "rms_norm_grad"))
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    before = monitor.compile_phases()
    feed = {k: v[None] for k, v in _feed(CFG).items()}
    exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    after = monitor.compile_phases()
    assert after["grad_direct"] - before["grad_direct"] == direct


def test_device_counters_carry_the_held_experts_load_while_tracing():
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
        exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
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
    positions, k = BATCH * 2 * SEQ, CFG["num_experts_per_tok"]
    assert 0 < counters["moe_local_pairs"] <= 2 * positions * k
    # whole chunks of the op's own rule, as many as hold the layers' pairs
    assert counters["moe_local_pairs"] <= counters["moe_rows_walked"] \
        <= 2 * positions * k


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


# the benchmark's files for this stack ---------------------------------------


def test_cell_files_state_the_cut_and_draw_noise_at_the_noise_level():
    cell = registry.load_cell("sdar_30b_a3b_ep8_train")
    cfg, traffic = cell.cfg, cell.traffic
    assert traffic["fields"]["noise"]["p"] == traffic["noise_level"] \
        == cfg["noise_level"]
    assert traffic["block_length"] == cfg["block_length"] == 4
    assert traffic["token_high"] == cfg["mask_token_id"] \
        == cfg["vocab_size"] - 1
    # ISSUE 31's traffic: two rows of 2048 tokens, Adam at the other train
    # cells' rate; the routers are not trained, and the file says why
    assert cfg["router_trained"] is False
    assert "held experts" in cfg["departures"]["router_not_trained"]
    assert (traffic["batch"], traffic["seq_len"], traffic["steps_per_call"],
            traffic["learning_rate"]) == (2, 2048, 8, 1e-4)
    # published widths; the three cuts are in `reduced`
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_experts"]) == (2048, 32, 4, 128, 768, 8, 128)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    leaves = R.leaves(cfg, traffic)
    assert sum(int(np.prod(s)) for _, s, _, _ in leaves) == 456_346_624


def test_program_file_builds_the_cell_s_program():
    cell = registry.load_cell("sdar_30b_a3b_ep8_train")
    tiny = dict(cell.cfg, hidden_size=64, head_dim=64, num_attention_heads=4,
                num_key_value_heads=2, moe_intermediate_size=32,
                num_experts=4, router_experts=8, num_hidden_layers=1,
                vocab_size=211, mask_token_id=210, amp=True)
    traffic = dict(cell.traffic, seq_len=32)
    program = registry.load_module(cell.path(cell.cfg["program"]))
    prog, _, _ = program.build(tiny, traffic)
    attn = [op for op in prog.global_block().ops
            if op.type == "fused_attention"]
    assert [(op.attrs["mask"], op.attrs["block_length"],
             op.attrs["clean_offset"], op.attrs["fmt"]) for op in attn] == [
        ("block_diffusion", 4, 32, "bhtd")]
    ropes = [op for op in prog.global_block().ops if op.type == "rope"]
    assert [(op.attrs["pairing"], op.attrs["period"]) for op in ropes] == [
        ("half", 32)] * 2
    with pytest.raises(ValueError, match="block_length"):
        program.build(tiny, dict(traffic, block_length=8))
