"""Transformer model + flash attention tests (reference:
test_parallel_executor_transformer.py / dist_transformer.py scale-downs)."""

import functools

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import transformer as T


def _tiny_transformer(use_flash=False):
    return T.transformer(
        src_vocab_size=64,
        trg_vocab_size=64,
        max_length=16,
        n_layer=2,
        n_head=2,
        d_key=8,
        d_value=8,
        d_model=16,
        d_inner_hid=32,
        dropout_rate=0.0,
        src_seq_len=16,
        trg_seq_len=16,
        use_flash=use_flash,
    )


def test_transformer_trains():
    avg_cost, predict, feed_names = _tiny_transformer()
    pt.optimizer.Adam(learning_rate=2e-3).minimize(avg_cost)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    batch = T.make_batch(4, 16, 16, 2, 64, 64, rng)
    losses = []
    for _ in range(30):
        (l,) = exe.run(feed=batch, fetch_list=[avg_cost])
        losses.append(float(np.asarray(l)))
    assert losses[-1] < losses[0] * 0.6, losses  # memorizes the fixed batch


def test_flash_attention_matches_reference():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.attention import (
        flash_attention,
        reference_attention,
    )

    with jax.default_matmul_precision("highest"):
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 2, 128, 128).astype("float32"))
        k = jnp.asarray(rng.randn(1, 2, 128, 128).astype("float32"))
        v = jnp.asarray(rng.randn(1, 2, 128, 128).astype("float32"))
        bias = jnp.asarray(rng.randn(1, 2, 128, 128).astype("float32"))
        ref = reference_attention(q, k, v, bias, scale=0.125)
        out = flash_attention(q, k, v, bias, scale=0.125, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

        refc = reference_attention(q, k, v, None, 0.125, causal=True)
        outc = flash_attention(q, k, v, None, 0.125, causal=True,
                               block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(outc), np.asarray(refc), atol=1e-5)


@pytest.mark.parametrize(
    "name,tq,tk,bias_shape,causal",
    [
        ("plain", 128, 128, None, False),
        ("causal", 128, 128, None, True),
        ("full_bias", 128, 128, (2, 2, 128, 128), False),
        ("pad_mask_bias", 128, 128, (2, 1, 1, 128), False),
        ("bias_causal", 128, 128, (2, 1, 1, 128), True),
        ("tk1_bias", 128, 128, (2, 2, 128, 1), False),
        ("cross", 64, 128, None, False),
        ("cross_causal", 64, 128, None, True),
        ("masked_rows", 128, 64, None, True),  # tq>tk causal: empty rows
    ],
)
def test_flash_attention_grads_match_reference(name, tq, tk, bias_shape,
                                               causal):
    """Gradient parity of the Pallas backward kernels (dq/dk/dv/dbias) vs
    jax.grad of the unfused reference, over bias/causal/cross variants with
    batch*heads > 1 (the configs the round-3 review found broken)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.attention import (
        flash_attention,
        reference_attention,
    )

    d = 64
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 2, tq, d).astype("float32"))
    k = jnp.asarray(rng.randn(2, 2, tk, d).astype("float32"))
    v = jnp.asarray(rng.randn(2, 2, tk, d).astype("float32"))
    args = (q, k, v)
    if bias_shape is not None:
        args = args + (jnp.asarray(
            0.3 * rng.randn(*bias_shape).astype("float32")),)
    scale = 1.0 / np.sqrt(d)

    def make_loss(fn):
        def loss(*a):
            bias = a[3] if len(a) > 3 else None
            out = fn(a[0], a[1], a[2], bias, scale=scale, causal=causal)
            return jnp.sum(out * jnp.cos(out))
        return loss

    argnums = tuple(range(len(args)))
    flash = functools.partial(flash_attention, block_q=64, block_k=64)
    with jax.default_matmul_precision("highest"):
        grads_f = jax.grad(make_loss(flash), argnums)(*args)
        grads_r = jax.grad(make_loss(reference_attention), argnums)(*args)
    for gf, gr in zip(grads_f, grads_r):
        assert np.all(np.isfinite(np.asarray(gf))), name
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=2e-4, rtol=1e-3,
            err_msg=name)


#: (tq, tk, block_q, block_k): causal_offset 0, > 0 (Tq < Tk) and < 0
#: (Tq > Tk: rows with no key), tiles of two sizes, one tile of t
CAUSAL_TILES = [(128, 128, 32, 32), (128, 128, 16, 64), (64, 128, 16, 32),
                (128, 64, 32, 16), (96, 128, 32, 32), (128, 128, 128, 128)]


@pytest.mark.parametrize("tq,tk,bq,bk", CAUSAL_TILES)
def test_causal_tile_mask_from_a_column_and_a_row_is_the_plane(tq, tk, bq,
                                                               bk):
    from paddle_tpu.kernels import attention as A

    tiles = np.tril(np.ones((tq, tk), bool), tk - tq).reshape(
        tq // bq, bq, tk // bk, bk)
    for i in range(tq // bq):
        for j in range(tk // bk):
            np.testing.assert_array_equal(
                A._tile_mask(i * bq, j * bk, bq, bk, tk - tq, None),
                tiles[i, :, j], err_msg=f"tile ({i}, {j})")


@pytest.mark.parametrize("tq,tk,bq,bk", CAUSAL_TILES)
def test_causal_tile_masks_change_no_bit(tq, tk, bq, bk, monkeypatch):
    """out, lse, dQ, dK, dV: the reference's within this file's tolerances,
    and bit for bit what the kernels give comparing [block_q, block_k]
    planes of positions pair by pair (the kernels before PR 36)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    rng = np.random.RandomState(tq + tk + bq)
    q, g = (jnp.asarray(rng.randn(2, 2, tq, 64).astype("float32"))
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, 2, tk, 64).astype("float32"))
            for _ in range(2))
    opts = dict(scale=0.125, causal=True, block_q=bq, block_k=bk,
                interpret=True)

    def kernels():
        out, lse = A.flash_attention_fwd(q, k, v, None, **opts)
        assert lse is not None  # the kernels ran, not the fallback
        return (out, lse) + A.flash_attention_bwd(q, k, v, None, out, lse,
                                                  g, **opts)[:3]

    got = kernels()
    with jax.default_matmul_precision("highest"):
        ref, vjp = jax.vjp(lambda q, k, v: A.reference_attention(
            q, k, v, None, 0.125, causal=True), q, k, v)
        want = vjp(g)
    np.testing.assert_allclose(got[0], ref, atol=1e-5)
    for mine, theirs in zip(got[2:], want):
        np.testing.assert_allclose(mine, theirs, atol=2e-4, rtol=1e-3)

    def pair_by_pair(q_lo, k_lo, block_q, block_k, causal_offset, mask):
        shape = (block_q, block_k)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return q_pos + causal_offset >= k_pos

    monkeypatch.setattr(A, "_tile_mask", pair_by_pair)
    for mine, before in zip(got, kernels()):
        np.testing.assert_array_equal(mine, before)


#: name -> (tq, tk, heads, head size, bias shape, causal, dropout rate):
#: the lane-dense kernels' two group layouts (pairs of heads of 64, one
#: head of 128), walks of one to four blocks, the cells' bias kinds (a
#: [b, 1, 1, t] padding row, a [b, 1, t, t] plane, a per-head bias), tq != tk
#: both ways under causal (rows that see no key), and hash dropout
_BTHD_CASES = {
    "plain": (128, 128, 2, 64, None, False, 0.0),
    "causal": (128, 128, 2, 64, None, True, 0.0),
    "full_bias": (128, 128, 2, 64, (2, 2, 128, 128), False, 0.0),
    "pad_mask_bias": (128, 128, 2, 64, (2, 1, 1, 128), False, 0.0),
    "cross": (64, 128, 2, 64, None, False, 0.0),
    "h8_t256_plane": (256, 256, 8, 64, (2, 1, 256, 256), False, 0.0),
    "h12_t512_pad": (512, 512, 12, 64, (2, 1, 1, 512), False, 0.0),
    "h12_t128_head_bias": (128, 128, 12, 64, (1, 12, 1, 128), False, 0.0),
    "h8_d128_causal_cross": (128, 256, 8, 128, None, True, 0.0),
    "h12_d128_t512_pad": (512, 512, 12, 128, (2, 1, 1, 512), False, 0.0),
    "h8_causal_empty_rows": (256, 128, 8, 64, None, True, 0.0),
    "h8_t256_hash_dropout": (256, 256, 8, 64, (2, 1, 1, 256), False, 0.2),
    "h12_d128_causal_dropout": (128, 128, 12, 128, None, True, 0.1),
}


@pytest.mark.parametrize("name", sorted(_BTHD_CASES))
def test_flash_attention_bthd_format(name):
    """The transpose-free [B,T,H,D] calling convention (the lane-dense
    kernels, interpret mode) must match the [B,H,T,D] reference in
    outputs AND gradients — dQ, dK, dV and the bias's — at the head
    counts and sizes the cells run (it is the layout the bench transformer
    and BERT run)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.attention import (
        flash_attention,
        reference_attention,
    )

    tq, tk, h, d, bias_shape, causal, rate = _BTHD_CASES[name]
    block = 64 if max(tq, tk) <= 128 else 128
    seed = jnp.asarray([41], jnp.uint32)
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, h, tq, d).astype("float32"))
    k = jnp.asarray(rng.randn(2, h, tk, d).astype("float32"))
    v = jnp.asarray(rng.randn(2, h, tk, d).astype("float32"))
    args = (q, k, v)
    if bias_shape is not None:
        args = args + (jnp.asarray(
            0.3 * rng.randn(*bias_shape).astype("float32")),)
    scale = 1.0 / np.sqrt(d)

    def loss_ref(*a):
        bias = a[3] if len(a) > 3 else None
        out = reference_attention(a[0], a[1], a[2], bias, scale=scale,
                                  causal=causal, dropout_rate=rate,
                                  dropout_seed=seed)
        return jnp.sum(out * jnp.cos(out))

    def bthd(*a):
        bias = a[3] if len(a) > 3 else None
        return flash_attention(
            a[0].transpose(0, 2, 1, 3), a[1].transpose(0, 2, 1, 3),
            a[2].transpose(0, 2, 1, 3), bias, scale=scale, causal=causal,
            block_q=block, block_k=block, fmt="bthd", dropout_rate=rate,
            dropout_seed=seed)

    def loss_bthd(*a):
        out = bthd(*a)
        return jnp.sum(out * jnp.cos(out))

    argnums = tuple(range(len(args)))
    with jax.default_matmul_precision("highest"):
        out_b = bthd(*args)
        out_r = reference_attention(q, k, v,
                                    args[3] if len(args) > 3 else None,
                                    scale=scale, causal=causal,
                                    dropout_rate=rate, dropout_seed=seed)
        np.testing.assert_allclose(
            np.asarray(out_b.transpose(0, 2, 1, 3)), np.asarray(out_r),
            atol=1e-5, err_msg=name)
        grads_b = jax.grad(loss_bthd, argnums)(*args)
        grads_r = jax.grad(loss_ref, argnums)(*args)
    for gb, gr in zip(grads_b, grads_r):
        assert np.all(np.isfinite(np.asarray(gb))), name
        np.testing.assert_allclose(
            np.asarray(gb), np.asarray(gr), atol=2e-4, rtol=1e-3,
            err_msg=name)


@pytest.mark.parametrize("h,d,route", [
    (8, 64, "kernel"),   # four groups of two heads of 64
    (2, 128, "kernel"),  # a group is one head of 128
    (3, 64, "xla"),      # an odd head count at 64 makes no pairs
    (2, 192, "xla"),     # neither 64 nor a multiple of 128 lanes
])
def test_bthd_sites_counted_by_route(h, d, route):
    """A bthd site counts among the compile totals as its forward is
    traced inside an Executor call: `bthd_sites_kernel` where the plan
    takes its head group, `bthd_sites_xla` where it rejects the shape,
    and the rejected site computes the reference."""
    import jax.numpy as jnp

    from paddle_tpu import monitor
    from paddle_tpu.kernels.attention import _reference_bthd, flash_attention
    from paddle_tpu.monitor import flight

    rng = np.random.RandomState(h + d)
    q, k, v = (jnp.asarray(rng.randn(2, 128, h, d).astype("float32"))
               for _ in range(3))
    before = monitor.compile_phases()
    with flight.executor_call():
        out = flash_attention(q, k, v, None, scale=d ** -0.5, block_q=64,
                              block_k=64, fmt="bthd")
    after = monitor.compile_phases()
    counted = tuple(after[f"bthd_sites_{r}"] - before[f"bthd_sites_{r}"]
                    for r in ("kernel", "xla"))
    assert counted == ((1, 0) if route == "kernel" else (0, 1))
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_reference_bthd(q, k, v, None, d ** -0.5, False)),
        atol=1e-5)


@pytest.mark.parametrize(
    "name,fmt,causal",
    [
        ("bhtd", "bhtd", False),
        ("bhtd_causal", "bhtd", True),
        ("bthd", "bthd", False),
        ("bthd_causal", "bthd", True),
    ],
)
def test_flash_attention_dropout_matches_reference(name, fmt, causal):
    """In-kernel weights-dropout (deterministic hash mask) vs the pure-XLA
    fallback with the SAME seed: outputs and all grads must match — i.e.
    the fwd kernel, both bwd kernels, and the fallback all regenerate the
    identical mask from (seed, global element index)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.attention import (
        flash_attention,
        reference_attention,
    )

    d, t = 64, 128
    rate = 0.3
    rng = np.random.RandomState(11)
    seed = jnp.asarray([12345], jnp.uint32)
    if fmt == "bhtd":
        shape = (2, 2, t, d)
    else:
        shape = (2, t, 2, d)
    q = jnp.asarray(rng.randn(*shape).astype("float32"))
    k = jnp.asarray(rng.randn(*shape).astype("float32"))
    v = jnp.asarray(rng.randn(*shape).astype("float32"))
    scale = 1.0 / np.sqrt(d)

    def ref(q, k, v):
        if fmt == "bthd":
            out = reference_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), None, scale, causal, rate, seed)
            return out.transpose(0, 2, 1, 3)
        return reference_attention(q, k, v, None, scale, causal, rate, seed)

    def flash(q, k, v):
        return flash_attention(q, k, v, None, scale=scale, causal=causal,
                               block_q=64, block_k=64, fmt=fmt,
                               dropout_rate=rate, dropout_seed=seed)

    with jax.default_matmul_precision("highest"):
        out_f = flash(q, k, v)
        out_r = ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   atol=1e-5, err_msg=name)
        # dropped entries really exist (mask is active)
        assert not np.allclose(
            np.asarray(out_f),
            np.asarray(flash_attention(q, k, v, None, scale=scale,
                                       causal=causal, block_q=64,
                                       block_k=64, fmt=fmt)))

        def mk_loss(fn):
            return lambda *a: jnp.sum(fn(*a) * jnp.cos(fn(*a)))

        gf = jax.grad(mk_loss(flash), (0, 1, 2))(q, k, v)
        gr = jax.grad(mk_loss(ref), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3, err_msg=name)


def test_flash_attention_dropout_bias_grad():
    """Trainable-bias cotangent under in-kernel dropout (the _dbias_xla
    recompute must apply the same hash mask)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.attention import (
        flash_attention,
        reference_attention,
    )

    d, t = 64, 128
    rate = 0.2
    rng = np.random.RandomState(3)
    seed = jnp.asarray([777], jnp.uint32)
    q = jnp.asarray(rng.randn(2, 2, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(2, 2, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(2, 2, t, d).astype("float32"))
    bias = jnp.asarray(0.3 * rng.randn(2, 2, t, t).astype("float32"))
    scale = 1.0 / np.sqrt(d)

    def loss(fn, *a):
        out = fn(a[0], a[1], a[2], a[3])
        return jnp.sum(out * jnp.cos(out))

    with jax.default_matmul_precision("highest"):
        gf = jax.grad(
            lambda *a: loss(
                lambda q, k, v, b: flash_attention(
                    q, k, v, b, scale=scale, block_q=64, block_k=64,
                    dropout_rate=rate, dropout_seed=seed), *a),
            (0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(
            lambda *a: loss(
                lambda q, k, v, b: reference_attention(
                    q, k, v, b, scale, False, rate, seed), *a),
            (0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_fused_attention_layer_dropout_in_program():
    """fused_attention layer with dropout_rate: in-kernel weights dropout —
    train output differs from no-dropout but is deterministic per step,
    and is_test mode disables it."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    def build(rate):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            q = layers.data(name="q", shape=[2, 64, 32], dtype="float32")
            k = layers.data(name="k", shape=[2, 64, 32], dtype="float32")
            v = layers.data(name="v", shape=[2, 64, 32], dtype="float32")
            out = layers.contrib.fused_attention(
                q, k, v, scale=0.2, dropout_rate=rate)
            s = layers.reduce_sum(out)
        return prog, startup, out, s

    rng = np.random.RandomState(0)
    feed = {n: rng.randn(1, 2, 64, 32).astype("float32") for n in "qkv"}
    exe = pt.Executor(pt.CPUPlace())

    prog0, st0, out0, _ = build(0.0)
    scope0 = pt.Scope()
    with pt.scope_guard(scope0):
        exe.run(st0, scope=scope0)
        (base,) = exe.run(prog0, feed=feed, fetch_list=[out0], scope=scope0)

    prog, st, out, _ = build(0.4)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(st, scope=scope)
        (a,) = exe.run(prog, feed=feed, fetch_list=[out], scope=scope)
        infer = prog.clone(for_test=True)
        (b,) = exe.run(infer, feed=feed, fetch_list=[out], scope=scope)
    assert not np.allclose(np.asarray(a), np.asarray(base))
    np.testing.assert_allclose(np.asarray(b), np.asarray(base), atol=1e-5)


def test_fused_attention_layer_in_program():
    from paddle_tpu import layers

    q = layers.data(name="q", shape=[2, 64, 128], dtype="float32")
    k = layers.data(name="k", shape=[2, 64, 128], dtype="float32")
    v = layers.data(name="v", shape=[2, 64, 128], dtype="float32")
    out = layers.contrib.fused_attention(q, k, v, scale=0.1)
    exe = pt.Executor(pt.CPUPlace())
    rng = np.random.RandomState(0)
    feed = {
        n: rng.randn(1, 2, 64, 128).astype("float32") for n in ("q", "k", "v")
    }
    (o,) = exe.run(feed=feed, fetch_list=[out])
    assert o.shape == (1, 2, 64, 128)

    from paddle_tpu.kernels.attention import reference_attention
    import jax.numpy as jnp

    ref = reference_attention(
        jnp.asarray(feed["q"]), jnp.asarray(feed["k"]), jnp.asarray(feed["v"]),
        None, 0.1,
    )
    np.testing.assert_allclose(o, np.asarray(ref), atol=2e-2, rtol=2e-2)


@pytest.mark.slow
def test_transformer_with_flash_matches_unfused():
    # same seed -> same params; flash vs unfused attention give same loss
    prog_a, prog_b = pt.Program(), pt.Program()
    startup_a, startup_b = pt.Program(), pt.Program()
    losses = {}
    rng_batch = np.random.RandomState(3)
    batch = T.make_batch(2, 16, 16, 2, 64, 64, rng_batch)
    for name, prog, startup, flash in (
        ("unfused", prog_a, startup_a, False),
        ("flash", prog_b, startup_b, True),
    ):
        with pt.program_guard(prog, startup):
            with pt.core.framework.guard_unique_name():
                avg_cost, _, _ = _tiny_transformer(use_flash=flash)
        prog.random_seed = startup.random_seed = 17
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        (l,) = exe.run(prog, feed=batch, fetch_list=[avg_cost], scope=scope)
        losses[name] = float(np.asarray(l))
    assert abs(losses["flash"] - losses["unfused"]) < 2e-2, losses
