"""Fused conv / batch-norm Pallas kernels — the round-7 attack on the
ResNet BN-reduction wall (PERF.md r04 attribution: the 53 BNs' per-channel
sum/sum² reductions, forward AND backward, are ~90 ms per 16 steps of full
passes over the big NHWC activations; reference analogue: the cuDNN fused
CUDNN_BATCHNORM_SPATIAL_PERSISTENT ops reached through batch_norm_op.cu).

Three kernels, composed by ops/nn_ops.py `conv2d_bn` / the fused
`batch_norm` route (gate: FLAGS_fused_bn):

1. `dot_col_stats` — 1x1-conv-as-dot with a BN-statistics epilogue.
   A 1x1 stride-1 NHWC convolution IS a matmul over the collapsed
   [N*H*W, C_in] view (a free, layout-preserving reshape — the Pallas
   custom call accepts the activation's native NHWC row-major layout, so
   the r05 layout-dual collapse that killed the naive XLA-dot lowering,
   2521 -> 1412 img/s, cannot recur).  Per-channel sum/sum² of the conv
   output accumulate in VMEM as the M-grid walks: the activation is
   written once and NEVER re-read from HBM for statistics.
   Filter orientation: the kernel consumes w as [C_out, C_in] — the
   OIHW param's own 2-D view — and the custom VJP computes BOTH dx and
   dw from that same orientation (dx = dot(gy, w) contracting C_out,
   dw = dot(gy, x) contracting M).  No transposed filter dual exists
   anywhere in the fused 1x1 path, which is the r04 "momentum chain in
   two layout duals" fix for these sites.

2. `channel_stats` — one-pass per-channel sum/sum² of an NHWC activation
   (the stats epilogue for convs the dot path can't express: 3x3, 7x7,
   strided+padded).  Custom VJP: the stats cotangents fold into an
   effective dy (gy + gs1 + 2*y*gs2) that XLA fuses into whatever
   consumes it — the backward stat passes disappear into the conv
   backward.  Channels < 128 lanes fold into the lane dim (lane j is
   channel j % C when 128 % C == 0), so the 64-channel stem still gets
   the one-pass kernel.

3. `bn_apply` / `scale_shift_act` — the BN epilogue: normalize +
   scale/shift + optional residual add + optional ReLU in ONE read of
   the activation.  The custom VJP stores no normalized intermediate
   (FlashAttention-style recompute, Dao et al. 2022): the backward
   regenerates the ReLU mask from the saved output and x-hat from the
   saved conv output, and its Pallas kernel folds the dgamma/dbeta
   channel reductions INTO the dx pass — today those are separate full
   passes over the activation in the optimized HLO (tools/hlo_diag.py
   --bn-fusion counts them).

Cost model carried over from the r05 matmul_stats experiment (that module
is now a deprecation alias of this one): at the ResNet 1x1 shapes XLA's
plain dot beats a naive Pallas matmul by 35-50% at K=64/128, and XLA
already fuses per-column sum/sum² into a DOT's epilogue for free — so the
fused path must (a) only claim sites where the stats epilogue rides a
kernel that is at least throughput-neutral, and (b) keep the XLA
composition as the measured fallback.  Every entry point therefore
degrades to plain XLA when the tile plan fails, and bench.py
`--model convbn` measures fused-vs-XLA per shape (PERF.md r07 protocol).
"""

from __future__ import annotations

import functools

# Candidate tile sizes, largest first.  Sublane blocks must divide the row
# count and respect the dtype's min sublane tile (8 f32 / 16 bf16); lane
# blocks must be multiples of 128.
_ROW_BLOCKS = (512, 256, 128, 64, 32, 16, 8)
_COL_BLOCKS = (512, 256, 128)


class _Plan:
    __slots__ = ("rows", "ncols", "block_r", "block_c", "fold", "interpret")

    def __init__(self, rows, ncols, block_r, block_c, fold, interpret):
        self.rows = rows
        self.ncols = ncols
        self.block_r = block_r
        self.block_c = block_c
        self.fold = fold
        self.interpret = interpret


def _plan(rows, c, dtype, interpret):
    """Tile plan for a [rows, c] channel-minor view, or None -> XLA
    fallback.  c < 128 folds rows into lanes: [rows, c] is re-viewed as
    [rows*c/128, 128] (row-major flattening keeps lane j == channel
    j % c whenever 128 % c == 0)."""
    import numpy as np

    from .placement import resolve

    compiled, interpret = resolve(interpret)
    if not (compiled or interpret):
        return None
    fold = 1
    ncols = int(c)
    rows = int(rows)
    if ncols % 128 != 0:
        if 128 % ncols == 0 and (rows * ncols) % 128 == 0:
            fold = 128 // ncols
            rows = rows * ncols // 128
            ncols = 128
        else:
            return None
    sub = 16 if np.dtype(dtype).itemsize < 4 else 8
    block_r = next((b for b in _ROW_BLOCKS
                    if b % sub == 0 and rows % b == 0), 0)
    block_c = next((b for b in _COL_BLOCKS if ncols % b == 0), 0)
    if not block_r or not block_c:
        return None
    return _Plan(rows, ncols, block_r, block_c, fold, interpret)


def _fold_vec(v, fold):
    """Tile a [C] vector across the folded 128-lane view (lane j reads
    channel j % C)."""
    import jax.numpy as jnp

    return jnp.tile(v, fold) if fold > 1 else v


def _unfold_stats(s, fold, c):
    """Sum a folded [128] per-lane stat back to [C] per-channel."""
    if fold <= 1:
        return s
    return s.reshape(fold, c).sum(0)


def _stats_rows(tile8):
    """(s1, s2) from the kernels' (8, C) accumulator layout: rows 0-3 each
    hold s1/4, rows 4-7 each hold s2/4 (sublane-tile-filling trick carried
    over from the r05 matmul_stats kernel)."""
    return tile8[:4].sum(0), tile8[4:].sum(0)


def _stats_tile(s1, s2):
    import jax.numpy as jnp

    n = s1.shape[0]
    return jnp.concatenate(
        [jnp.broadcast_to(s1[None, :], (4, n)),
         jnp.broadcast_to(s2[None, :], (4, n))], axis=0) / 4.0


# ---------------------------------------------------------------------------
# channel_stats: one-pass per-channel sum / sum-of-squares
# ---------------------------------------------------------------------------


def _channel_stats_kernel(x_ref, stats_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    mi = pl.program_id(1)
    xs = x_ref[...].astype(jnp.float32)
    tile = _stats_tile(jnp.sum(xs, axis=0), jnp.sum(xs * xs, axis=0))

    @pl.when(mi == 0)
    def _init():
        stats_ref[...] = tile

    @pl.when(mi != 0)
    def _acc():
        stats_ref[...] += tile


def _channel_stats_impl(y, c, plan):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if plan is None:
        ys = y.astype(jnp.float32).reshape(-1, c)
        return ys.sum(0), (ys * ys).sum(0)
    y2 = y.reshape(plan.rows, plan.ncols)
    grid = (plan.ncols // plan.block_c, plan.rows // plan.block_r)
    stats = pl.pallas_call(
        _channel_stats_kernel,
        name="conv_bn_channel_stats_fwd",
        grid=grid,
        in_specs=[pl.BlockSpec((plan.block_r, plan.block_c),
                               lambda ni, mi: (mi, ni))],
        out_specs=pl.BlockSpec((8, plan.block_c), lambda ni, mi: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((8, plan.ncols), jnp.float32),
        interpret=plan.interpret,
    )(y2)
    s1, s2 = _stats_rows(stats)
    return _unfold_stats(s1, plan.fold, c), _unfold_stats(s2, plan.fold, c)


def channel_stats(y, interpret=None):
    """(s1, s2): f32 per-channel sum and sum-of-squares of `y` over all
    but the trailing (channel) dim, in ONE pass over y.

    Custom VJP: ds1/ds2 fold into dy = gs1 + 2*y*gs2 — an elementwise
    expression XLA fuses into dy's consumer, so the backward stat
    reductions cost no extra pass either."""
    import jax
    import jax.numpy as jnp

    c = int(y.shape[-1])
    rows = 1
    for s in y.shape[:-1]:
        rows *= int(s)
    plan = _plan(rows, c, y.dtype, interpret)

    @jax.custom_vjp
    def _cs(y):
        return _channel_stats_impl(y, c, plan)

    def _fwd(y):
        return _cs(y), y

    def _bwd(y, gs):
        gs1, gs2 = gs
        shape = (1,) * (y.ndim - 1) + (c,)
        gy = (gs1.reshape(shape)
              + 2.0 * y.astype(jnp.float32) * gs2.reshape(shape))
        return (gy.astype(y.dtype),)

    _cs.defvjp(_fwd, _bwd)
    return _cs(y)


# ---------------------------------------------------------------------------
# dot_col_stats: 1x1-conv-as-dot with statistics epilogue
# ---------------------------------------------------------------------------


def _dot_stats_kernel(x_ref, w_ref, y_ref, stats_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    mi = pl.program_id(1)
    # w is [C_out, C_in]: contract C_in of both operands (rhs-transposed
    # matmul — the single filter orientation shared with the backward)
    # bf16 operands pin the precision: every precision yields the same
    # products from them, and Mosaic refuses an fp32 contract precision
    # on bf16 ("Bad lhs type", libtpu 0.0.34) — which an ambient
    # jax.default_matmul_precision("highest") would otherwise request
    narrow = x_ref.dtype.itemsize < 4
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT if narrow else None,
        preferred_element_type=jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)
    # stats of the STORED dtype (the bf16-rounded y is what the BN
    # normalization and any recompute see)
    ys = y_ref[...].astype(jnp.float32)
    tile = _stats_tile(jnp.sum(ys, axis=0), jnp.sum(ys * ys, axis=0))

    @pl.when(mi == 0)
    def _init():
        stats_ref[...] = tile

    @pl.when(mi != 0)
    def _acc():
        stats_ref[...] += tile


def _dot_plan(m, oc, dtype, interpret):
    """(block_m, block_n, interpret) or None.  oc rides the lane dim of
    the output tile, so it must block in 128s; the contracted C_in stays
    unblocked (full-K tiles, the r05 plan that measured best)."""
    import numpy as np

    from .placement import resolve

    compiled, interpret = resolve(interpret)
    if not (compiled or interpret):
        return None
    sub = 16 if np.dtype(dtype).itemsize < 4 else 8
    block_m = next((b for b in _ROW_BLOCKS
                    if b % sub == 0 and m % b == 0), 0)
    block_n = next((b for b in _COL_BLOCKS if oc % b == 0), 0)
    if not block_m or not block_n:
        return None
    return block_m, block_n, interpret


def _dot_col_stats_impl(x2, w2, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    m, k = x2.shape
    oc, k2 = w2.shape
    assert k == k2, (x2.shape, w2.shape)
    plan = _dot_plan(m, oc, x2.dtype, interpret)
    if plan is None:
        y = jax.lax.dot_general(
            x2, w2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x2.dtype)
        ys = y.astype(jnp.float32)
        return y, ys.sum(0), (ys * ys).sum(0)
    block_m, block_n, interp = plan
    grid = (oc // block_n, m // block_m)  # m fastest: stats accumulate
    y, stats = pl.pallas_call(
        _dot_stats_kernel,
        name="conv_bn_dot_stats_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((block_n, k), lambda ni, mi: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda ni, mi: (mi, ni)),
            pl.BlockSpec((8, block_n), lambda ni, mi: (0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, oc), x2.dtype),
            jax.ShapeDtypeStruct((8, oc), jnp.float32),
        ],
        interpret=interp,
    )(x2, w2)
    return y, *_stats_rows(stats)


def dot_col_stats(x2, w2, interpret=None):
    """(y, s1, s2) with y = x2 @ w2.T for x2 [M, C_in], w2 [C_out, C_in];
    s1/s2 are f32 [C_out] per-column sum / sum² of y, accumulated in the
    dot's epilogue (y is never re-read from HBM for statistics).

    The custom VJP folds the stats cotangents into an effective dY
    (dY_eff = dY + ds1 + 2*y*ds2 — they are linear/quadratic in y) and
    computes dx and dw from the SAME [C_out, C_in] filter orientation the
    forward consumed: no transposed filter copy exists in this path."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def _dot(x2, w2):
        return _dot_col_stats_impl(x2, w2, interpret)

    def _fwd(x2, w2):
        y, s1, s2 = _dot_col_stats_impl(x2, w2, interpret)
        return (y, s1, s2), (x2, w2, y)

    def _bwd(res, gs):
        x2, w2, y = res
        gy, gs1, gs2 = gs
        gy_eff = (gy.astype(jnp.float32) + gs1[None, :]
                  + 2.0 * y.astype(jnp.float32) * gs2[None, :])
        gy_eff = gy_eff.astype(x2.dtype)
        # dx: contract C_out -> [M, C_in]; dw: contract M -> [C_out, C_in].
        # Both consume w2/produce dw in the forward's orientation.
        dx = jax.lax.dot_general(
            gy_eff, w2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x2.dtype)
        dw = jax.lax.dot_general(
            gy_eff, x2, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(w2.dtype)
        return dx, dw

    _dot.defvjp(_fwd, _bwd)
    return _dot(x2, w2)


def matmul_col_stats(x, w, block_m=512, block_n=512, interpret=None):
    """r05-compat entry point: (y, sum, sqsum) with y = x @ w for x [M, K],
    w [K, N].  Kept for the measured-negative-result record (PERF.md r05);
    new code should use dot_col_stats ([N, K] filter orientation) or
    conv_bn_stats.  block_m/block_n are accepted for signature parity and
    superseded by the internal tile plan."""
    del block_m, block_n
    return dot_col_stats(x, w.T, interpret=interpret)


# ---------------------------------------------------------------------------
# conv + stats composition
# ---------------------------------------------------------------------------


def conv_bn_stats(x, w, strides=(1, 1), paddings=(0, 0), dilations=(1, 1),
                  groups=1, interpret=None):
    """(y, s1, s2): NHWC conv2d output plus its f32 per-channel sum/sum²,
    with the statistics riding a kernel epilogue instead of separate
    reduction passes.  w is OIHW (the framework's checkpoint layout).

    1x1 unpadded undilated ungrouped convs lower as the dot_col_stats
    kernel over the collapsed [N*H*W, C] view (strided 1x1 pre-slices the
    rows — the same work the conv window would skip); everything else runs
    XLA's conv (the r05 measurement: beating XLA's conv schedule is not
    the goal — removing the stats passes around it is) followed by the
    one-pass channel_stats epilogue."""
    import jax.lax as lax

    oc, ic_g, kh, kw = w.shape
    strides = tuple(int(s) for s in strides)
    paddings = tuple(int(p) for p in paddings)
    dilations = tuple(int(d) for d in dilations)
    one_by_one = (kh == 1 and kw == 1 and paddings == (0, 0)
                  and dilations == (1, 1) and (groups or 1) == 1)
    if one_by_one:
        if strides != (1, 1):
            x = x[:, ::strides[0], ::strides[1], :]
        n, h, wd, ic = x.shape
        y2, s1, s2 = dot_col_stats(
            x.reshape(n * h * wd, ic), w.reshape(oc, ic_g),
            interpret=interpret)
        return y2.reshape(n, h, wd, oc), s1, s2
    y = lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(paddings[0], paddings[0]), (paddings[1], paddings[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        feature_group_count=groups or 1,
    )
    s1, s2 = channel_stats(y, interpret=interpret)
    return y, s1, s2


# ---------------------------------------------------------------------------
# bn_apply: normalize + scale/shift + residual + ReLU epilogue
# ---------------------------------------------------------------------------


def _ssa_fwd_kernel(wb_ref, x_ref, *rest, relu, has_res):
    import jax.numpy as jnp

    if has_res:
        r_ref, o_ref = rest
    else:
        (o_ref,) = rest
    x = x_ref[...]
    # (1, C) row slices broadcast against the (block_r, C) tile (2-D
    # broadcasts are the Mosaic-safe idiom — PERF.md r04 pitfall (a))
    w = wb_ref[0:1, :].astype(x.dtype)
    b = wb_ref[1:2, :].astype(x.dtype)
    out = x * w + b
    if has_res:
        out = out + r_ref[...].astype(x.dtype)
    if relu:
        out = jnp.maximum(out, jnp.zeros((), x.dtype))
    o_ref[...] = out


def _ssa_bwd_kernel(wb_ref, g_ref, x_ref, *rest, relu, has_res):
    """dx tile + dres tile + the dwv/dbv channel reductions, all in the
    SAME read of (g, out, x) — the backward's separate dgamma/dbeta
    full-pass reductions fold into the dx pass."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rest = list(rest)
    stats_ref = rest.pop()
    o_ref = rest.pop(0) if relu else None
    dx_ref = rest.pop(0)
    dres_ref = rest.pop(0) if has_res else None

    mi = pl.program_id(1)
    g = g_ref[...]
    if relu:
        # compare in f32: v5e has no bf16 vector compare ("Target does
        # not support this comparison", Mosaic on arith.cmpf bf16)
        g = jnp.where(o_ref[...].astype(jnp.float32) > 0, g,
                      jnp.zeros((), g.dtype))
    w = wb_ref[0:1, :].astype(g.dtype)
    dx_ref[...] = g * w
    if has_res:
        dres_ref[...] = g.astype(dres_ref.dtype)
    g32 = g.astype(jnp.float32)
    x32 = x_ref[...].astype(jnp.float32)
    tile = _stats_tile(jnp.sum(g32, axis=0), jnp.sum(g32 * x32, axis=0))

    @pl.when(mi == 0)
    def _init():
        stats_ref[...] = tile

    @pl.when(mi != 0)
    def _acc():
        stats_ref[...] += tile


def _wb_mat(wv, bv, fold, ncols):
    """Pack the per-channel scale/shift into one (8, ncols) f32 operand
    (row 0 = w, row 1 = b; 8 rows fill the f32 sublane tile)."""
    import jax.numpy as jnp

    wb = jnp.zeros((8, ncols), jnp.float32)
    wb = wb.at[0].set(_fold_vec(wv, fold))
    return wb.at[1].set(_fold_vec(bv, fold))


def _ssa_fwd_impl(x, wv, bv, residual, relu, c, plan):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if plan is None:
        shape = (1,) * (x.ndim - 1) + (c,)
        out = (x * wv.astype(x.dtype).reshape(shape)
               + bv.astype(x.dtype).reshape(shape))
        if residual is not None:
            out = out + residual.astype(x.dtype)
        if relu:
            out = jnp.maximum(out, jnp.zeros((), x.dtype))
        return out
    shape = x.shape
    x2 = x.reshape(plan.rows, plan.ncols)
    spec = pl.BlockSpec((plan.block_r, plan.block_c),
                        lambda ni, mi: (mi, ni))
    wb_spec = pl.BlockSpec((8, plan.block_c), lambda ni, mi: (0, ni))
    operands = [_wb_mat(wv, bv, plan.fold, plan.ncols), x2]
    in_specs = [wb_spec, spec]
    if residual is not None:
        operands.append(residual.reshape(plan.rows, plan.ncols))
        in_specs.append(spec)
    grid = (plan.ncols // plan.block_c, plan.rows // plan.block_r)
    out = pl.pallas_call(
        functools.partial(_ssa_fwd_kernel, relu=relu,
                          has_res=residual is not None),
        name="conv_bn_scale_shift_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((plan.rows, plan.ncols), x.dtype),
        interpret=plan.interpret,
    )(*operands)
    return out.reshape(shape)


def _ssa_bwd_impl(g, out, x, wv, residual_dtype, relu, c, plan):
    """(dx, dres_or_None, S_g, S_gx): the fused backward pass.
    S_g = per-channel sum of the (ReLU-masked) cotangent, S_gx = sum of
    cotangent * x — i.e. d(bv) and d(wv)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    has_res = residual_dtype is not None
    if plan is None:
        if relu:
            g = jnp.where(out > 0, g, jnp.zeros((), g.dtype))
        shape = (1,) * (x.ndim - 1) + (c,)
        dx = g * wv.astype(g.dtype).reshape(shape)
        dres = g.astype(residual_dtype) if has_res else None
        g32 = g.astype(jnp.float32).reshape(-1, c)
        x32 = x.astype(jnp.float32).reshape(-1, c)
        return dx, dres, g32.sum(0), (g32 * x32).sum(0)
    shape = x.shape
    g2 = g.reshape(plan.rows, plan.ncols)
    x2 = x.reshape(plan.rows, plan.ncols)
    spec = pl.BlockSpec((plan.block_r, plan.block_c),
                        lambda ni, mi: (mi, ni))
    wb_spec = pl.BlockSpec((8, plan.block_c), lambda ni, mi: (0, ni))
    operands = [_wb_mat(wv, jnp.zeros_like(wv), plan.fold, plan.ncols),
                g2, x2]
    in_specs = [wb_spec, spec, spec]
    if relu:
        operands.append(out.reshape(plan.rows, plan.ncols))
        in_specs.append(spec)
    out_specs = [spec]
    out_shape = [jax.ShapeDtypeStruct((plan.rows, plan.ncols), x.dtype)]
    if has_res:
        out_specs.append(spec)
        out_shape.append(
            jax.ShapeDtypeStruct((plan.rows, plan.ncols), residual_dtype))
    out_specs.append(wb_spec)
    out_shape.append(jax.ShapeDtypeStruct((8, plan.ncols), jnp.float32))
    grid = (plan.ncols // plan.block_c, plan.rows // plan.block_r)
    res = pl.pallas_call(
        functools.partial(_ssa_bwd_kernel, relu=relu, has_res=has_res),
        name="conv_bn_scale_shift_bwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=plan.interpret,
    )(*operands)
    dx = res[0].reshape(shape)
    dres = res[1].reshape(shape) if has_res else None
    s_g, s_gx = _stats_rows(res[-1])
    return (dx, dres, _unfold_stats(s_g, plan.fold, c),
            _unfold_stats(s_gx, plan.fold, c))


def scale_shift_act(x, wv, bv, residual=None, relu=False, interpret=None):
    """out = [relu](x * wv + bv [+ residual]) with wv/bv f32 per-channel
    vectors applied in x's dtype (the reference batch_norm lowering's
    folded form) — one fused kernel forward, and a custom VJP whose
    backward folds the dwv/dbv channel reductions into the dx pass.

    The only fwd->bwd residuals are x, the output (for ReLU-mask
    regeneration — both already live as neighboring layers' activations)
    and the [C] vectors: no normalized intermediate or mask is stored."""
    import jax

    c = int(x.shape[-1])
    rows = 1
    for s in x.shape[:-1]:
        rows *= int(s)
    plan = _plan(rows, c, x.dtype, interpret)
    relu = bool(relu)
    rdt = residual.dtype if residual is not None else None

    if residual is None:
        @jax.custom_vjp
        def _ssa(x, wv, bv):
            return _ssa_fwd_impl(x, wv, bv, None, relu, c, plan)

        def _fwd(x, wv, bv):
            out = _ssa(x, wv, bv)
            return out, (x, wv, out if relu else None)

        def _bwd(saved, g):
            x, wv, out = saved
            dx, _, s_g, s_gx = _ssa_bwd_impl(g, out, x, wv, None, relu, c,
                                             plan)
            return dx, s_gx.astype(wv.dtype), s_g.astype(wv.dtype)

        _ssa.defvjp(_fwd, _bwd)
        return _ssa(x, wv, bv)

    @jax.custom_vjp
    def _ssa_res(x, wv, bv, residual):
        return _ssa_fwd_impl(x, wv, bv, residual, relu, c, plan)

    def _fwd(x, wv, bv, residual):
        out = _ssa_res(x, wv, bv, residual)
        return out, (x, wv, out if relu else None)

    def _bwd(saved, g):
        x, wv, out = saved
        dx, dres, s_g, s_gx = _ssa_bwd_impl(g, out, x, wv, rdt, relu, c,
                                            plan)
        return dx, s_gx.astype(wv.dtype), s_g.astype(wv.dtype), dres

    _ssa_res.defvjp(_fwd, _bwd)
    return _ssa_res(x, wv, bv, residual)


def bn_apply(x, scale, bias, mean, var, residual=None, eps=1e-5,
             act="", interpret=None):
    """Batch-norm application epilogue: normalize x with (mean, var), apply
    scale/shift, then the optional residual add and ReLU — one kernel, one
    read of x.  mean/var may be traced batch statistics (training: their
    gradients flow through the [C]-vector folding below and back into the
    stats producers) or global running stats (inference).

    act: "" (identity) or "relu"."""
    import jax
    import jax.numpy as jnp

    if act not in ("", "relu", None):
        raise ValueError(f"bn_apply: unsupported act {act!r} "
                         "(fusable epilogues: '', 'relu')")
    # [C]-vector folding in fp32 (outside the custom-vjp boundary, so
    # autodiff routes the kernel's dwv/dbv straight to scale/bias/mean/var)
    istd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    wv = scale.astype(jnp.float32) * istd
    bv = bias.astype(jnp.float32) - mean.astype(jnp.float32) * wv
    return scale_shift_act(x, wv, bv, residual=residual,
                           relu=(act == "relu"), interpret=interpret)
