"""Ops of a pre-norm decoder block with a sparse expert layer: RMS norm,
rotary positions (interleaved or half-split pairs, positions that may
restart along a row), SwiGLU, a gated short convolution (kernels/
short_conv.py), a sigmoid or softmax top-k router and the experts a chip
holds (grouped matmuls over the routed pairs sorted by expert, kernels/
grouped_matmul.py).

Each has a forward lowering and a registered grad op (registry.
residual_grad): the grad op reads what the forward wrote (`InvRms`,
`Scores` and `TopkIdx`, `H` and `Load`) instead of re-running the
forward under `jax.vjp`, so no kernel of the expert layer runs twice a
step.  The numerically sensitive parts (norm statistics, rotation,
silu, the router's scores) are float32 inside whatever the operands'
dtype; results come back in the activation's dtype (amp.py keeps the
router in float32 and hands the expert matmuls bfloat16 operands)."""

from __future__ import annotations

from ..core.registry import register, residual_grad


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# rms_norm
# ---------------------------------------------------------------------------


@register("rms_norm", residuals=("InvRms",))
def lower_rms_norm(ctx, ins):
    """Y = X * rsqrt(mean(X^2, last axis) + epsilon) * Scale.  InvRms
    ([.., 1] float32) is the residual rms_norm_grad reads."""
    import jax
    import jax.numpy as jnp

    x, scale = ins["X"][0], ins["Scale"][0]
    xs = _f32(x)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xs), axis=-1, keepdims=True)
                        + ctx.attr("epsilon", 1e-6))
    return {"Y": [(xs * inv * _f32(scale)).astype(x.dtype)], "InvRms": [inv]}


@residual_grad("rms_norm")
def lower_rms_norm_grad(ctx, ins):
    """dX and dScale from X, Scale, the forward's InvRms and Y@GRAD."""
    import jax.numpy as jnp

    x, scale, inv = ins["X"][0], ins["Scale"][0], ins["InvRms"][0]
    dy = _f32(ins["Y@GRAD"][0]).reshape(x.shape)
    xhat = _f32(x) * inv
    g = dy * _f32(scale)
    dx = inv * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum((dy * xhat).reshape(-1, x.shape[-1]), axis=0)
    return {"X@GRAD": [dx.astype(x.dtype)],
            "Scale@GRAD": [dscale.astype(scale.dtype)]}


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------


def _rope_tables(t, d, theta, period):
    """cos, sin [t, d/2] of position p times theta^(-2i/d), float32; with
    a `period` the position of row p is p mod period."""
    import jax.numpy as jnp

    inv_freq = jnp.power(
        jnp.float32(theta), -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = jnp.arange(t, dtype=jnp.float32)
    if period:
        pos = (jnp.arange(t, dtype=jnp.int32) % period).astype(jnp.float32)
    angle = pos[:, None] * inv_freq[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rotate(ctx, x, sign):
    """x [b, t, h, d]: each pair turned by sign * its position's angle.
    Attr `pairing`: "interleaved" pairs (x[2i], x[2i+1]) or "half" pairs
    (x[i], x[i + d/2]); the output keeps the input's layout."""
    import jax.numpy as jnp

    b, t, h, d = x.shape
    cos, sin = _rope_tables(t, d, ctx.attr("theta", 10000.0),
                            ctx.attr("period", 0))
    cos, sin = cos[None, :, None, :], sign * sin[None, :, None, :]
    pairing = ctx.attr("pairing", "interleaved")
    if pairing == "half":
        x0, x1 = _f32(x[..., :d // 2]), _f32(x[..., d // 2:])
        out = jnp.concatenate([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                              axis=-1)
        return out.astype(x.dtype)
    if pairing != "interleaved":
        raise ValueError(f"rope: unknown pairing {pairing!r}")
    pairs = _f32(x).reshape(b, t, h, d // 2, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@register("rope")
def lower_rope(ctx, ins):
    """Rotary position embedding of X [b, t, h, d], attrs `theta`,
    `pairing` ("interleaved", `rope_interleave`, or "half", the
    `rotate_half` layout) and `period`: positions count from 0 along axis
    1 and, with a period, start again every `period` rows (the two halves
    of a [noisy ; clean] row both count 0 .. L-1)."""
    return {"Out": [_rotate(ctx, ins["X"][0], 1.0)]}


@residual_grad("rope")
def lower_rope_grad(ctx, ins):
    """A rotation's transpose is the rotation back."""
    x = ins["X"][0]
    g = ins["Out@GRAD"][0].astype(x.dtype).reshape(x.shape)
    return {"X@GRAD": [_rotate(ctx, g, -1.0)]}


# ---------------------------------------------------------------------------
# swiglu
# ---------------------------------------------------------------------------


def _swiglu_parts(x):
    import jax

    f = x.shape[-1] // 2
    gate, up = _f32(x[..., :f]), _f32(x[..., f:])
    sig = jax.nn.sigmoid(gate)
    return gate, up, sig


@register("swiglu")
def lower_swiglu(ctx, ins):
    """Out = silu(X[.., :f]) * X[.., f:], X the packed [gate | up]."""
    x = ins["X"][0]
    gate, up, sig = _swiglu_parts(x)
    return {"Out": [(gate * sig * up).astype(x.dtype)]}


def _swiglu_bwd(x, d_act):
    """d[gate | up] from d(silu(gate) * up), float32 in and out."""
    import jax.numpy as jnp

    gate, up, sig = _swiglu_parts(x)
    d_gate = d_act * up * sig * (1.0 + gate * (1.0 - sig))
    return jnp.concatenate([d_gate, d_act * gate * sig], axis=-1)


@residual_grad("swiglu")
def lower_swiglu_grad(ctx, ins):
    x = ins["X"][0]
    g = _f32(ins["Out@GRAD"][0]).reshape(x.shape[:-1] + (x.shape[-1] // 2,))
    return {"X@GRAD": [_swiglu_bwd(x, g).astype(x.dtype)]}


# ---------------------------------------------------------------------------
# short_conv
# ---------------------------------------------------------------------------


@register("short_conv")
def lower_short_conv(ctx, ins):
    """Out [b, t, d] = C * causal_depthwise(B * x) of X [b, t, 3d] = [B |
    C | x] and Filter [d, L]: c[t] = sum_j Filter[:, j] * (B * x)[t - (L-1)
    + j] within a row, zeros before its start (LFM2's gated short
    convolution between its in- and out-projection).  The Pallas pair of
    kernels/short_conv.py on the TPU, its XLA composition elsewhere."""
    from ..kernels.short_conv import short_conv

    return {"Out": [short_conv(ins["X"][0], ins["Filter"][0])]}


@residual_grad("short_conv")
def lower_short_conv_grad(ctx, ins):
    """dX and dFilter (summed over batch and time in float32) from X, the
    filter and Out@GRAD: B * x and the convolution are made again from X,
    which is cheaper than keeping them."""
    from ..kernels.short_conv import short_conv_bwd

    x, w = ins["X"][0], ins["Filter"][0]
    dx, dw = short_conv_bwd(x, w, ins["Out@GRAD"][0])
    return {"X@GRAD": [dx], "Filter@GRAD": [dw.astype(w.dtype)]}


# ---------------------------------------------------------------------------
# moe_router
# ---------------------------------------------------------------------------


def _router_dot(a, b, dims):
    import jax

    return jax.lax.dot_general(_f32(a), _f32(b), (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def _router_scoring(ctx):
    scoring = ctx.attr("scoring", "sigmoid")
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_router: unknown scoring {scoring!r}")
    return scoring


@register("moe_router", residuals=("Scores", "TopkIdx"))
def lower_moe_router(ctx, ins):
    """Scores over all experts, float32 at the highest matmul precision:
    attr `scoring` = "sigmoid" (each expert's own; DeepSeek-V3) or
    "softmax" (over the experts; the Qwen3-MoE lineage).  The top_k of
    (scores + Bias) are chosen (`noaux_tc` with one group: the correction
    bias enters the choice only; Bias may be absent) and weighted by their
    own scores, normalised over the chosen (`norm_topk_prob`: their sum
    plus `norm_eps`, 1e-20 where a family states none) and times `scale`.

    X [.., d], W [d, E], Bias [E] or none -> TopkIdx [T, k] int32,
    TopkWeight [T, k] float32, Scores [T, E] float32 (T = the leading dims
    flattened)."""
    import jax
    import jax.numpy as jnp

    x, w = ins["X"][0], ins["W"][0]
    bias = ins.get("Bias", [None])[0]
    logits = _router_dot(x.reshape(-1, x.shape[-1]), w, ((1,), (0,)))
    scores = (jax.nn.sigmoid(logits) if _router_scoring(ctx) == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, idx = jax.lax.top_k(
        scores if bias is None else scores + _f32(bias)[None, :],
        ctx.attr("top_k", 8))
    idx = idx.astype(jnp.int32)
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    weight = chosen * (ctx.attr("scale", 1.0)
                       / (jnp.sum(chosen, axis=1, keepdims=True)
                          + ctx.attr("norm_eps", 1e-20)))
    return {"TopkIdx": [idx], "TopkWeight": [weight], "Scores": [scores]}


@residual_grad("moe_router")
def lower_moe_router_grad(ctx, ins):
    """dX and dW through the chosen scores; the choice itself and the
    bias carry no gradient."""
    import jax.numpy as jnp

    x, w = ins["X"][0], ins["W"][0]
    scores, idx = ins["Scores"][0], ins["TopkIdx"][0]
    dw_pair = _f32(ins["TopkWeight@GRAD"][0]).reshape(idx.shape)
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    total = jnp.sum(chosen, axis=1, keepdims=True) + ctx.attr(
        "norm_eps", 1e-20)
    d_chosen = ctx.attr("scale", 1.0) * (
        dw_pair / total
        - jnp.sum(dw_pair * chosen, axis=1, keepdims=True) / (total * total))
    rows = jnp.arange(idx.shape[0], dtype=jnp.int32)[:, None]
    d_scores = jnp.zeros_like(scores).at[rows, idx].add(d_chosen)
    if _router_scoring(ctx) == "sigmoid":
        d_logits = d_scores * scores * (1.0 - scores)
    else:
        d_logits = scores * (d_scores - jnp.sum(
            d_scores * scores, axis=1, keepdims=True))
    x2 = x.reshape(-1, x.shape[-1])
    dx = _router_dot(d_logits, w, ((1,), (1,)))
    grads = {"X@GRAD": [dx.astype(x.dtype).reshape(x.shape)],
             "W@GRAD": [_router_dot(x2, d_logits, ((0,), (0,))).astype(
                 w.dtype)]}
    if "Bias" in ins:
        grads["Bias@GRAD"] = [None]
    return grads


# ---------------------------------------------------------------------------
# moe_experts
# ---------------------------------------------------------------------------


def _dispatch(idx, n_held, offset):
    """The routed (token, choice) pairs sorted by held expert.

    idx [T, k] are expert ids over the whole layer; this chip holds
    experts offset .. offset + n_held - 1.  Returns (order [T*k]: the
    pairs' flat indices, held experts' first and in expert order, pairs of
    absent experts last; load [n_held] int32: pairs an expert)."""
    import jax.numpy as jnp

    local = idx.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    load = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=key.dtype),
                   axis=0, dtype=jnp.int32)
    return order, load


def chunk_rows(pairs, held, routed):
    """Rows of one chunk of the walk over `pairs` sorted (token, choice)
    pairs, for a chip that holds `held` of the layer's `routed` experts:
    the smallest of a quarter, a half and all of the pairs that holds
    twice the held experts' mean load, pairs x held / routed, in whole row
    tiles of the grouped matmuls.  A share of an eighth or a sixteenth of
    the experts walks a quarter and a share of a quarter a half, one trip
    at all but rare loads (a quarter share at a quarter chunk takes a
    second trip in every other layer-step: +2.9 % of the step in
    lfm2_8b_a1b_ep4_train, PERF.md section 6).  A layer that holds every
    expert walks all its pairs whatever the chunk and needs no such room:
    a quarter, four trips.  All of them, one chunk, where that part is not
    whole tiles.  Fixed by the shapes: no caller chooses it."""
    from ..kernels.grouped_matmul import ROW_TILE

    parts = 4 if held == routed else next(
        (n for n in (4, 2) if n * 2 * held <= routed), 1)
    return pairs // parts if pairs % (parts * ROW_TILE) == 0 else pairs


def rows_walked(live, pairs, held, routed, ceil_div=lambda a, b: -(-a // b)):
    """Rows the walk visits for `live` held pairs of `pairs`: whole chunks,
    trips x R.  THE rule, for the op's trip count and for the counter
    `moe_rows_walked` (models/mla_moe_decoder.py), which hands in a
    `ceil_div` over a program's variables."""
    rows = chunk_rows(pairs, held, routed)
    return ceil_div(live, rows) * rows


def _walk(load, order, routed):
    """How the op walks its sorted pairs: in chunks of R = chunk_rows rows,
    as many as hold a live row.  Returns (R, the held experts' offsets
    [G+1] in the sorted order, live = the held experts' pairs, trips =
    ceil(live / R), back [T*k]: where each pair sits in the sorted
    order)."""
    import jax.numpy as jnp

    pairs, held = order.shape[0], load.shape[0]
    rows = chunk_rows(pairs, held, routed)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(load, dtype=jnp.int32)])
    return (rows, offsets, offsets[-1],
            rows_walked(offsets[-1], pairs, held, routed) // rows,
            jnp.argsort(order).astype(order.dtype))


def _chunk(c, rows, offsets, order):
    """Chunk c of the walk: (its first row, its pairs' flat indices [R],
    the rows of it each held expert owns [G])."""
    import jax
    import jax.numpy as jnp

    base = c * rows
    edges = jnp.clip(offsets, base, base + rows)
    return (base, jax.lax.dynamic_slice(order, (base,), (rows,)),
            edges[1:] - edges[:-1])


def _put(buffer, chunk, base):
    import jax

    return jax.lax.dynamic_update_slice(
        buffer, chunk, (base,) + (0,) * (chunk.ndim - 1))


def _add(total, new):
    """total + new in float32, kept in total's dtype."""
    return (_f32(total) + _f32(new)).astype(total.dtype)


def _tokens_sum(chunk, base, back, live, t, k, weight=None):
    """Each token's pairs that sit in ONE chunk [R, d] of the sorted
    order, summed (times the pairs' `weight` [T, k]) -> [T, d] float32.
    A pair outside the chunk's live rows reads some row of it and is
    discarded by the select: the kernels do not write dead rows.  The
    gather's source is the chunk, not a T x top_k buffer: small enough
    for XLA to hold it in VMEM, where a row costs a fifth of what it
    costs from HBM (PERF.md section 6, PR 32)."""
    import jax.numpy as jnp

    rows = chunk.shape[0]
    local = back - base
    kept = (local >= 0) & (local < jnp.minimum(rows, live - base))
    pairs = jnp.where(kept[:, None],
                      _f32(chunk[jnp.clip(local, 0, rows - 1)]), 0.0)
    pairs = pairs.reshape(t, k, -1)
    if weight is not None:
        pairs = pairs * _f32(weight)[:, :, None]
    return jnp.sum(pairs, axis=1)


@register("moe_experts", residuals=("H", "Load", "Order"), unfilled=("H",))
def lower_moe_experts(ctx, ins):
    """Out[t] = sum over the chosen experts i THIS CHIP HOLDS of
    TopkWeight[t, i] * SwiGLU_i(X[t]); what absent experts would add is
    left out (one chip's share of an expert-parallel layer; there is no
    exchange on one chip).

    X [.., d], TopkIdx / TopkWeight [T, k], WGateUp [G, d, 2f] (packed
    gate | up), WDown [G, f, d]; attrs `expert_offset`: the first held
    expert's id, and `n_experts`: the router's width, which with G sets
    the walk's chunk (`chunk_rows`).  The residuals: H [T*k, 2f] (the
    pairs' gate | up pre-activations, sorted by expert; rows past the held
    experts' are never written: `unfilled`), Load [G] (pairs an expert),
    Order [T*k] (the sort).  Dropless: every pair of a held expert is
    computed.  The sorted pairs are walked in chunks (`_walk`) under a
    traced trip count, so apart from H every array made here has a chunk's
    rows or a token's, and the work follows the pairs the held experts
    really got."""
    import jax
    import jax.numpy as jnp

    from ..kernels.grouped_matmul import grouped_matmul

    x, idx, weight = ins["X"][0], ins["TopkIdx"][0], ins["TopkWeight"][0]
    t, k = idx.shape
    dt = x.dtype
    w_gu, w_down = ins["WGateUp"][0].astype(dt), ins["WDown"][0].astype(dt)
    x2 = x.reshape(t, -1)
    order, load = _dispatch(idx, w_gu.shape[0], ctx.attr("expert_offset", 0))
    rows, offsets, live, trips, back = _walk(
        load, order, ctx.attr("n_experts"))

    def chunk(c, carry):
        h_all, out = carry
        base, pairs, sizes = _chunk(c, rows, offsets, order)
        h = grouped_matmul(x2[pairs // k], w_gu, sizes)
        gate, up, sig = _swiglu_parts(h)
        y = grouped_matmul((gate * sig * up).astype(dt), w_down, sizes)
        # the pair's weight in float32, a token's choices summed in float32
        return _put(h_all, h, base), out + _tokens_sum(
            y, base, back, live, t, k, weight)

    def start():  # H is allocated and never filled
        return (jax.lax.empty((t * k, w_gu.shape[2]), dt),
                jnp.zeros(x2.shape, jnp.float32))

    # the conditional is there for XLA's scheduler alone (a loop of no
    # trip is the same result): an operand-free allocation is scheduled
    # at the start of the computation it is in, so free-standing every
    # layer's H would be live from the step's first operation; in a branch
    # it is allocated where the walk runs (PERF.md section 7 (11))
    h_all, out = jax.lax.cond(
        trips > 0, lambda: jax.lax.fori_loop(0, trips, chunk, start()), start)
    return {"Out": [out.astype(dt).reshape(x.shape)], "H": [h_all],
            "Load": [load], "Order": [order]}


@residual_grad("moe_experts")
def lower_moe_experts_grad(ctx, ins):
    """The two dX and two dW grouped matmuls on the forward's own H, over
    the forward's chunks.  A dW is summed over the chunks in float32 and
    kept in the stream's dtype between them, as the kernel hands it over:
    at one trip it is the kernel's own result, at n trips it has been
    rounded n times where one float32 accumulation rounds once."""
    import jax
    import jax.numpy as jnp

    from ..kernels.grouped_matmul import grouped_matmul, grouped_matmul_dw

    x, idx = ins["X"][0], ins["TopkIdx"][0]
    h_all, load, order = ins["H"][0], ins["Load"][0], ins["Order"][0]
    t, k = idx.shape
    dt = x.dtype
    w_gu, w_down = ins["WGateUp"][0].astype(dt), ins["WDown"][0].astype(dt)
    x2 = x.reshape(t, -1)
    g2 = ins["Out@GRAD"][0].astype(dt).reshape(t, -1)
    weight = _f32(ins["TopkWeight"][0]).reshape(-1)
    wants_weight = any(ctx.op.output("TopkWeight@GRAD"))
    rows, offsets, live, trips, back = _walk(
        load, order, ctx.attr("n_experts"))

    def chunk(c, carry):
        dx, dwt_all, dw_gu, dw_down = carry
        base, pairs, sizes = _chunk(c, rows, offsets, order)
        x_c, g_c = x2[pairs // k], g2[pairs // k]
        w_c = weight[pairs][:, None]
        h = jax.lax.dynamic_slice(h_all, (base, 0), (rows, h_all.shape[1]))
        gate, up, sig = _swiglu_parts(h)
        act = gate * sig * up
        # d(act) before the pair's weight: its product with act is the
        # weight's own gradient
        d_act = _f32(grouped_matmul(g_c, w_down, sizes, transpose_rhs=True))
        if wants_weight:
            dwt_all = _put(dwt_all, jnp.sum(d_act * act, axis=1), base)
        dw_down = _add(dw_down, grouped_matmul_dw(
            (act * w_c).astype(dt), g_c, sizes))
        d_h = _swiglu_bwd(h, d_act * w_c).astype(dt)
        dw_gu = _add(dw_gu, grouped_matmul_dw(x_c, d_h, sizes))
        d_rows = grouped_matmul(d_h, w_gu, sizes, transpose_rhs=True)
        return (dx + _tokens_sum(d_rows, base, back, live, t, k),
                dwt_all, dw_gu, dw_down)

    dx, d_weight, dw_gu, dw_down = jax.lax.fori_loop(0, trips, chunk, (
        jnp.zeros(x2.shape, jnp.float32),
        jnp.zeros((t * k,), jnp.float32) if wants_weight else None,
        jnp.zeros(w_gu.shape, dt), jnp.zeros(w_down.shape, dt)))
    if wants_weight:  # back to the pairs' own order; a dead pair's is zero
        d_weight = jnp.where(back < live, d_weight[back], 0.0).reshape(t, k)
    return {"X@GRAD": [dx.astype(dt).reshape(x.shape)],
            "TopkIdx@GRAD": [None],
            "TopkWeight@GRAD": [d_weight],
            "WGateUp@GRAD": [dw_gu.astype(ins["WGateUp"][0].dtype)],
            "WDown@GRAD": [dw_down.astype(ins["WDown"][0].dtype)]}
