"""Ops of a pre-norm decoder block with a sparse expert layer: RMS norm,
rotary positions (interleaved or half-split pairs, positions that may
restart along a row), SwiGLU, a sigmoid or softmax top-k router and the
experts a chip holds (grouped matmuls over the routed pairs sorted by
expert, kernels/grouped_matmul.py).

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
    own scores, normalised over the chosen (`norm_topk_prob`) and times
    `scale`.

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
                       / (jnp.sum(chosen, axis=1, keepdims=True) + 1e-20))
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
    total = jnp.sum(chosen, axis=1, keepdims=True) + 1e-20
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


def _combine(rows, order, t, k):
    """Sum each token's pairs: rows [T*k, ..] in sorted order -> [T, ..]
    float32."""
    import jax.numpy as jnp

    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return jnp.sum(_f32(rows[back]).reshape((t, k) + rows.shape[1:]), axis=1)


@register("moe_experts", residuals=("H", "Load"))
def lower_moe_experts(ctx, ins):
    """Out[t] = sum over the chosen experts i THIS CHIP HOLDS of
    TopkWeight[t, i] * SwiGLU_i(X[t]); what absent experts would add is
    left out (one chip's share of an expert-parallel layer; there is no
    exchange on one chip).

    X [.., d], TopkIdx / TopkWeight [T, k], WGateUp [G, d, 2f] (packed
    gate | up), WDown [G, f, d]; attr `expert_offset`: the first held
    expert's id.  H [T*k, 2f] (the pairs' gate | up pre-activations,
    sorted by expert) and Load [G] (pairs an expert) are the residuals.
    Dropless: every pair of a held expert is computed."""
    import jax.numpy as jnp

    from ..kernels.grouped_matmul import grouped_matmul

    x, idx = ins["X"][0], ins["TopkIdx"][0]
    w_gu, w_down = ins["WGateUp"][0], ins["WDown"][0]
    t, k = idx.shape
    order, load = _dispatch(idx, w_gu.shape[0], ctx.attr("expert_offset", 0))
    x_sorted = x.reshape(t, -1)[order // k]
    h = grouped_matmul(x_sorted, w_gu.astype(x.dtype), load)
    gate, up, sig = _swiglu_parts(h)
    y = grouped_matmul((gate * sig * up).astype(x.dtype),
                       w_down.astype(x.dtype), load)
    weight = _f32(ins["TopkWeight"][0]).reshape(-1)[order]
    out = _combine(_f32(y) * weight[:, None], order, t, k)
    return {"Out": [out.astype(x.dtype).reshape(x.shape)], "H": [h],
            "Load": [load]}


@residual_grad("moe_experts")
def lower_moe_experts_grad(ctx, ins):
    """The two dX and two dW grouped matmuls on the forward's own H."""
    import jax.numpy as jnp

    from ..kernels.grouped_matmul import grouped_matmul, grouped_matmul_dw

    x, idx = ins["X"][0], ins["TopkIdx"][0]
    w_gu, w_down = ins["WGateUp"][0], ins["WDown"][0]
    h, load = ins["H"][0], ins["Load"][0]
    t, k = idx.shape
    dt = x.dtype
    order, _ = _dispatch(idx, w_gu.shape[0], ctx.attr("expert_offset", 0))
    token = order // k
    x_sorted = x.reshape(t, -1)[token]
    g_sorted = ins["Out@GRAD"][0].astype(dt).reshape(t, -1)[token]
    weight = _f32(ins["TopkWeight"][0]).reshape(-1)[order][:, None]
    gate, up, sig = _swiglu_parts(h)
    act = gate * sig * up
    # d(act) before the pair's weight: its product with act is the
    # weight's own gradient
    d_act = _f32(grouped_matmul(g_sorted, w_down.astype(dt), load,
                                transpose_rhs=True))
    d_weight = jnp.zeros((t * k,), jnp.float32).at[order].set(
        jnp.sum(d_act * act, axis=1))
    dw_down = grouped_matmul_dw((act * weight).astype(dt), g_sorted, load)
    d_h = _swiglu_bwd(h, d_act * weight).astype(dt)
    dw_gu = grouped_matmul_dw(x_sorted, d_h, load)
    dx = _combine(grouped_matmul(d_h, w_gu.astype(dt), load,
                                 transpose_rhs=True),
                  order, t, k)
    return {"X@GRAD": [dx.astype(dt).reshape(x.shape)],
            "TopkIdx@GRAD": [None],
            "TopkWeight@GRAD": [d_weight.reshape(t, k)],
            "WGateUp@GRAD": [dw_gu.astype(w_gu.dtype)],
            "WDown@GRAD": [dw_down.astype(w_down.dtype)]}
