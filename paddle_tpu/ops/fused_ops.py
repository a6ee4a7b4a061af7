"""Fused ops backed by Pallas kernels (the TPU analogue of the reference's
operators/fused/ CPU+cuDNN fusions and operators/jit/ codegen kernels —
SURVEY.md §2.3)."""

from __future__ import annotations

from ..core.registry import register, residual_grad


def _attn_dropout_seed(ctx):
    """(rate, seed) for an attention op's in-kernel weights dropout: 0 in
    is_test, else the step-key-derived (1,) uint32 stream seed keyed by
    the op's static rng_id — shared by fused_attention and
    fused_qkv_attention so the two ops can never diverge in seeding."""
    from ..kernels import hash_rng

    rate = ctx.attr("dropout_rate", 0.0)
    if ctx.attr("is_test", False) or ctx.is_test:
        rate = 0.0
    if not rate:
        return 0.0, None
    base = getattr(ctx.executor_ctx, "base_key", None)
    if base is None:
        base = ctx.executor_ctx._base_key  # eager session
    return rate, hash_rng.seed_from_key(base, ctx.attr("rng_id", 1))


def _bias_is_trainable(ctx, bias):
    """Whether the op's Bias input needs a gradient.  Stop-gradient
    biases (padding/causal masks — the usual case) keep the TPU
    hardware-PRNG dropout fast path: their dbias recompute is
    dead-code-eliminated, so its hash-mask mismatch is unobservable.  A
    genuinely trainable bias forces the hash mask everywhere so the bias
    cotangent sees the same mask the kernels applied."""
    if bias is None:
        return False
    try:
        bname = ctx.op.inputs.get("Bias", [None])[0]
        bvar = ctx.block._find_var_recursive(bname) if bname else None
        return bvar is None or not bvar.stop_gradient
    except Exception:
        return True  # unknown provenance: stay correct


# attr-gated randomness: in-kernel weights dropout draws its mask seed from
# the step key only when dropout_rate is armed — the SAME predicate the
# executor's step-key threading uses (executor._COND_RANDOM_OPS), and what
# the static verifier cross-checks (paddle_tpu/analysis/verifier.py)
def _attn_derives_rng(op) -> bool:
    return bool(op.attrs.get("dropout_rate", 0.0))


def _attn_kernel_opts(ctx, bias):
    """What both attention ops, and their grad ops, hand the kernels: the
    grad op reads the same attrs (the grad maker copies them, `rng_id`
    among them) and the same step key, so it derives the same seed."""
    rate, seed = _attn_dropout_seed(ctx)
    opts = dict(
        scale=ctx.attr("scale", 1.0),
        causal=ctx.attr("causal", False),
        block_q=ctx.attr("block_q", 512),
        block_k=ctx.attr("block_k", 512),
        dropout_rate=rate,
        dropout_seed=seed,
        trainable_bias=_bias_is_trainable(ctx, bias),
    )
    mask = ctx.attr("mask", "")
    if mask == "block_diffusion":
        opts["mask"] = (int(ctx.attr("block_length")),
                        int(ctx.attr("clean_offset")))
    elif mask:
        raise ValueError(f"{ctx.op.type}: unknown mask {mask!r}")
    return opts


def _cotangent(ins, shape, dtype):
    """Out@GRAD as the generic grad lowering hands it to the vjp: in the
    forward output's shape and dtype."""
    import jax.numpy as jnp

    return jnp.asarray(ins["Out@GRAD"][0], dtype).reshape(shape)


@register("fused_attention", derives_rng=_attn_derives_rng,
          residuals=("Out", "Lse"))
def lower_fused_attention(ctx, ins):
    """Flash attention over [B,H,T,D] (fmt "bhtd") or [B,T,H,D] (fmt
    "bthd") q/k/v with optional additive bias.  "bthd" is the
    transpose-free convention — see kernels/attention.py.

    K and V may carry fewer heads than Q (fmt "bhtd"; their shape says how
    many): query head i reads key/value head i // group, and K@GRAD /
    V@GRAD are sums over the group.  Attr `mask` = "block_diffusion" with
    `block_length` and `clean_offset` masks by position inside the kernels
    (kernels/attention.py `_bd_visible`): the rows are [noisy ; clean]
    copies of a sequence, and tiles that hold no visible pair are skipped.

    dropout_rate > 0 applies the reference's dropout-on-attention-weights
    semantics (transformer_model.py:44) INSIDE the kernels: the mask is the
    counter-based hash of (step base key, rng_id, global element index) —
    deterministic within a step, so the backward kernels regenerate the
    identical mask from the seed the grad op derives anew and the [Tq,Tk]
    mask never exists in HBM (see kernels/hash_rng.py).

    Lse ([B, H, Tq] float32, the kernel's logsumexp) is the residual that
    fused_attention_grad reads beside Out; it is written where the kernel
    route ran and the op declares the slot."""
    from ..kernels.attention import flash_attention_fwd

    bias = ins.get("Bias", [None])[0]
    out, lse = flash_attention_fwd(
        ins["Q"][0], ins["K"][0], ins["V"][0], bias,
        fmt=ctx.attr("fmt", "bhtd"), **_attn_kernel_opts(ctx, bias))
    return {"Out": [out], "Lse": [lse]}


@residual_grad("fused_attention")
def lower_fused_attention_grad(ctx, ins):
    """The backward kernels on the forward's own (Out, Lse): the forward
    kernel is not run again for them."""
    from ..kernels.attention import flash_attention_bwd

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins.get("Bias", [None])[0]
    out = ins["Out"][0]
    grads = flash_attention_bwd(
        q, k, v, bias, out, ins["Lse"][0],
        _cotangent(ins, out.shape, out.dtype),
        fmt=ctx.attr("fmt", "bhtd"), **_attn_kernel_opts(ctx, bias))
    if grads is None:
        return None
    dq, dk, dv, dbias = grads
    return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv],
            "Bias@GRAD": [dbias]}


def _fused_qkv_infer(ctx):
    xs = ctx.input_shape("X")
    ws = ctx.input_shape("WOut")
    if xs is not None and ws is not None:
        ctx.set_output("Out", tuple(xs[:-1]) + (ws[1],),
                       ctx.input_dtype("X"))


@register("fused_qkv_attention", infer_shape=_fused_qkv_infer,
          derives_rng=_attn_derives_rng,
          residuals=("Q", "K", "V", "Ctx", "Lse"))
def lower_fused_qkv_attention(ctx, ins):
    """Self-attention from the residual stream, one op a site
    (kernels/attention.py flash_qkv_attention): X [b, t, d_model], WQkv
    [d_model, 3*n_head*d_head] (the layers.fc packed layout), WOut
    [n_head*d_head, d_model], optional additive Bias.  The q, k, v and
    output projections are XLA dots that read and write [b, t, h, dh]
    straight, round the bthd flash forward kernel; the fc + split +
    fused_attention + reshape + fc chain it stands for slices a
    [b, t, 3*h*dh] array and concatenates its gradient.  (Until PR 30 a
    Pallas kernel ran the projections inside the attention walk, at a
    third of the peak: PERF.md PR 28 (3), PR 30.)  Dropout
    semantics/seeding follow fused_attention (in-kernel weights dropout,
    step-key-derived seed); shapes the kernel plan rejects run the XLA
    reference inside the same composition.

    Q, K, V, Ctx ([b, t, n_head, d_head], X's dtype) and Lse ([b, n_head,
    t] float32, where the kernel ran) are the residuals
    fused_qkv_attention_grad reads, written where the op declares the
    slots; a program that fetches none of them and has no grad op
    (is_test) leaves XLA nothing to keep."""
    from ..kernels.attention import flash_qkv_attention_fwd

    bias = ins.get("Bias", [None])[0]
    out, q, k, v, attn_ctx, lse = flash_qkv_attention_fwd(
        ins["X"][0], ins["WQkv"][0], ins["WOut"][0], bias,
        n_head=ctx.attr("n_head", 1), **_attn_kernel_opts(ctx, bias))
    return {"Out": [out], "Q": [q], "K": [k], "V": [v], "Ctx": [attn_ctx],
            "Lse": [lse]}


@residual_grad("fused_qkv_attention")
def lower_fused_qkv_attention_grad(ctx, ins):
    """The bthd backward kernels, between XLA dots for the projections'
    backward, on the forward's own (Q, K, V, Ctx, Lse): nothing of the
    forward is computed again.  Counts the site as `qkv_bwd_composed`
    (monitor.compile_phases): a site that falls to the generic route — a
    program built before the slots, a plan rejection — is not counted."""
    from ..kernels.attention import flash_qkv_attention_bwd
    from ..monitor import flight

    x, w_qkv, w_out = ins["X"][0], ins["WQkv"][0], ins["WOut"][0]
    bias = ins.get("Bias", [None])[0]
    grads = flash_qkv_attention_bwd(
        x, w_qkv, w_out, bias, ins["Q"][0], ins["K"][0], ins["V"][0],
        ins["Ctx"][0], ins["Lse"][0],
        _cotangent(ins, x.shape[:-1] + w_out.shape[1:], x.dtype),
        n_head=ctx.attr("n_head", 1), **_attn_kernel_opts(ctx, bias))
    if grads is None:
        return None
    flight.note_compile_count("qkv_bwd_composed")
    dx, dw_qkv, dw_out, dbias = grads
    return {"X@GRAD": [dx], "WQkv@GRAD": [dw_qkv], "WOut@GRAD": [dw_out],
            "Bias@GRAD": [dbias]}


@register("fused_layer_norm_gelu")
def lower_fused_ln_gelu(ctx, ins):
    """layer_norm + gelu epilogue; XLA fuses these — kept as one op so graph
    passes can target it (parity with fuse_elewise_add_act ideas)."""
    import jax

    from .nn_ops import layer_norm_core

    x = ins["X"][0]
    y, _, _ = layer_norm_core(
        x,
        ins.get("Scale", [None])[0],
        ins.get("Bias", [None])[0],
        ctx.attr("begin_norm_axis", x.ndim - 1),
        ctx.attr("epsilon", 1e-5),
    )
    # default matches the standalone gelu op (exact erf form)
    approx = bool(ctx.attr("approximate", False))
    return {"Out": [jax.nn.gelu(y, approximate=approx)]}


def _ring_attention_infer(ctx):
    qs = ctx.input_shape("Q")
    if qs is not None:
        ctx.set_output("Out", tuple(qs), ctx.input_dtype("Q"))


@register("ring_attention", infer_shape=_ring_attention_infer)
def lower_ring_attention(ctx, ins):
    """Context-parallel exact attention: the sequence axis is sharded over a
    mesh axis and K/V shards stream around the ring via ppermute over ICI
    (kernels/ring_attention.py; SURVEY.md §5.7 — a capability the reference
    lacks, its max context is bounded by one device's memory).

    Lowers to shard_map(ring) when the executor's mesh has the `axis_name`
    axis; otherwise (single-device trace, tests, dryrun without an sp axis)
    falls back to the numerically-identical reference attention.  Supports
    causal masking and sequence lengths that do not divide the axis (the
    sharded entry pads and masks via the ring-traveling key bias);
    additive bias is not supported on the ring path (pad-free batches or
    pure-causal decoders)."""
    from ..kernels.attention import _reference_bthd, reference_attention
    from ..kernels.ring_attention import ring_attention_sharded

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    scale = ctx.attr("scale", 1.0)
    causal = ctx.attr("causal", False)
    axis_name = ctx.attr("axis_name", "sp")
    fmt = ctx.attr("fmt", "bhtd")
    mesh = getattr(ctx.executor_ctx, "mesh", None)
    if (
        mesh is None
        or axis_name not in getattr(mesh, "axis_names", ())
    ):
        if fmt == "bthd":
            out = _reference_bthd(q, k, v, None, scale, causal)
        else:
            out = reference_attention(q, k, v, None, scale=scale,
                                      causal=causal)
    else:
        out = ring_attention_sharded(
            q, k, v, mesh, axis_name=axis_name, scale=scale, causal=causal,
            fmt=fmt)
    return {"Out": [out]}
