"""Contrib layers: fused/TPU-native extensions beyond the reference API."""

from __future__ import annotations

from ..layer_helper import LayerHelper


def _residual(helper, shape, dtype):
    """A variable for what an attention kernel writes beside its result and
    its grad op reads back (ops/fused_ops.py): it carries no gradient."""
    var = helper.create_variable_for_type_inference(dtype,
                                                    stop_gradient=True)
    if shape is not None:
        var.shape = tuple(shape)
    return var


def fused_attention(q, k, v, bias=None, scale=1.0, causal=False,
                    dropout_rate=0.0, block_q=512, block_k=512,
                    fmt="bhtd", weights_dropout=True, mask=None,
                    block_length=0, clean_offset=0, name=None):
    """Flash-attention layer (Pallas kernel on TPU) over [B,H,T,D] tensors
    (fmt="bhtd") or [B,T,H,D] tensors (fmt="bthd" — the transpose-free
    convention: reshape the projection output [B,T,H*D] to [B,T,H,D] and
    skip split/merge-head transposes entirely).

    Grouped-query attention (fmt="bhtd"): k and v may have fewer heads
    than q, a count that divides q's; query head i reads key/value head
    i // group, and K and V are never expanded to q's head count.

    mask="block_diffusion" (fmt="bhtd", with `block_length` B and
    `clean_offset` L; not with causal=True) is the training mask of a
    block-diffusion model (BD3-LM, arXiv:2503.09573) over rows that hold
    [noisy ; clean] copies of a sequence of L tokens, T = 2L: with
    blk(p) = (p mod L) // B, a noisy row sees the noisy keys of its own
    block and the clean keys of earlier blocks; a clean row sees the clean
    keys of blocks up to its own.  The kernels compute it from positions
    and skip every tile that holds no visible pair.

    With dropout_rate > 0 and weights_dropout=True (default), dropout
    applies to the attention WEIGHTS inside the kernels (the reference's
    dropout-on-softmax semantics, transformer_model.py:44) via a
    deterministic per-step mask that never exists in HBM: on compiled
    TPU the bits come from the hardware PRNG re-seeded per tile
    (kernels/attention.py _keep_tile_prng, FLAGS_tpu_prng_dropout —
    this removed the O(T²·H) hash-regeneration cost that used to make
    long sequences a net loss, so weights-dropout is now the default at
    every length); interpret/XLA fallbacks use the counter-based hash
    (kernels/hash_rng.py).  weights_dropout=False instead applies hash
    dropout to the attention OUTPUT (O(T·D) work, flash-style
    semantics)."""
    from ..core import framework as fw

    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    qs = q.shape
    lse = _residual(helper, qs and (
        (qs[0], qs[2], qs[1]) if fmt == "bthd" else qs[:3]), "float32")
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    in_kernel_rate = dropout_rate if weights_dropout else 0.0
    attrs = {
        "scale": float(scale),
        "causal": causal,
        "block_q": block_q,
        "block_k": block_k,
        "fmt": fmt,
        "dropout_rate": float(in_kernel_rate),
        "rng_id": fw.unique_rng_id() if in_kernel_rate else 0,
    }
    if mask is not None:
        attrs.update(mask=mask, block_length=int(block_length),
                     clean_offset=int(clean_offset))
    helper.append_op(
        "fused_attention",
        inputs=inputs,
        outputs={"Out": [out], "Lse": [lse]},
        attrs=attrs,
    )
    # the context has v's head size (latent attention: d_v != d_qk)
    out.shape = qs and v.shape and tuple(qs[:-1]) + (v.shape[-1],)
    if dropout_rate and not weights_dropout:
        from .nn import dropout

        out = dropout(out, dropout_prob=dropout_rate,
                      dropout_implementation="upscale_in_train")
    return out


def fused_qkv_attention(x, n_head, d_key, d_model, bias=None, scale=1.0,
                        causal=False, dropout_rate=0.0, block_q=512,
                        block_k=512, qkv_param_attr=None,
                        out_param_attr=None, name=None):
    """Self-attention layer, projections and attention in one op
    (ops/fused_ops.py fused_qkv_attention; kernels/attention.py
    flash_qkv_attention): the q, k, v and output projections are XLA dots
    straight into and out of the [b, t, h, d_key] layout the bthd flash
    kernels take, and q, k, v, the context and the logsumexp are kept for
    the grad op, which recomputes nothing (PERF.md PR 28 (3), PR 30: a
    Pallas kernel that ran the projections in VMEM was slower, and went).

    Creates the SAME two parameters as the fc + split + fused_attention +
    fc composition — [d_model_in, 3*n_head*d_key] packed qkv weight and
    [n_head*d_key, d_model] output weight, same shapes, same default
    initializer — so checkpoints interop with it (pass that path's names
    via qkv_param_attr/out_param_attr).  Weights-dropout semantics follow
    fused_attention (reference dropout-on-softmax, mask never in HBM)."""
    from ..core import framework as fw

    dtype = x.dtype
    # parameters ride the SAME LayerHelper("fc") name sequence as the
    # unfused qkv-fc + output-fc pair (the conv2d_bn recipe): explicit
    # attr names match trivially, and DEFAULT names — plus every later
    # unnamed fc in the model — land on identical fc_N draws, so
    # checkpoints interop with that composition (asserted in
    # tests/test_fused_qkv_attention.py on the BERT builder, whose ffn/
    # head fcs are unnamed)
    qkv_helper = LayerHelper("fc", param_attr=qkv_param_attr)
    w_qkv = qkv_helper.create_parameter(
        qkv_helper.param_attr(), shape=[x.shape[-1], 3 * d_key * n_head],
        dtype=dtype)
    out_helper = LayerHelper("fc", param_attr=out_param_attr)
    w_out = out_helper.create_parameter(
        out_helper.param_attr(), shape=[d_key * n_head, d_model],
        dtype=dtype)
    helper = LayerHelper("fused_qkv_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    q, k, v, ctx = (
        _residual(helper, (x.shape[0], x.shape[1], n_head, d_key), dtype)
        for _ in range(4))
    lse = _residual(helper, (x.shape[0], n_head, x.shape[1]), "float32")
    inputs = {"X": [x], "WQkv": [w_qkv], "WOut": [w_out]}
    if bias is not None:
        inputs["Bias"] = [bias]
    helper.append_op(
        "fused_qkv_attention",
        inputs=inputs,
        outputs={"Out": [out], "Q": [q], "K": [k], "V": [v], "Ctx": [ctx],
                 "Lse": [lse]},
        attrs={
            "n_head": n_head,
            "scale": float(scale),
            "causal": causal,
            "block_q": block_q,
            "block_k": block_k,
            "dropout_rate": float(dropout_rate),
            "rng_id": fw.unique_rng_id() if dropout_rate else 0,
        },
    )
    out.shape = tuple(x.shape[:-1]) + (d_model,)
    return out


def ring_attention(q, k, v, scale=1.0, causal=False, axis_name="sp",
                   fmt="bhtd", name=None):
    """Context-parallel attention layer over [B,H,T,D] (fmt "bhtd") or
    [B,T,H,D] (fmt "bthd" — the transpose-free convention; the ring path
    reuses the single-device bthd block specs, so CP introduces no
    split/merge-head transposes) tensors: the T axis shards over mesh
    axis `axis_name` (see ops/fused_ops.py ring_attention).  Use through
    a ShardingPlan whose mesh declares that axis."""
    helper = LayerHelper("ring_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        "ring_attention",
        inputs={"Q": [q], "K": [k], "V": [v]},
        outputs={"Out": [out]},
        attrs={"scale": float(scale), "causal": causal,
               "axis_name": axis_name, "fmt": fmt},
    )
    out.shape = q.shape
    return out


# ---------------------------------------------------------------------------
# Pre-norm decoder blocks with a sparse expert layer (ops/llm_ops.py)
# ---------------------------------------------------------------------------


def rms_norm(x, epsilon=1e-6, param_attr=None, name=None):
    """y = x * rsqrt(mean(x^2, last axis) + epsilon) * scale, scale a
    parameter [x.shape[-1]] that starts at one.  Statistics in float32."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        helper.param_attr(), shape=[x.shape[-1]], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(x.dtype)
    inv = _residual(helper, x.shape and tuple(x.shape[:-1]) + (1,),
                    "float32")
    helper.append_op(
        "rms_norm", inputs={"X": [x], "Scale": [scale]},
        outputs={"Y": [out], "InvRms": [inv]},
        attrs={"epsilon": float(epsilon)})
    out.shape = x.shape
    return out


def rope(x, theta=10000.0, pairing="interleaved", period=0, name=None):
    """Rotary position embedding of x [b, t, h, d] over "interleaved"
    pairs (x[2i], x[2i+1]) or "half" pairs (x[i], x[i + d/2]); positions
    count from 0 along axis 1 and, with a `period`, start again every
    `period` rows."""
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"theta": float(theta)}
    if pairing != "interleaved":
        attrs["pairing"] = pairing
    if period:
        attrs["period"] = int(period)
    helper.append_op("rope", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs=attrs)
    out.shape = x.shape
    return out


def swiglu(x, name=None):
    """silu(x[.., :f]) * x[.., f:] of a packed [gate | up] activation."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("swiglu", inputs={"X": [x]}, outputs={"Out": [out]})
    out.shape = x.shape and tuple(x.shape[:-1]) + (x.shape[-1] // 2,)
    return out


def short_conv(x, taps=3, param_attr=None, gated=True, name=None):
    """Gated short convolution (ops/llm_ops.py short_conv) of x [b, t, 3d]
    = [B | C | x]: C * causal_depthwise(B * x) -> [b, t, d], with one
    filter of `taps` taps a channel, a parameter [d, taps] drawn at
    normal(0, taps ** -0.5), a depthwise filter's fan-in scale, unless
    `param_attr` brings an initializer; no bias.  `gated=False`: the plain
    form, silu(causal_depthwise(x)) of x [b, t, d]."""
    from ..initializer import NormalInitializer

    d = x.shape[-1] // 3 if gated else x.shape[-1]
    helper = LayerHelper("short_conv", param_attr=param_attr, name=name)
    w = helper.create_parameter(
        helper.param_attr(), shape=[d, taps], dtype="float32",
        default_initializer=NormalInitializer(0.0, taps ** -0.5))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("short_conv", inputs={"X": [x], "Filter": [w]},
                     outputs={"Out": [out]},
                     attrs={} if gated else {"activation": "silu"})
    out.shape = tuple(x.shape[:-1]) + (d,)
    return out


def kda_gate(f, b, a_log_attr=None, dt_bias_attr=None, name=None):
    """The gates of a KDA layer (ops/llm_ops.py kda_gate): the log-decay
    G = -exp(a_log[h]) * softplus(f + dt_bias) of f [b, t, h*dk] and
    Beta = sigmoid(b) of b [b, t, h], both float32.  a_log [h] and
    dt_bias [h*dk] are parameters (zeros unless their attrs bring an
    initializer).  Returns (G, Beta)."""
    from ..initializer import ConstantInitializer

    heads = b.shape[-1]
    helper = LayerHelper("kda_gate", name=name)

    def param(attr, shape):
        h = LayerHelper("kda_gate", param_attr=attr)
        return h.create_parameter(h.param_attr(), shape=[shape],
                                  dtype="float32",
                                  default_initializer=ConstantInitializer(
                                      0.0))

    a_log = param(a_log_attr, heads)
    dt_bias = param(dt_bias_attr, f.shape[-1])
    g = helper.create_variable_for_type_inference("float32")
    beta = helper.create_variable_for_type_inference("float32")
    helper.append_op("kda_gate",
                     inputs={"F": [f], "B": [b], "ALog": [a_log],
                             "DtBias": [dt_bias]},
                     outputs={"G": [g], "Beta": [beta]})
    g.shape, beta.shape = f.shape, b.shape
    return g, beta


def kda(q, k, v, g, beta, name=None):
    """Kimi Delta Attention (ops/llm_ops.py kda) of q, k [b, t, h*dk], v
    [b, t, h*dv], the log-decay g [b, t, h*dk] and beta [b, t, h]: the
    gated delta rule with q and k L2-normalised, scale dk ** -0.5, chunk
    by chunk.  Returns o [b, t, h*dv]."""
    helper = LayerHelper("kda", name=name)
    out = helper.create_variable_for_type_inference(v.dtype)
    states = _residual(helper, None, "float32")
    factors = _residual(helper, None, "float32")
    helper.append_op("kda",
                     inputs={"Q": [q], "K": [k], "V": [v], "G": [g],
                             "Beta": [beta]},
                     outputs={"Out": [out], "States": [states],
                              "Factors": [factors]})
    out.shape = v.shape
    return out


def moe_router(x, n_experts, top_k, scale=1.0, bias_std=0.0,
               param_attr=None, bias_attr=None, scoring="sigmoid",
               norm_eps=None, name=None):
    """Top-k router over `n_experts` (ops/llm_ops.py moe_router), scoring
    each expert by a "sigmoid" of its own logit or by a "softmax" over the
    experts: returns (TopkIdx [T, k] int32, TopkWeight [T, k] float32, the
    chosen scores normalised over the chosen, their sum plus `norm_eps`
    where a family states one, times `scale`).  The
    score-correction bias is a buffer that enters the choice alone: a
    parameter that is not trained, drawn once at `bias_std` (zeros at 0);
    `bias_attr=False` leaves it out."""
    from ..initializer import ConstantInitializer, NormalInitializer

    helper = LayerHelper("moe_router", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    w = helper.create_parameter(helper.param_attr(),
                                shape=[x.shape[-1], n_experts],
                                dtype="float32")
    inputs = {"X": [x], "W": [w]}
    if bias_attr is not False:
        import copy

        battr = copy.copy(helper.bias_attr())  # the caller's attr stays
        battr.trainable = False
        bias = helper.create_parameter(
            battr, shape=[n_experts], dtype="float32", is_bias=True,
            default_initializer=NormalInitializer(0.0, bias_std) if bias_std
            else ConstantInitializer(0.0))
        bias.stop_gradient = True
        inputs["Bias"] = [bias]
    attrs = {"top_k": int(top_k), "scale": float(scale)}
    if scoring != "sigmoid":
        attrs["scoring"] = scoring
    if norm_eps is not None:
        attrs["norm_eps"] = float(norm_eps)
    idx = _residual(helper, None, "int32")
    scores = _residual(helper, None, "float32")
    weight = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "moe_router", inputs=inputs,
        outputs={"TopkIdx": [idx], "TopkWeight": [weight],
                 "Scores": [scores]},
        attrs=attrs)
    return idx, weight


def moe_experts(x, topk_idx, topk_weight, n_held, d_ff, n_experts,
                expert_offset=0, gate_up_attr=None, down_attr=None, name=None):
    """The routed experts this chip holds (ops/llm_ops.py moe_experts):
    experts expert_offset .. expert_offset + n_held - 1 of the layer, each
    a SwiGLU of width d_ff, as two stacked parameters [n_held, d, 2*d_ff]
    (gate | up) and [n_held, d_ff, d]; `n_experts`, the router's width,
    lets the op size its walk's chunk by the share held.  Returns (out
    shaped like x, load [n_held] int32: the pairs each held expert
    computed this step)."""
    d = x.shape[-1]
    gu_helper = LayerHelper("moe_experts", param_attr=gate_up_attr)
    w_gu = gu_helper.create_parameter(
        gu_helper.param_attr(), shape=[n_held, d, 2 * d_ff], dtype=x.dtype)
    down_helper = LayerHelper("moe_experts", param_attr=down_attr)
    w_down = down_helper.create_parameter(
        down_helper.param_attr(), shape=[n_held, d_ff, d], dtype=x.dtype)
    helper = LayerHelper("moe_experts", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    h = _residual(helper, None, x.dtype)
    load = _residual(helper, (n_held,), "int32")
    order = _residual(helper, None, "int32")
    helper.append_op(
        "moe_experts",
        inputs={"X": [x], "TopkIdx": [topk_idx], "TopkWeight": [topk_weight],
                "WGateUp": [w_gu], "WDown": [w_down]},
        outputs={"Out": [out], "H": [h], "Load": [load], "Order": [order]},
        attrs={"expert_offset": int(expert_offset),
               "n_experts": int(n_experts)})
    out.shape = x.shape
    return out, load
