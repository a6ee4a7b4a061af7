"""Plain reference of the `lfm2_8b_a1b_ep4` configuration: one chip's share
of LFM2-8B-A1B (config.json keys as `lfm2_moe` names them) trained on the
next token, in float32 `jax.numpy`, every matrix product through
`reference/blocks.py`'s `Dots`.  Imports nothing of the program under
test, has no kernels, does not sort: the convolution is three shifted
multiplies, attention a masked softmax with K and V repeated to the
query's head count, the held experts a dense loop under the router's mask.

Block i, pre-norm (RMSNorm, eps `norm_eps`), no biases anywhere:
h <- h + Op_i(RMSNorm(h)); h <- h + FFN_i(RMSNorm(h)).

Op_i where `layer_types[i]` is "conv" (`conv_L_cache` 3 taps, `conv_bias`
false): [B | C | x] = u W_in (d -> 3d); z = B * x; c[t] = sum_j w[:, j]
z[t - 2 + j], z[t] = 0 for t < 0, within one sequence; y = C * c; out = y
W_out.  Where it is "full_attention": q = u W_q as [32, 64], k = u W_k and
v = u W_v as [8, 64]; q_i <- RoPE(RMSNorm_64(q_i) g_q), k_j <-
RoPE(RMSNorm_64(k_j) g_k), one g_q and g_k in R^64 a layer; RoPE theta
`rope_theta`, half-split pairs (x[i], x[i + 32]) as the published
`rotate_half`, positions from 0; query head i reads key/value head i // 4;
o_i = softmax(q_i k^T / sqrt(64) + causal) v; concat heads, W_o.

FFN_i for i < `num_dense_layers`: SwiGLU of width `intermediate_size`.
After that: s = sigmoid(x W_r) over `router_experts`; the top
`num_experts_per_tok` of s + b are chosen (b, the expert bias, is a buffer:
drawn from the seed, never trained; it enters the choice only); w_i =
`routed_scaling_factor` s_i / (sum of the chosen s + 1e-6)
(`norm_topk_prob`); y = sum over the chosen experts HELD HERE
(`expert_offset` .. + `num_experts`) of w_i E_i(x), E_i a SwiGLU of width
`moe_intermediate_size`; no shared expert.  What absent experts would add
is left out, as in the program.  The router scores by its OWN arithmetic,
so a token whose 4th and 5th scores lie within rounding may choose
otherwise than the program does (PERF.md, section 6).  A diagnostic
outside `correct` (tools/router_flips.py) may hand the choice in as one
more field of the rows, `route_as` [rows, expert layers, seq, top_k]; no
traffic has it.

Output: one RMSNorm (the family's `embedding_norm`), then the head, which
is the embedding transposed (`tie_word_embeddings`: one leaf, its gradient
the look-up's plus the head's); cross-entropy on the next token over the
vocabulary slice.

Departures: the gate and up projections of a SwiGLU are one packed matrix
[gate | up] (the same product); the bias b gets no update step; no
auxiliary balance loss; with `router_trained` false the router's weights
are no trained leaf and the combine weights are constants to the gradient
(`stop_gradient`), as in the program."""

import jax
import jax.numpy as jnp

from reference import blocks as B

WEIGHTS_FIELD = "loss_weight"
HEADS_PER_GROUP = 4  # attention is computed a group of heads at a time
ROUTER_NORM_EPS = 1e-6


def layer_types(cfg):
    """The operators of the layers held: the first `num_hidden_layers`
    entries of the published table, which the file keeps whole."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def conv_init_std(cfg):
    """A filter tap's standard deviation: the fan-in scale of a depthwise
    filter of `conv_L_cache` taps (the configuration's `assumed`)."""
    return cfg["conv_L_cache"] ** -0.5


def leaves(cfg, traffic):
    """(name, shape, init kind, trainable), in the order in which the
    program's builder creates its parameters."""
    d, h, hk = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    dh = d // h
    ffe, held, vocab = (cfg["moe_intermediate_size"], cfg["num_experts"],
                        cfg["vocab_size"])
    std = f"normal:{cfg['initializer_range']}"
    out = [("embed_w", (vocab, d), std, True)]

    def norm(name, width=d):
        out.append((name + ".scale", (width,), "ones", True))

    for i, kind in enumerate(layer_types(cfg)):
        p = f"layer{i}"
        norm(p + ".attn_norm")
        if kind == "conv":
            out.append((p + ".conv_in_w", (d, 3 * d), std, True))
            out.append((p + ".conv_w", (d, cfg["conv_L_cache"]),
                        f"normal:{conv_init_std(cfg)}", True))
            out.append((p + ".conv_out_w", (d, d), std, True))
        else:
            out.append((p + ".q_w", (d, h * dh), std, True))
            out.append((p + ".k_w", (d, hk * dh), std, True))
            out.append((p + ".v_w", (d, hk * dh), std, True))
            norm(p + ".q_norm", dh)
            norm(p + ".k_norm", dh)
            out.append((p + ".o_w", (h * dh, d), std, True))
        norm(p + ".ffn_norm")
        if i < cfg["num_dense_layers"]:
            ff = cfg["intermediate_size"]
            out.append((p + ".gate_up_w", (d, 2 * ff), std, True))
            out.append((p + ".down_w", (ff, d), std, True))
            continue
        out.append((p + ".router_w", (d, cfg["router_experts"]), std,
                    cfg.get("router_trained", True)))
        out.append((p + ".router_bias", (cfg["router_experts"],),
                    f"normal:{cfg['router_bias_std']}", False))
        out.append((p + ".experts_gate_up_w", (held, d, 2 * ffe), std, True))
        out.append((p + ".experts_down_w", (held, ffe, d), std, True))
    norm("final_norm")
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [b, t, h, d]: the pair (x[i], x[i + d/2]) at row p is turned by
    the angle p * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x0, x1 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                           axis=-1)


def swiglu(dots, x, w_gate_up, w_down):
    gate, up = jnp.split(dots.mm(x, w_gate_up), 2, axis=-1)
    return dots.mm(jax.nn.silu(gate) * up, w_down)


def gated_conv(bcx, w):
    """bcx [b, t, 3d] = [B | C | x], w [d, 3]: C * (w[:, 2] z[t] + w[:, 1]
    z[t-1] + w[:, 0] z[t-2]) with z = B * x and zeros before a row's
    start: three shifted multiplies."""
    gate_b, gate_c, x = jnp.split(bcx, 3, axis=-1)
    z = gate_b * x
    z1 = jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    z2 = jnp.pad(z, ((0, 0), (2, 0), (0, 0)))[:, :-2]
    return gate_c * (w[:, 2] * z + w[:, 1] * z1 + w[:, 0] * z2)


def short_conv(dots, cfg, x, P, p):
    if cfg["conv_L_cache"] != 3 or cfg["conv_bias"]:
        raise ValueError("the reference writes out three taps and no bias")
    y = gated_conv(dots.mm(x, P[p + ".conv_in_w"]), P[p + ".conv_w"])
    return dots.mm(y, P[p + ".conv_out_w"])


def attention(dots, cfg, x, P, p):
    b, t, d = x.shape
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    q = dots.mm(x, P[p + ".q_w"]).reshape(b, t, h, dh)
    k = dots.mm(x, P[p + ".k_w"]).reshape(b, t, hk, dh)
    v = dots.mm(x, P[p + ".v_w"]).reshape(b, t, hk, dh)
    q = rope(rms_norm(q, P[p + ".q_norm.scale"], eps), theta)
    k = rope(rms_norm(k, P[p + ".k_norm.scale"], eps), theta)
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    causal = jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e30)

    @jax.checkpoint
    def heads(qkv):  # [b, g, t, dh] each: a group of heads at a time
        qg, kg, vg = qkv
        s = dots.bmm(qg, jnp.swapaxes(kg, -1, -2)) * dh ** -0.5
        return dots.bmm(jax.nn.softmax(s + causal, axis=-1), vg)

    g = min(HEADS_PER_GROUP, h)

    def grouped(a):  # [b, t, h, dh] -> [h/g, b, g, t, dh]
        return a.transpose(0, 2, 1, 3).reshape(
            b, h // g, g, t, dh).transpose(1, 0, 2, 3, 4)

    ctx = jax.lax.map(heads, (grouped(q), grouped(k), grouped(v)))
    ctx = ctx.transpose(1, 3, 0, 2, 4).reshape(b, t, h * dh)
    return dots.mm(ctx, P[p + ".o_w"])


def route(dots, cfg, x, w, bias, idx=None):
    """[b, t, router_experts] of each token's weight for each expert: 0 but
    for the chosen (`idx` [b, t, top_k] where a diagnostic hands them in)."""
    scores = jax.nn.sigmoid(dots.mm(x, w))
    if idx is None:
        _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weight = cfg["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    picked = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
    return jnp.sum(picked * weight[..., None], axis=-2)


def moe(dots, cfg, x, P, p, offset=None, held=None, idx=None):
    """The chosen experts among those held here (default: the
    configuration's share)."""
    offset = cfg["expert_offset"] if offset is None else offset
    gates = route(dots, cfg, x, P[p + ".router_w"], P[p + ".router_bias"],
                  idx)
    if not cfg.get("router_trained", True):
        gates = jax.lax.stop_gradient(gates)
    w_gu, w_down = P[p + ".experts_gate_up_w"], P[p + ".experts_down_w"]
    held = w_gu.shape[0] if held is None else held
    mine = jnp.moveaxis(gates[..., offset:offset + held], -1, 0)

    def one(acc, ws):
        w1, w2, gate = ws
        return acc + gate[..., None] * swiglu(dots, x, w1, w2), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (w_gu, w_down, mine))
    return out


def block(dots, cfg, x, P, i, idx=None):
    p, eps = f"layer{i}", cfg["norm_eps"]
    op = short_conv if layer_types(cfg)[i] == "conv" else attention
    x = x + op(dots, cfg, rms_norm(x, P[p + ".attn_norm.scale"], eps), P, p)
    y = rms_norm(x, P[p + ".ffn_norm.scale"], eps)
    if i < cfg["num_dense_layers"]:
        return x + swiglu(dots, y, P[p + ".gate_up_w"], P[p + ".down_w"])
    return x + moe(dots, cfg, y, P, p, idx=idx)


def hidden_states(dots, cfg, P, ids, route_as=None):
    """ids [b, seq]: the stack's state before the final norm, [b, seq, d]."""
    dense = cfg["num_dense_layers"]
    x = P["embed_w"][ids]
    for i in range(len(layer_types(cfg))):
        x = jax.checkpoint(
            lambda x, P, i=i, idx=None if route_as is None or i < dense
            else route_as[:, i - dense]: block(dots, cfg, x, P, i, idx))(x, P)
    return x


def loss_sum(dots, cfg, params, block_):
    """sum over the rows' positions of weight * CE(logits at the position,
    the next token), the head the embedding transposed."""
    ids = block_["ids"][..., 0]
    weights = block_[WEIGHTS_FIELD][..., 0]
    x = hidden_states(dots, cfg, params, ids[:, :-1], block_.get("route_as"))
    final = rms_norm(x, params["final_norm.scale"], cfg["norm_eps"])
    return B.weighted_cross_entropy_sum(
        dots.mm(final, params["embed_w"].T), ids[:, 1:], weights)
