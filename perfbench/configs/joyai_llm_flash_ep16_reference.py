"""Plain reference of the `joyai_llm_flash_ep16` configuration: one chip's
share of JoyAI-LLM-Flash (config.json keys as DeepSeek-V3 names them) in
float32 `jax.numpy`, every matrix product through `reference/blocks.py`'s
`Dots`.  Imports nothing of the program under test, has no kernels, does
not sort: the held experts are a dense loop under the router's mask.

With x a block's input, every layer pre-norm (RMSNorm, eps `rms_norm_eps`):
h <- h + Attn(RMSNorm(h)); h <- h + FFN(RMSNorm(h)).

MLA (DeepSeek-V2, arXiv:2405.04434, 2.1): c_q = RMSNorm(x W_dq); q = c_q
W_uq, a head [q_nope 128 | q_r 64]; [c_kv | k_r] = x W_dkv; [k_nope | v] a
head = RMSNorm(c_kv) W_ukv; rotary (theta `rope_theta`, pairs interleaved,
positions from 0) on q_r and on k_r, ONE k_r a position for all heads;
softmax(q k^T / sqrt(192) + causal) v with d_v 128; concat heads, W_o.
FFN of the first `first_k_dense_replace` layers: SwiGLU width
`intermediate_size`.  Every other layer: s = sigmoid(x W_g) over
`router_experts`; the top `num_experts_per_tok` of s + b are chosen (b, the
score-correction bias, is a buffer: drawn from the seed, never trained);
w_i = `routed_scaling_factor` s_i / sum of the chosen s; y = sum over the
chosen experts HELD HERE (`expert_offset` .. + `n_routed_experts`) of w_i
E_i(x), plus the shared expert.  What absent experts would add is left
out, as in the program.  The router scores by its OWN arithmetic, so a
token whose 8th and 9th scores lie within rounding may choose otherwise
than the program does (PERF.md, section 6).  A diagnostic outside `correct`
(tools/router_flips.py) may hand the choice in as one more field of the
rows, `route_as` [rows, expert layers, seq, top_k]; no traffic has it.
Output: RMSNorm, the untied head over the vocabulary slice, cross-entropy
on the next token.  MTP (DeepSeek-V3, arXiv:2412.19437, 2.2): h' =
[RMSNorm(h) | RMSNorm(Emb(next token))] M, one block of the MoE kind, its
own final norm, the shared embedding and head, cross-entropy on the token
after next, times `mtp_loss_weight`.

Departures: the gate and up projections of a SwiGLU are one packed matrix
[gate | up] (the same product); the bias b gets no update step (the config
gives no rate); no auxiliary balance loss."""

import jax
import jax.numpy as jnp

from reference import blocks as B

WEIGHTS_FIELD = "loss_weight"
HEADS_PER_GROUP = 8  # attention is computed a group of heads at a time


def _n_moe(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def leaves(cfg, traffic):
    """(name, shape, init kind, trainable), in the order in which the
    program's builder creates its parameters."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    ffe, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    vocab = cfg["vocab_size"]
    std = f"normal:{cfg['initializer_range']}"
    out = [("embed_w", (vocab, d), std, True)]

    def norm(name, width=d):
        out.append((name + ".scale", (width,), "ones", True))

    def swiglu(name, ff):
        out.append((name + ".gate_up_w", (d, 2 * ff), std, True))
        out.append((name + ".down_w", (ff, d), std, True))

    def block(p, moe):
        norm(p + ".attn_norm")
        out.append((p + ".q_a_w", (d, ql), std, True))
        norm(p + ".q_a_norm", ql)
        out.append((p + ".q_b_w", (ql, h * (nope + rot)), std, True))
        out.append((p + ".kv_a_w", (d, kvl + rot), std, True))
        norm(p + ".kv_a_norm", kvl)
        out.append((p + ".kv_b_w", (kvl, h * (nope + dv)), std, True))
        out.append((p + ".o_w", (h * dv, d), std, True))
        norm(p + ".ffn_norm")
        if not moe:
            swiglu(p, cfg["intermediate_size"])
            return
        out.append((p + ".router_w", (d, cfg["router_experts"]), std, True))
        out.append((p + ".router_bias", (cfg["router_experts"],),
                    f"normal:{cfg['router_bias_std']}", False))
        out.append((p + ".experts_gate_up_w", (held, d, 2 * ffe), std, True))
        out.append((p + ".experts_down_w", (held, ffe, d), std, True))
        swiglu(p + ".shared", ffe * cfg["n_shared_experts"])

    for i in range(cfg["num_hidden_layers"]):
        block(f"layer{i}", moe=i >= cfg["first_k_dense_replace"])
    norm("final_norm")
    out.append(("head_w", (d, vocab), std, True))
    for k in range(cfg["num_nextn_predict_layers"]):
        p = f"mtp{k}"
        norm(p + ".hnorm")
        norm(p + ".enorm")
        out.append((p + ".proj_w", (2 * d, d), std, True))
        block(p + ".block", moe=True)
        norm(p + ".final_norm")
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [b, t, h, d]: the pair (x[2i], x[2i+1]) at position p is turned
    by the angle p * theta^(-2i/d)."""
    b, t, h, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def swiglu(dots, x, w_gate_up, w_down):
    gate, up = jnp.split(dots.mm(x, w_gate_up), 2, axis=-1)
    return dots.mm(jax.nn.silu(gate) * up, w_down)


def mla(dots, cfg, x, P, p):
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = rms_norm(dots.mm(x, P[p + ".q_a_w"]), P[p + ".q_a_norm.scale"],
                   eps)
    q = dots.mm(c_q, P[p + ".q_b_w"]).reshape(b, t, h, nope + rot)
    c_kv, k_r = jnp.split(dots.mm(x, P[p + ".kv_a_w"]),
                          [cfg["kv_lora_rank"]], axis=-1)
    kv = dots.mm(rms_norm(c_kv, P[p + ".kv_a_norm.scale"], eps),
                 P[p + ".kv_b_w"]).reshape(b, t, h, nope + dv)
    k_r = jnp.broadcast_to(rope(k_r[:, :, None, :], theta), (b, t, h, rot))
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    v = kv[..., nope:]
    causal = jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e30)

    @jax.checkpoint
    def heads(qkv):  # [b, g, t, .] each: a group of heads at a time
        qg, kg, vg = qkv
        s = dots.bmm(qg, jnp.swapaxes(kg, -1, -2)) * (nope + rot) ** -0.5
        return dots.bmm(jax.nn.softmax(s + causal, axis=-1), vg)

    g = min(HEADS_PER_GROUP, h)

    def grouped(a):  # [b, t, h, e] -> [h/g, b, g, t, e]
        return a.transpose(0, 2, 1, 3).reshape(
            b, h // g, g, t, a.shape[-1]).transpose(1, 0, 2, 3, 4)

    ctx = jax.lax.map(heads, (grouped(q), grouped(k), grouped(v)))
    ctx = ctx.transpose(1, 3, 0, 2, 4).reshape(b, t, h * dv)
    return dots.mm(ctx, P[p + ".o_w"])


def route(dots, cfg, x, w, bias, idx=None):
    """[b, t, router_experts] of each token's weight for each expert: 0 but
    for the chosen (`idx` [b, t, top_k] where a diagnostic hands them in)."""
    scores = jax.nn.sigmoid(dots.mm(x, w))
    if idx is None:
        _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weight = cfg["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    picked = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
    return jnp.sum(picked * weight[..., None], axis=-2)


def moe(dots, cfg, x, P, p, offset=None, held=None, idx=None):
    """The shared expert plus the chosen experts among those held here
    (default: the configuration's share)."""
    offset = cfg["expert_offset"] if offset is None else offset
    gates = route(dots, cfg, x, P[p + ".router_w"], P[p + ".router_bias"],
                  idx)
    w_gu, w_down = P[p + ".experts_gate_up_w"], P[p + ".experts_down_w"]
    held = w_gu.shape[0] if held is None else held
    mine = jnp.moveaxis(gates[..., offset:offset + held], -1, 0)

    def one(acc, ws):
        w1, w2, gate = ws
        return acc + gate[..., None] * swiglu(dots, x, w1, w2), None

    out, _ = jax.lax.scan(
        one, swiglu(dots, x, P[p + ".shared.gate_up_w"],
                    P[p + ".shared.down_w"]), (w_gu, w_down, mine))
    return out


def block(dots, cfg, x, P, p, is_moe, idx=None):
    eps = cfg["rms_norm_eps"]
    x = x + mla(dots, cfg, rms_norm(x, P[p + ".attn_norm.scale"], eps), P, p)
    y = rms_norm(x, P[p + ".ffn_norm.scale"], eps)
    if is_moe:
        return x + moe(dots, cfg, y, P, p, idx=idx)
    return x + swiglu(dots, y, P[p + ".gate_up_w"], P[p + ".down_w"])


def hidden_states(dots, cfg, P, ids, route_as=None):
    """ids [b, seq + 1 + mtp]: ([the main stack's final-normed state, each
    MTP module's], seq)."""
    eps = cfg["rms_norm_eps"]
    n_mtp = cfg["num_nextn_predict_layers"]
    seq = ids.shape[1] - 1 - n_mtp
    dense = cfg["first_k_dense_replace"]

    def chosen(j):  # the j-th expert layer's, main stack first
        return None if route_as is None or j < 0 else route_as[:, j]

    x = P["embed_w"][ids[:, :seq]]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x, P, p=f"layer{i}", m=i >= dense, idx=chosen(i - dense):
            block(dots, cfg, x, P, p, m, idx))(x, P)
    outs = [rms_norm(x, P["final_norm.scale"], eps)]
    for k in range(n_mtp):
        p = f"mtp{k}"
        both = jnp.concatenate(
            [rms_norm(x, P[p + ".hnorm.scale"], eps),
             rms_norm(P["embed_w"][ids[:, k + 1:k + 1 + seq]],
                      P[p + ".enorm.scale"], eps)], axis=-1)
        x = jax.checkpoint(
            lambda x, P, p=p, idx=chosen(_n_moe(cfg) + k):
            block(dots, cfg, x, P, p + ".block", True, idx))(
                dots.mm(both, P[p + ".proj_w"]), P)
        outs.append(rms_norm(x, P[p + ".final_norm.scale"], eps))
    return outs, seq


def loss_sum(dots, cfg, params, block_):
    """sum over the rows' positions of weight * (CE(next token) +
    mtp_loss_weight * CE(token after next))."""
    ids = block_["ids"][..., 0]
    weights = block_[WEIGHTS_FIELD][..., 0]
    states, seq = hidden_states(dots, cfg, params, ids,
                                block_.get("route_as"))
    total = 0.0
    for k, state in enumerate(states):
        scale = 1.0 if k == 0 else cfg["mtp_loss_weight"]
        total = total + scale * B.weighted_cross_entropy_sum(
            dots.mm(state, params["head_w"]), ids[:, k + 1:k + 1 + seq],
            weights)
    return total
