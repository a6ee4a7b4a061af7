"""Plain reference of the `sdar_30b_a3b_ep8` configuration: one chip's
share of SDAR-30B-A3B-Chat (config.json keys as Qwen3-MoE names them)
trained by block diffusion, in float32 `jax.numpy`, every matrix product
through `reference/blocks.py`'s `Dots`.  Imports nothing of the program
under test, has no kernels, does not sort: the held experts are a dense
loop under the router's mask, the attention mask is a plain [2L, 2L]
boolean, K and V are repeated to the query's head count.

Every layer pre-norm (RMSNorm, eps `rms_norm_eps`), no biases:
h <- h + Attn(RMSNorm(h)); h <- h + MoE(RMSNorm(h)).

Attention: q = x W_q as [32, 128], k = x W_k and v = x W_v as [4, 128];
q_i <- RoPE(RMSNorm_128(q_i) g_q), k_j <- RoPE(RMSNorm_128(k_j) g_k), one
g_q and g_k in R^128 a layer; RoPE theta `rope_theta`, half-split pairs
(x[i], x[i + 64]) as the published `rotate_half`, the position of row p is
p mod L; query head i reads key/value head i // 8; o_i = softmax(q_i k^T /
sqrt(128) + M) v; concat heads, W_o.

MoE: p = softmax(x W_g) over `router_experts`; the top
`num_experts_per_tok` of p are chosen; w_i = p_i / sum of the chosen p
(`norm_topk_prob`); y = sum over the chosen experts HELD HERE
(`expert_offset` .. + `num_experts`) of w_i E_i(x), E_i a SwiGLU of width
`moe_intermediate_size`.  What absent experts would add is left out, as in
the program.  The router scores by its OWN arithmetic, so a token whose
8th and 9th scores lie within rounding may choose otherwise than the
program does (PERF.md, section 6); every masked position enters the stack
with the same embedding row.  A diagnostic outside `correct`
(tools/router_flips.py) may hand the choice in as one more field of the
rows, `route_as` [rows, layers, 2L, top_k]; no traffic has it.

Block diffusion (BD3-LM, arXiv:2503.09573, section 3, one forward pass):
a row x0 of L tokens in blocks of `block_length`; x_t = [MASK]
(`mask_token_id`) where the `noise` field is 1; the stack's input is
[x_t ; x0], 2L positions.  With blk(p) = (p mod L) // B, row i sees key j
iff  i < L, j < L: blk(j) = blk(i);  i < L, j >= L: blk(j) < blk(i);
i >= L, j >= L: blk(j) <= blk(i);  i >= L, j < L: never.
Output: RMSNorm and the untied head over the noisy half; logits at noisy
position i predict token i.  loss_sum = sum w_i (m_i / t) CE_i, t =
`noise_level`, m the noise field.

Departures: packed [gate | up] expert matrices (the same products); no
auxiliary balance loss; one fixed t for every row; with `router_trained`
false the router's weights are no trained leaf and the combine weights are
constants to the gradient (`stop_gradient`), as in the program."""

import jax
import jax.numpy as jnp

from reference import blocks as B

WEIGHTS_FIELD = "loss_weight"
HEADS_PER_GROUP = 8  # attention is computed a group of heads at a time


def leaves(cfg, traffic):
    """(name, shape, init kind, trainable), in the order in which the
    program's builder creates its parameters."""
    d, h, hk, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    ffe, held, vocab = (cfg["moe_intermediate_size"], cfg["num_experts"],
                        cfg["vocab_size"])
    std = f"normal:{cfg['initializer_range']}"
    out = [("embed_w", (vocab, d), std, True)]

    def norm(name, width=d):
        out.append((name + ".scale", (width,), "ones", True))

    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        norm(p + ".attn_norm")
        out.append((p + ".q_w", (d, h * dh), std, True))
        out.append((p + ".k_w", (d, hk * dh), std, True))
        out.append((p + ".v_w", (d, hk * dh), std, True))
        norm(p + ".q_norm", dh)
        norm(p + ".k_norm", dh)
        out.append((p + ".o_w", (h * dh, d), std, True))
        norm(p + ".ffn_norm")
        out.append((p + ".router_w", (d, cfg["router_experts"]), std,
                    cfg.get("router_trained", True)))
        out.append((p + ".experts_gate_up_w", (held, d, 2 * ffe), std, True))
        out.append((p + ".experts_down_w", (held, ffe, d), std, True))
    norm("final_norm")
    out.append(("head_w", (d, vocab), std, True))
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta, period):
    """x [b, t, h, d]: the pair (x[i], x[i + d/2]) at row p is turned by
    the angle (p mod period) * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = (jnp.arange(x.shape[1]) % period).astype(jnp.float32)
    angle = pos[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x0, x1 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                           axis=-1)


def block_diffusion_mask(seq, block):
    """[2 seq, 2 seq] bool by the four rules, rows and keys [noisy ;
    clean]."""
    pos = jnp.arange(2 * seq)
    noisy = pos < seq
    blk = (pos % seq) // block
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


def swiglu(dots, x, w_gate_up, w_down):
    gate, up = jnp.split(dots.mm(x, w_gate_up), 2, axis=-1)
    return dots.mm(jax.nn.silu(gate) * up, w_down)


def attention(dots, cfg, x, P, p):
    b, t, _ = x.shape
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta, seq = cfg["rms_norm_eps"], float(cfg["rope_theta"]), t // 2
    q = dots.mm(x, P[p + ".q_w"]).reshape(b, t, h, dh)
    k = dots.mm(x, P[p + ".k_w"]).reshape(b, t, hk, dh)
    v = dots.mm(x, P[p + ".v_w"]).reshape(b, t, hk, dh)
    q = rope(rms_norm(q, P[p + ".q_norm.scale"], eps), theta, seq)
    k = rope(rms_norm(k, P[p + ".k_norm.scale"], eps), theta, seq)
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    mask = jnp.where(block_diffusion_mask(seq, cfg["block_length"]), 0.0,
                     -1e30)

    @jax.checkpoint
    def heads(qkv):  # [b, g, t, dh] each: a group of heads at a time
        qg, kg, vg = qkv
        s = dots.bmm(qg, jnp.swapaxes(kg, -1, -2)) * dh ** -0.5
        return dots.bmm(jax.nn.softmax(s + mask, axis=-1), vg)

    g = min(HEADS_PER_GROUP, h)

    def grouped(a):  # [b, t, h, dh] -> [h/g, b, g, t, dh]
        return a.transpose(0, 2, 1, 3).reshape(
            b, h // g, g, t, dh).transpose(1, 0, 2, 3, 4)

    ctx = jax.lax.map(heads, (grouped(q), grouped(k), grouped(v)))
    ctx = ctx.transpose(1, 3, 0, 2, 4).reshape(b, t, h * dh)
    return dots.mm(ctx, P[p + ".o_w"])


def route(dots, cfg, x, w, idx=None):
    """[b, t, router_experts] of each position's weight for each expert:
    0 but for the chosen (`idx` [b, t, top_k] where a diagnostic hands
    them in)."""
    scores = jax.nn.softmax(dots.mm(x, w), axis=-1)
    if idx is None:
        _, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weight = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    picked = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
    return jnp.sum(picked * weight[..., None], axis=-2)


def moe(dots, cfg, x, P, p, offset=None, held=None, idx=None):
    """The chosen experts among those held here (default: the
    configuration's share)."""
    offset = cfg["expert_offset"] if offset is None else offset
    gates = route(dots, cfg, x, P[p + ".router_w"], idx)
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


def block(dots, cfg, x, P, p, idx=None):
    eps = cfg["rms_norm_eps"]
    x = x + attention(dots, cfg,
                      rms_norm(x, P[p + ".attn_norm.scale"], eps), P, p)
    return x + moe(dots, cfg, rms_norm(x, P[p + ".ffn_norm.scale"], eps),
                   P, p, idx=idx)


def hidden_states(dots, cfg, P, ids, noise, route_as=None):
    """ids, noise [b, L]: the stack's state over [x_t ; x0], [b, 2L, d]."""
    noisy = jnp.where(noise > 0, cfg["mask_token_id"], ids)
    x = P["embed_w"][jnp.concatenate([noisy, ids], axis=1)]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x, P, p=f"layer{i}",
            idx=None if route_as is None else route_as[:, i]:
            block(dots, cfg, x, P, p, idx))(x, P)
    return x


def loss_sum(dots, cfg, params, block_):
    """sum over the rows' noisy positions of weight * (masked / t) *
    CE(logits at the position, its own token)."""
    ids = block_["ids"][..., 0]
    noise = block_["noise"][..., 0]
    weights = block_[WEIGHTS_FIELD][..., 0]
    x = hidden_states(dots, cfg, params, ids, noise, block_.get("route_as"))
    final = rms_norm(x[:, :ids.shape[1]], params["final_norm.scale"],
                     cfg["rms_norm_eps"])
    return B.weighted_cross_entropy_sum(
        dots.mm(final, params["head_w"]), ids,
        weights * noise / cfg["noise_level"])
