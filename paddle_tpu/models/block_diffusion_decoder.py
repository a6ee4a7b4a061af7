"""Pre-norm decoder-only stack with grouped-query attention, an RMS norm
over each head of q and of k before the rotary turn (`q_norm` / `k_norm`
of the Qwen3 lineage) and a sparse expert layer in every block, trained by
block diffusion (BD3-LM, Arriola et al. 2025, arXiv:2503.09573, section 3,
in its one-pass form; SDAR, arXiv:2510.06303, trains so).

A training row is x0 of L tokens in L / B blocks of B.  Each position is
replaced by [MASK] where the `noise` feed says so (the harness draws it at
one rate t a row), and the stack runs over the 2L positions [x_t ; x0],
rotary positions 0 .. L-1 twice, under the block-diffusion mask (layers.
contrib.fused_attention, mask="block_diffusion": a noisy block sees itself
both ways and the clean blocks before it, the clean half is block-causal).
The head reads the noisy half only; logits at noisy position i predict
token i (no shift); loss = sum w (m / t) CE / sum w, m the masked
positions (the linear schedule's NELBO weight).

The expert layer, its share of an expert-parallel deployment (`n_held`,
`expert_offset`), the block skeleton and the load counters are
models/mla_moe_decoder.py's; this stack brings its own attention, a softmax
router without a bias, and no shared expert.

Parameters in order: embedding; each block: attention norm, q, k, v
weights, q norm, k norm, output weight, FFN norm, router, the two stacked
expert weights; final norm; head.  All matmul weights normal(0, init_std)."""

from __future__ import annotations

from .. import layers
from ..layers import contrib
from .mla_moe_decoder import (_Net, _embedding, _linear, _publish_load,
                              _shaped, decoder_block)


def grouped_query_attention(net, x, name, period=0, **masking):
    """x [b, t, d]: `n_head` query heads over `n_kv_head` key/value heads
    of `head_dim`, each head of q and k RMS-normed (one learned scale a
    layer, shared by the heads) and then turned (half-split pairs,
    positions restarting every `period` rows where one is given); the bhtd
    flash kernels read key/value head i // group under `masking`
    (fused_attention's `causal` or `mask` arguments)."""
    b, t = x.shape[0], x.shape[1]
    h, hk, dh = net.n_head, net.n_kv_head, net.head_dim

    def heads(w_name, n):
        w = net.weight(name + w_name, (net.d_model, n * dh))
        return _shaped(layers.reshape(_linear(x, w, n * dh), [b, t, n, dh]),
                       (b, t, n, dh))

    q, k, v = heads(".q_w", h), heads(".k_w", hk), heads(".v_w", hk)

    def turned(a, norm_name):
        return contrib.rope(net.norm(a, name + norm_name),
                            theta=net.rope_theta, pairing="half",
                            period=period)

    q, k = turned(q, ".q_norm"), turned(k, ".k_norm")
    to_bhtd = [0, 2, 1, 3]
    ctx = contrib.fused_attention(
        _shaped(layers.transpose(q, to_bhtd), (b, h, t, dh)),
        _shaped(layers.transpose(k, to_bhtd), (b, hk, t, dh)),
        _shaped(layers.transpose(v, to_bhtd), (b, hk, t, dh)),
        scale=dh ** -0.5, fmt="bhtd", **masking)
    ctx = layers.reshape(layers.transpose(ctx, to_bhtd), [b, t, h * dh])
    w_o = net.weight(name + ".o_w", (h * dh, net.d_model))
    return _linear(_shaped(ctx, (b, t, h * dh)), w_o, net.d_model)


def gqa_attention(net, x, name):
    """x [b, 2L, d] = [noisy ; clean]: grouped-query attention whose
    positions restart at L, masked by position (block diffusion)."""
    return grouped_query_attention(
        net, x, name, period=net.seq_len, mask="block_diffusion",
        block_length=net.block_length, clean_offset=net.seq_len)


def build_train_net(vocab_size, seq_len, batch, block_length=4,
                    noise_level=0.5, mask_token_id=None, d_model=2048,
                    n_head=32, n_kv_head=4, head_dim=128, n_layer=4,
                    d_ff_expert=768, n_experts=128, n_held=None,
                    expert_offset=0, top_k=8, rope_theta=1e6, rms_eps=1e-6,
                    init_std=0.02, lr=1e-4, with_optimizer=True,
                    train_router=True):
    """Block-diffusion training program over packed rows.

    Feeds: `ids` [batch, seq_len, 1] int64 (x0), `noise` [batch, seq_len,
    1] float32 (1 where the position is masked; the harness draws it at
    rate `noise_level`) and `loss_weight` [batch, seq_len, 1].
    `mask_token_id` defaults to the vocabulary's last row.
    `train_router` False: the routers' weights are not trained and the
    combine weights carry no gradient (mla_moe_decoder.moe_ffn).
    Returns (loss, the last hidden state before the final norm, [batch,
    2 * seq_len, d_model])."""
    from .. import optimizer as opt_mod
    from ..core import framework as fw

    if seq_len % block_length:
        raise ValueError(f"seq_len {seq_len} is not whole blocks of "
                         f"{block_length}")
    net = _Net(
        vocab_size=vocab_size, seq_len=seq_len, block_length=block_length,
        d_model=d_model, n_head=n_head, n_kv_head=n_kv_head,
        head_dim=head_dim, d_ff_expert=d_ff_expert, n_experts=n_experts,
        n_held=n_experts if n_held is None else n_held,
        expert_offset=expert_offset, top_k=top_k, routed_scale=1.0,
        n_shared=0, scoring="softmax", router_bias=False, bias_std=0.0,
        rope_theta=rope_theta, rms_eps=rms_eps, init_std=init_std,
        train_router=train_router)
    ids = layers.data(name="ids", shape=[batch, seq_len, 1], dtype="int64",
                      append_batch_size=False)
    noise = layers.data(name="noise", shape=[batch, seq_len, 1],
                        dtype="float32", append_batch_size=False)
    weights = layers.data(name="loss_weight", shape=[batch, seq_len, 1],
                          dtype="float32", append_batch_size=False)
    mask_id = vocab_size - 1 if mask_token_id is None else mask_token_id
    noisy = layers.where(noise, layers.fill_constant([1], "int64", mask_id),
                         ids)
    tokens = layers.concat([noisy, ids], axis=1)  # [x_t ; x0]

    x = _embedding(tokens, net.weight("embed_w", (vocab_size, d_model)),
                   (batch, 2 * seq_len, d_model))
    for i in range(n_layer):
        x = decoder_block(net, x, f"layer{i}", moe=True,
                          attention=gqa_attention)
    # the head over the noisy half only: the clean half is context
    final = net.norm(
        _shaped(layers.slice(x, axes=[1], starts=[0], ends=[seq_len]),
                (batch, seq_len, d_model)), "final_norm")
    head_w = net.weight("head_w", (d_model, vocab_size))
    logits = layers.reshape(_linear(final, head_w, vocab_size),
                            [-1, vocab_size])
    ce = layers.softmax_with_cross_entropy(
        logits=logits, label=layers.reshape(ids, [-1, 1]))
    w2 = layers.reshape(weights, [-1, 1])
    masked = layers.scale(layers.reshape(noise, [-1, 1]),
                          scale=1.0 / float(noise_level))
    total = layers.reduce_sum(
        layers.elementwise_mul(ce, layers.elementwise_mul(w2, masked)))
    loss = layers.elementwise_div(total, layers.reduce_sum(w2))
    program = fw.default_main_program()
    _publish_load(net, program)
    if with_optimizer:
        opt_mod.Adam(learning_rate=lr).minimize(loss)
    return loss, x
