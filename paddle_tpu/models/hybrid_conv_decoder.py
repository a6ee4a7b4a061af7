"""Pre-norm decoder-only stack whose OPERATOR differs by layer from a
published table, as LFM2 defines it (LiquidAI/LFM2-8B-A1B, model_type
`lfm2_moe`): `layer_types[i]` is "conv", a gated short convolution between
an in- and an out-projection, or "full_attention", causal grouped-query
attention with an RMS norm over each head of q and of k before the rotary
turn.  The first `num_dense_layers` blocks have a dense SwiGLU FFN, the
rest a sigmoid top-k router with a choice-only expert bias over routed
experts and no shared expert; the chosen scores are normalised by their
sum plus 1e-6.  One RMS norm after the last block, then the head, which is
the embedding transposed: one parameter, read by the look-up and by the
logits' product, its gradient the sum of both.  Trained on the next token.

The expert layer, its share of an expert-parallel deployment (`n_held`,
`expert_offset`), the block skeleton, the dense FFN and the load counters
are models/mla_moe_decoder.py's; the attention is models/
block_diffusion_decoder.py's with `causal` in the place of its mask.

Parameters in order: embedding; each block: operator norm, the operator's
weights (conv: in-projection, filter, out-projection; attention: q, k, v
weights, q norm, k norm, output weight), FFN norm, the FFN's weights
(dense: gate|up, down; experts: router, expert bias, the two stacked
expert weights); final norm.  Matmul weights normal(0, init_std), filters
normal(0, taps ** -0.5)."""

from __future__ import annotations

from .. import layers
from ..layers import contrib
from ..param_attr import ParamAttr
from .block_diffusion_decoder import grouped_query_attention
from .mla_moe_decoder import (_Net, _embedding, _linear, _publish_load,
                              _shaped, decoder_block)

def short_conv_operator(net, x, name):
    """[B | C | x] = x W_in; y = C * causal_depthwise(B * x), `conv_taps`
    taps a channel, no bias; out = y W_out."""
    d = net.d_model
    bcx = _linear(x, net.weight(name + ".conv_in_w", (d, 3 * d)), 3 * d)
    y = contrib.short_conv(bcx, taps=net.conv_taps,
                           param_attr=ParamAttr(name=name + ".conv_w"))
    return _linear(y, net.weight(name + ".conv_out_w", (d, d)), d)


def causal_gqa_attention(net, x, name):
    return grouped_query_attention(net, x, name, causal=True)


OPERATORS = {"conv": short_conv_operator,
             "full_attention": causal_gqa_attention}


def build_train_net(vocab_size, seq_len, batch, layer_types,
                    num_dense_layers=2, d_model=2048, n_head=32, n_kv_head=8,
                    head_dim=64, conv_taps=3, d_ff_dense=7168,
                    d_ff_expert=1792, n_experts=32, n_held=None,
                    expert_offset=0, top_k=4, routed_scale=1.0,
                    router_norm_eps=1e-6, rope_theta=1e6, rms_eps=1e-5,
                    init_std=0.02, bias_std=0.0, lr=1e-4,
                    with_optimizer=True, train_router=True):
    """Next-token training program over packed sequences.

    Feeds: `ids` [batch, seq_len + 1, 1] int64 (the inputs are its first
    seq_len positions, the labels the same ids shifted by one) and
    `loss_weight` [batch, seq_len, 1].  Loss = sum(w * CE) / sum(w).
    `layer_types` names each block's operator; block i has a dense FFN
    where i < `num_dense_layers` and the expert layer after that.
    Returns (loss, the last hidden state before the final norm)."""
    from .. import optimizer as opt_mod
    from ..core import framework as fw

    unknown = sorted(set(layer_types) - set(OPERATORS))
    if unknown:
        raise ValueError(
            f"layer_types {unknown} are none of {sorted(OPERATORS)}")
    net = _Net(
        vocab_size=vocab_size, d_model=d_model, n_head=n_head,
        n_kv_head=n_kv_head, head_dim=head_dim, conv_taps=conv_taps,
        d_ff_dense=d_ff_dense, d_ff_expert=d_ff_expert, n_experts=n_experts,
        n_held=n_experts if n_held is None else n_held,
        expert_offset=expert_offset, top_k=top_k, routed_scale=routed_scale,
        router_norm_eps=router_norm_eps, n_shared=0, bias_std=bias_std,
        rope_theta=rope_theta, rms_eps=rms_eps, init_std=init_std,
        train_router=train_router)
    ids = layers.data(name="ids", shape=[batch, seq_len + 1, 1],
                      dtype="int64", append_batch_size=False)
    weights = layers.data(name="loss_weight", shape=[batch, seq_len, 1],
                          dtype="float32", append_batch_size=False)

    def shifted(k):
        return layers.slice(ids, axes=[1], starts=[k], ends=[k + seq_len])

    embed_w = net.weight("embed_w", (vocab_size, d_model))
    x = _embedding(shifted(0), embed_w, (batch, seq_len, d_model))
    for i, kind in enumerate(layer_types):
        x = decoder_block(net, x, f"layer{i}", moe=i >= num_dense_layers,
                          attention=OPERATORS[kind])
    final = net.norm(x, "final_norm")
    # the tied head: logits = final . embed_w^T, the embedding read as it
    # lies (a product that contracts both operands' last axis)
    logits = layers.reshape(
        layers.matmul(final, embed_w, transpose_y=True), [-1, vocab_size])
    ce = layers.softmax_with_cross_entropy(
        logits=logits, label=layers.reshape(shifted(1), [-1, 1]))
    w2 = layers.reshape(weights, [-1, 1])
    loss = layers.elementwise_div(
        layers.reduce_sum(layers.elementwise_mul(ce, w2)),
        layers.reduce_sum(w2))
    if net.loads:
        _publish_load(net, fw.default_main_program())
    if with_optimizer:
        opt_mod.Adam(learning_rate=lr).minimize(loss)
    return loss, x
