"""Pre-norm decoder-only stack with latent attention (MLA), sparse expert
layers and a multi-token-prediction module, as DeepSeek-V2/V3 define
them (arXiv:2405.04434 section 2.1, arXiv:2412.19437 sections 2.1-2.2),
trained with causal attention.

The first builder whose layers are of different kinds: `n_dense` leading
blocks with a dense SwiGLU FFN, `n_moe` blocks whose FFN is a sigmoid
top-k router over `n_experts` routed experts plus shared experts, and
`n_mtp` prediction modules that share the embedding and the head.

One chip's share of an expert-parallel deployment is built by telling
the expert layer which experts it holds (`n_held`, `expert_offset`): the
router scores all `n_experts`, the chip computes the chosen experts it
holds and the shared expert, and what absent experts would add is left
out.  There is no exchange on one chip and nothing stands in for the
absent chips.

Parameters are created in one fixed order (embedding; each block:
attention norm, q down/norm/up, kv down/norm/up, output, FFN norm, FFN;
final norm; head; each MTP module: its two norms, its projection, a
block, its final norm), all matmul weights normal(0, init_std)."""

from __future__ import annotations

import math

from .. import layers
from ..initializer import NormalInitializer
from ..layer_helper import LayerHelper
from ..layers import contrib
from ..param_attr import ParamAttr


class _Net:
    """The sizes of one stack, and the parameters it creates by name.
    A stack that says nothing else routes by sigmoid scores with a
    score-correction bias (`scoring`, `router_bias`), normalises the
    chosen scores by their bare sum (`router_norm_eps`: a family's own
    addend) and trains its router (`train_router`)."""

    scoring = "sigmoid"
    router_bias = True
    router_norm_eps = None
    train_router = True

    def __init__(self, **sizes):
        self.__dict__.update(sizes)
        # an expert layer: (Load [n_held] int32, the pairs it routes)
        self.loads = []

    def attr(self, name):
        return ParamAttr(name=name,
                         initializer=NormalInitializer(0.0, self.init_std))

    def weight(self, name, shape):
        return LayerHelper("mla_moe_decoder").create_parameter(
            self.attr(name), shape=list(shape), dtype="float32")

    def norm(self, x, name):
        return contrib.rms_norm(x, epsilon=self.rms_eps,
                                param_attr=ParamAttr(name=name + ".scale"))


def _linear(x, w, out_features):
    """x [b, t, k] @ w [k, n] -> [b, t, n]."""
    helper = LayerHelper("mul")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mul", inputs={"X": [x], "Y": [w]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": 2, "y_num_col_dims": 1})
    out.shape = tuple(x.shape[:2]) + (out_features,)
    return out


def _shaped(var, shape):
    var.shape = tuple(shape)
    return var


def _embedding(tokens, embed_w, shape):
    """A look-up of `tokens` [.., 1] in the one embedding parameter."""
    helper = LayerHelper("embedding")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "lookup_table", inputs={"Ids": [tokens], "W": [embed_w]},
        outputs={"Out": [out]},
        attrs={"is_sparse": False, "is_distributed": False,
               "padding_idx": -1})
    return _shaped(out, shape)


def mla_attention(net, x, name):
    """Multi-head latent attention: queries and keys/values go through
    low-rank latents with their own RMS norms; each head's query and key
    are a no-position part and a rotary part, the key's rotary part is ONE
    vector a position shared by all heads; values have their own head
    size.  The flash kernels (fused_attention, fmt bhtd) take d_qk != d_v."""
    b, t = x.shape[0], x.shape[1]
    h, nope, rot, dv = net.n_head, net.qk_nope_dim, net.qk_rope_dim, \
        net.v_head_dim
    w_dq = net.weight(name + ".q_a_w", (net.d_model, net.q_lora_rank))
    c_q = net.norm(_linear(x, w_dq, net.q_lora_rank), name + ".q_a_norm")
    w_uq = net.weight(name + ".q_b_w", (net.q_lora_rank, h * (nope + rot)))
    q = layers.reshape(_linear(c_q, w_uq, h * (nope + rot)),
                       [b, t, h, nope + rot])
    q_nope, q_rot = layers.split(_shaped(q, (b, t, h, nope + rot)),
                                 [nope, rot], dim=3)
    w_dkv = net.weight(name + ".kv_a_w",
                       (net.d_model, net.kv_lora_rank + rot))
    c_kv, k_rot = layers.split(_linear(x, w_dkv, net.kv_lora_rank + rot),
                               [net.kv_lora_rank, rot], dim=2)
    c_kv = net.norm(c_kv, name + ".kv_a_norm")
    w_ukv = net.weight(name + ".kv_b_w", (net.kv_lora_rank, h * (nope + dv)))
    kv = layers.reshape(_linear(c_kv, w_ukv, h * (nope + dv)),
                        [b, t, h, nope + dv])
    k_nope, v = layers.split(_shaped(kv, (b, t, h, nope + dv)),
                             [nope, dv], dim=3)
    q_rot = contrib.rope(q_rot, theta=net.rope_theta)
    k_rot = contrib.rope(
        _shaped(layers.reshape(k_rot, [b, t, 1, rot]), (b, t, 1, rot)),
        theta=net.rope_theta)
    q = layers.concat([q_nope, q_rot], axis=3)
    k = layers.concat([k_nope, layers.expand(k_rot, [1, 1, h, 1])], axis=3)
    to_bhtd = [0, 2, 1, 3]
    ctx = contrib.fused_attention(
        _shaped(layers.transpose(q, to_bhtd), (b, h, t, nope + rot)),
        _shaped(layers.transpose(k, to_bhtd), (b, h, t, nope + rot)),
        _shaped(layers.transpose(v, to_bhtd), (b, h, t, dv)),
        scale=(nope + rot) ** -0.5, causal=True, fmt="bhtd")
    ctx = layers.reshape(layers.transpose(ctx, to_bhtd), [b, t, h * dv])
    w_o = net.weight(name + ".o_w", (h * dv, net.d_model))
    return _linear(_shaped(ctx, (b, t, h * dv)), w_o, net.d_model)


def swiglu_ffn(net, x, d_ff, name):
    w_gu = net.weight(name + ".gate_up_w", (net.d_model, 2 * d_ff))
    act = contrib.swiglu(_linear(x, w_gu, 2 * d_ff))
    w_down = net.weight(name + ".down_w", (d_ff, net.d_model))
    return _linear(act, w_down, net.d_model)


def moe_ffn(net, x, name):
    """The chosen routed experts this chip holds, plus the shared expert
    where the stack has one (`n_shared`).  A stack that does not train its
    router (`train_router` False) keeps the router's weights as they are
    and hands no gradient on through the combine weights: on one chip's
    share only the held experts return an output, so that gradient is
    theirs alone and pulls tokens onto this chip."""
    router_attr = net.attr(name + ".router_w")
    router_attr.trainable = net.train_router
    idx, weight = contrib.moe_router(
        x, net.n_experts, net.top_k, scale=net.routed_scale,
        bias_std=net.bias_std, param_attr=router_attr,
        bias_attr=ParamAttr(name=name + ".router_bias")
        if net.router_bias else False, scoring=net.scoring,
        norm_eps=net.router_norm_eps)
    if not net.train_router:
        weight.stop_gradient = True
    routed, load = contrib.moe_experts(
        x, idx, weight, net.n_held, net.d_ff_expert, net.n_experts,
        expert_offset=net.expert_offset,
        gate_up_attr=net.attr(name + ".experts_gate_up_w"),
        down_attr=net.attr(name + ".experts_down_w"))
    net.loads.append((load, math.prod(x.shape[:-1]) * net.top_k))
    if not net.n_shared:
        return routed
    shared = swiglu_ffn(net, x, net.d_ff_expert * net.n_shared,
                        name + ".shared")
    return layers.elementwise_add(routed, shared)


def decoder_block(net, x, name, moe, attention=None):
    """h <- h + Attn(RMSNorm(h)); h <- h + FFN(RMSNorm(h)).  `attention`
    (net, x, name) is the stack's own; latent attention where none is
    given."""
    attn = (attention or mla_attention)(
        net, net.norm(x, name + ".attn_norm"), name)
    x = _shaped(layers.elementwise_add(x, attn), x.shape)
    y = net.norm(x, name + ".ffn_norm")
    ffn = moe_ffn(net, y, name) if moe else swiglu_ffn(
        net, y, net.d_ff_dense, name)
    return _shaped(layers.elementwise_add(x, ffn), x.shape)


def _token_loss(net, hidden, head_w, labels):
    """Per-token cross-entropy [b*t, 1] of hidden @ head against labels."""
    logits = layers.reshape(_linear(hidden, head_w, net.vocab_size),
                            [-1, net.vocab_size])
    return layers.softmax_with_cross_entropy(
        logits=logits, label=layers.reshape(labels, [-1, 1]))


def _publish_load(net, program):
    """Three device counters for the executor's flight event: the pairs
    this chip computed a step (all expert layers), the largest held
    expert's load over the mean, and the rows the expert layers walked
    for those pairs, by the op's own rule (ops/llm_ops.py rows_walked)."""
    from .. import monitor
    from ..ops.llm_ops import rows_walked

    loads, routed = zip(*net.loads)
    (routed,) = set(routed)  # every layer routes the same T x top_k pairs
    load = layers.cast(layers.stack(list(loads)), "float32")  # [layers, G]
    pairs = layers.reduce_sum(load)
    worst = layers.elementwise_div(
        layers.reduce_max(load),
        layers.elementwise_max(layers.reduce_mean(load),
                               layers.fill_constant([1], "float32", 1e-9)))
    walked = layers.reduce_sum(rows_walked(
        layers.reduce_sum(load, dim=1), routed, net.n_held, net.n_experts,
        lambda live, rows: layers.ceil(live / rows)))
    for name, var in (("moe_local_pairs", pairs),
                      ("moe_max_over_mean", worst),
                      ("moe_rows_walked", walked)):
        var.stop_gradient = True
        monitor.device_counter(program, name, var)


def build_train_net(vocab_size, seq_len, batch, d_model=2048, n_head=32,
                    q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
                    qk_rope_dim=64, v_head_dim=128, n_dense=1, n_moe=1,
                    d_ff_dense=7168, d_ff_expert=768, n_experts=256,
                    n_held=None, expert_offset=0, top_k=8, routed_scale=2.5,
                    n_shared=1, n_mtp=1, mtp_weight=0.3, rope_theta=1e4,
                    rms_eps=1e-6, init_std=0.02, bias_std=0.0, lr=1e-4,
                    with_optimizer=True):
    """Next-token training program over packed sequences.

    Feeds: `ids` [batch, seq_len + 1 + n_mtp, 1] int64 (the inputs are its
    first seq_len positions, the labels the same ids shifted by one, and
    by two for the MTP module) and `loss_weight` [batch, seq_len, 1].
    Loss = sum(w * (CE_next + mtp_weight * CE_after_next)) / sum(w).
    Returns (loss, the last hidden state before the final norm)."""
    from .. import optimizer as opt_mod
    from ..core import framework as fw

    net = _Net(
        vocab_size=vocab_size, d_model=d_model, n_head=n_head,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim,
        v_head_dim=v_head_dim, d_ff_dense=d_ff_dense,
        d_ff_expert=d_ff_expert, n_experts=n_experts,
        n_held=n_experts if n_held is None else n_held,
        expert_offset=expert_offset, top_k=top_k, routed_scale=routed_scale,
        n_shared=n_shared, rope_theta=rope_theta, rms_eps=rms_eps,
        init_std=init_std, bias_std=bias_std)
    ids = layers.data(name="ids", shape=[batch, seq_len + 1 + n_mtp, 1],
                      dtype="int64", append_batch_size=False)
    weights = layers.data(name="loss_weight", shape=[batch, seq_len, 1],
                          dtype="float32", append_batch_size=False)

    def shifted(k):
        return layers.slice(ids, axes=[1], starts=[k], ends=[k + seq_len])

    embed_w = net.weight("embed_w", (vocab_size, d_model))

    def embed(tokens):  # one parameter, a look-up a prediction depth
        return _embedding(tokens, embed_w, (batch, seq_len, d_model))

    x = embed(shifted(0))
    for i in range(n_dense + n_moe):
        x = decoder_block(net, x, f"layer{i}", moe=i >= n_dense)
    hidden = x
    final = net.norm(hidden, "final_norm")
    head_w = net.weight("head_w", (d_model, vocab_size))
    w2 = layers.reshape(weights, [-1, 1])
    total = layers.reduce_sum(layers.elementwise_mul(
        _token_loss(net, final, head_w, shifted(1)), w2))
    for k in range(n_mtp):
        # h' = [RMSNorm(h) | RMSNorm(Emb(next token))] M, one block of the
        # MoE kind, a final norm of its own, the SHARED embedding and head
        p = f"mtp{k}"
        h_n = net.norm(hidden, p + ".hnorm")
        e_n = net.norm(embed(shifted(k + 1)), p + ".enorm")
        proj = net.weight(p + ".proj_w", (2 * d_model, d_model))
        hidden = _linear(
            _shaped(layers.concat([h_n, e_n], axis=2),
                    (batch, seq_len, 2 * d_model)), proj, d_model)
        hidden = decoder_block(net, hidden, p + ".block", moe=True)
        ce = _token_loss(net, net.norm(hidden, p + ".final_norm"),
                         head_w, shifted(k + 2))
        total = layers.elementwise_add(total, layers.scale(
            layers.reduce_sum(layers.elementwise_mul(ce, w2)),
            scale=float(mtp_weight)))
    loss = layers.elementwise_div(total, layers.reduce_sum(w2))
    if net.loads:
        _publish_load(net, fw.default_main_program())
    if with_optimizer:
        opt_mod.Adam(learning_rate=lr).minimize(loss)
    return loss, x
