"""BERT-style encoder (BASELINE.md: "BERT-class (layer_norm/gelu/fused
attention)"; built from the same primitives as the reference would be —
layers/nn.py layer_norm:3030 + gelu + attention composed from matmul/softmax
— but with the Pallas fused-attention path available via use_flash).

Under use_flash the self-attention sites ride transformer.py's
multi_head_attention selection: each site is ONE fused_qkv_attention op,
whose projections are XLA dots straight into and out of the [b, t, h, dh]
layout of the bthd flash kernels and whose grad op reads q, k, v, the
context and the logsumexp its forward kept (PERF.md PR 28 (3), PR 30: the
Pallas kernel that kept q/k/v out of HBM was slower than this; it and
FLAGS_fused_qkv_attention were deleted in PR 30).  Parameter names and shapes are
those of the fc+split+fused_attention+fc composition (the unnamed
ffn/head fc parameters keep their fc_N draws — checkpoints interop,
asserted in tests/test_fused_qkv_attention.py)."""

from __future__ import annotations

import numpy as np

from .. import layers
from ..initializer import NormalInitializer, ConstantInitializer
from ..param_attr import ParamAttr


def _dropout_residual(sub, x, dropout_rate):
    """dropout(sub) + x: ONE fused dropout-add op (the epilogue kernel of
    kernels/dropout_epilogue.py — mask regenerated in-kernel, fwd and bwd)
    under FLAGS.fused_dropout_add; the reference's separate dropout +
    elementwise_add ops otherwise.  rate 0 is a plain add either way."""
    from ..flags import FLAGS

    if dropout_rate and FLAGS.fused_dropout_add:
        return layers.dropout_add(sub, x, dropout_rate)
    if dropout_rate:
        sub = layers.dropout(sub, dropout_prob=dropout_rate,
                             dropout_implementation="upscale_in_train")
    return layers.elementwise_add(x, sub)


def bert_encoder_layer(x, attn_bias, n_head, d_model, d_ff, dropout_rate,
                       use_flash=False, name="layer"):
    from .transformer import multi_head_attention

    attn = multi_head_attention(
        x, None, None, attn_bias, d_model // n_head, d_model // n_head,
        d_model, n_head, dropout_rate, use_flash=use_flash,
    )
    x = layers.layer_norm(_dropout_residual(attn, x, dropout_rate),
                          begin_norm_axis=len(x.shape) - 1)
    ff = layers.fc(input=x, size=d_ff, act="gelu", num_flatten_dims=2)
    ff = layers.fc(input=ff, size=d_model, num_flatten_dims=2)
    return layers.layer_norm(_dropout_residual(ff, x, dropout_rate),
                             begin_norm_axis=len(x.shape) - 1)


def bert_encoder(
    src_ids,
    position_ids,
    sentence_ids,
    input_mask,
    vocab_size=30522,
    max_position=512,
    type_vocab_size=2,
    n_layer=12,
    n_head=12,
    d_model=768,
    d_ff=3072,
    dropout_rate=0.1,
    use_flash=False,
):
    """input_mask: [B, T, 1] float 1/0.  Returns [B, T, d_model]."""
    emb = layers.embedding(
        src_ids, size=[vocab_size, d_model],
        param_attr=ParamAttr(name="word_embedding",
                             initializer=NormalInitializer(0.0, 0.02)),
    )
    pos = layers.embedding(
        position_ids, size=[max_position, d_model],
        param_attr=ParamAttr(name="pos_embedding",
                             initializer=NormalInitializer(0.0, 0.02)),
    )
    sent = layers.embedding(
        sentence_ids, size=[type_vocab_size, d_model],
        param_attr=ParamAttr(name="sent_embedding",
                             initializer=NormalInitializer(0.0, 0.02)),
    )
    x = layers.elementwise_add(layers.elementwise_add(emb, pos), sent)
    x = layers.layer_norm(x, begin_norm_axis=len(x.shape) - 1)
    if dropout_rate:
        x = layers.dropout(x, dropout_prob=dropout_rate,
                           dropout_implementation="upscale_in_train")

    # attn bias from mask: (1-m)(-1e9), broadcast over heads
    # input_mask [B,T,1] -> [B,1,1,T]
    m = layers.transpose(input_mask, [0, 2, 1])  # [B,1,T]
    neg = layers.scale(m, scale=1e9, bias=-1e9)  # 0 where valid, -1e9 pad

    b, t, _ = src_ids.shape if src_ids.shape else (None, None, None)
    bias4 = layers.reshape(neg, [-1, 1, 1, neg.shape[-1]])
    # padding mask, not a parameter: marks the fused-attention bias as
    # stop-gradient so the TPU hardware-PRNG dropout fast path stays on
    # (a trainable bias forces hash masks — see ops/fused_ops.py)
    bias4.stop_gradient = True

    for i in range(n_layer):
        x = bert_encoder_layer(x, bias4, n_head, d_model, d_ff, dropout_rate,
                               use_flash=use_flash, name=f"layer_{i}")
    return x


def build_pretrain_net(vocab_size=1000, seq_len=128, n_layer=2, n_head=4,
                       d_model=128, d_ff=512, dropout_rate=0.0,
                       use_flash=False, with_optimizer=True, lr=1e-4):
    """Masked-LM pretraining objective (simplified: predict all positions,
    weighted by mask_weight)."""
    from .. import optimizer as opt_mod

    src = layers.data(name="src_ids", shape=[seq_len, 1], dtype="int64")
    pos = layers.data(name="pos_ids", shape=[seq_len, 1], dtype="int64")
    sent = layers.data(name="sent_ids", shape=[seq_len, 1], dtype="int64")
    mask = layers.data(name="input_mask", shape=[seq_len, 1], dtype="float32")
    labels = layers.data(name="mask_labels", shape=[seq_len, 1], dtype="int64")
    weights = layers.data(name="mask_weights", shape=[seq_len, 1],
                          dtype="float32")

    enc = bert_encoder(
        src, pos, sent, mask, vocab_size=vocab_size, max_position=seq_len,
        n_layer=n_layer, n_head=n_head, d_model=d_model, d_ff=d_ff,
        dropout_rate=dropout_rate, use_flash=use_flash,
    )
    logits = layers.fc(input=enc, size=vocab_size, num_flatten_dims=2)
    logits2 = layers.reshape(logits, [-1, vocab_size])
    labels2 = layers.reshape(labels, [-1, 1])
    loss = layers.softmax_with_cross_entropy(logits=logits2, label=labels2)
    w2 = layers.reshape(weights, [-1, 1])
    weighted = layers.elementwise_mul(loss, w2)
    total = layers.reduce_sum(weighted)
    denom = layers.reduce_sum(w2)
    avg_loss = layers.elementwise_div(total, denom)
    if with_optimizer:
        opt_mod.Adam(learning_rate=lr).minimize(avg_loss)
    return avg_loss, enc


def make_batch(batch_size, seq_len, vocab_size, rng=None):
    rng = rng or np.random.RandomState(0)
    pos = np.tile(np.arange(seq_len, dtype=np.int64)[None, :, None],
                  (batch_size, 1, 1))
    return {
        "src_ids": rng.randint(0, vocab_size, (batch_size, seq_len, 1)).astype("int64"),
        "pos_ids": pos,
        "sent_ids": np.zeros((batch_size, seq_len, 1), np.int64),
        "input_mask": np.ones((batch_size, seq_len, 1), np.float32),
        "mask_labels": rng.randint(0, vocab_size, (batch_size, seq_len, 1)).astype("int64"),
        "mask_weights": (rng.rand(batch_size, seq_len, 1) < 0.15).astype("float32"),
    }
