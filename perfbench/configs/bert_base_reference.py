"""Plain reference of the `bert_base` configuration: the encoder of Devlin
et al. 2018 with a masked-language-model head, as this repository builds it
(`paddle_tpu.models.bert.build_pretrain_net`), in float32 `jax.numpy` over
`reference/blocks.py`.  Imports nothing of the program under test.

Departures from the paper, all the builder's and followed here: no biases
on the attention projections, the head is one projection from the last
layer to the vocabulary (no transform layer, no tied embedding, no
next-sentence loss), the loss is taken at the positions that
`mask_weights` marks, the position table has as many rows as the sequence,
dropout 0 (see the configuration file).  GELU is the exact erf form."""

import jax

from reference import blocks as B

WEIGHTS_FIELD = "mask_weights"


def leaves(cfg, traffic):
    """(name, shape, init kind, trainable), in the order in which the
    program's builder creates its parameters."""
    dm, dff, vocab = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"]
    std = f"normal:{cfg['initializer_range']}"
    out = [("word_emb", (vocab, dm), std, True),
           ("pos_emb", (traffic["seq_len"], dm), std, True),
           ("sent_emb", (cfg["type_vocab_size"], dm), std, True),
           ("emb_ln.scale", (dm,), "ones", True),
           ("emb_ln.bias", (dm,), "zeros", True)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        out += [(p + ".qkv_w", (dm, 3 * dm), "xavier", True),
                (p + ".out_w", (dm, dm), "xavier", True),
                (p + ".ln1.scale", (dm,), "ones", True),
                (p + ".ln1.bias", (dm,), "zeros", True),
                (p + ".ffn_in_w", (dm, dff), "xavier", True),
                (p + ".ffn_in_b", (dff,), "zeros", True),
                (p + ".ffn_out_w", (dff, dm), "xavier", True),
                (p + ".ffn_out_b", (dm,), "zeros", True),
                (p + ".ln2.scale", (dm,), "ones", True),
                (p + ".ln2.bias", (dm,), "zeros", True)]
    out += [("head_w", (dm, vocab), "xavier", True),
            ("head_b", (vocab,), "zeros", True)]
    return out


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


def loss_sum(dots, cfg, params, block):
    """Weighted masked-LM cross-entropy summed over the rows of `block`."""
    P = params
    n_head = cfg["num_attention_heads"]
    x = (P["word_emb"][block["src_ids"][..., 0]]
         + P["pos_emb"][block["pos_ids"][..., 0]]
         + P["sent_emb"][block["sent_ids"][..., 0]])
    x = B.layer_norm(x, P["emb_ln.scale"], P["emb_ln.bias"])
    mask = block["input_mask"][..., 0]  # [b, t], 1 valid / 0 pad
    bias = ((mask - 1.0) * 1e9)[:, None, None, :]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        attn = B.self_attention(dots, x, P[p + ".qkv_w"], P[p + ".out_w"],
                                bias, n_head)
        x = B.layer_norm(x + attn, P[p + ".ln1.scale"], P[p + ".ln1.bias"])
        ff = B.feed_forward(dots, x, P[p + ".ffn_in_w"], P[p + ".ffn_in_b"],
                            P[p + ".ffn_out_w"], P[p + ".ffn_out_b"], _gelu)
        x = B.layer_norm(x + ff, P[p + ".ln2.scale"], P[p + ".ln2.bias"])
    logits = dots.mm(x, P["head_w"]) + P["head_b"]
    return B.weighted_cross_entropy_sum(
        logits, block["mask_labels"][..., 0], block["mask_weights"][..., 0])
