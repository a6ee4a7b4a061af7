"""Plain reference of the `transformer_base` configuration: the
encoder-decoder of Vaswani et al. 2017 as this repository builds it, in
float32 `jax.numpy` over `reference/blocks.py`.  Imports nothing of the
program under test.

Departures from the paper, all the builder's (`paddle_tpu.models.
transformer.transformer`) and followed here so that the two compute the
same function: no sqrt(d_model) scaling of the embeddings, separate source
and target embeddings and output projection, no biases on the attention
projections, dropout 0 (see the configuration file).  The position tables
are the paper's sinusoids, made here and handed to the program."""

import jax
import jax.numpy as jnp

from reference import blocks as B

WEIGHTS_FIELD = "lbl_weight"


def _sizes(cfg):
    return (cfg["n_layer"], cfg["n_head"], cfg["d_model"],
            cfg["n_head"] * cfg["d_key"], cfg["d_inner_hid"])


def leaves(cfg, traffic):
    """(name, shape, init kind, trainable), in the order in which the
    program's builder creates its parameters."""
    n_layer, _, dm, hd, dff = _sizes(cfg)
    emb = f"normal:{dm ** -0.5}"
    max_len = max(traffic["src_len"], traffic["trg_len"])
    out = []

    def ln(prefix):
        out.append((prefix + ".scale", (dm,), "ones", True))
        out.append((prefix + ".bias", (dm,), "zeros", True))

    def ffn(prefix):
        out.append((prefix + ".ffn_in_w", (dm, dff), "xavier", True))
        out.append((prefix + ".ffn_in_b", (dff,), "zeros", True))
        out.append((prefix + ".ffn_out_w", (dff, dm), "xavier", True))
        out.append((prefix + ".ffn_out_b", (dm,), "zeros", True))

    out.append(("src_emb", (cfg["src_vocab_size"], dm), emb, True))
    out.append(("src_pos", (max_len, dm), "sinusoid", False))
    for i in range(n_layer):
        p = f"enc{i}"
        out.append((p + ".qkv_w", (dm, 3 * hd), "xavier", True))
        out.append((p + ".out_w", (hd, dm), "xavier", True))
        ln(p + ".ln1")
        ffn(p)
        ln(p + ".ln2")
    out.append(("trg_emb", (cfg["trg_vocab_size"], dm), emb, True))
    out.append(("trg_pos", (max_len, dm), "sinusoid", False))
    for i in range(n_layer):
        p = f"dec{i}"
        out.append((p + ".qkv_w", (dm, 3 * hd), "xavier", True))
        out.append((p + ".out_w", (hd, dm), "xavier", True))
        ln(p + ".ln1")
        for w in ("q", "k", "v"):
            out.append((p + f".cross_{w}_w", (dm, hd), "xavier", True))
        out.append((p + ".cross_out_w", (hd, dm), "xavier", True))
        ln(p + ".ln2")
        ffn(p)
        ln(p + ".ln3")
    out.append(("predict_w", (dm, cfg["trg_vocab_size"]), "xavier", True))
    out.append(("predict_b", (cfg["trg_vocab_size"],), "zeros", True))
    return out


def loss_sum(dots, cfg, params, block):
    """Weighted cross-entropy summed over the rows of `block`."""
    n_layer, n_head, _, _, _ = _sizes(cfg)
    P = params
    src, trg = block["src_word"][..., 0], block["trg_word"][..., 0]
    src_pos, trg_pos = block["src_pos"][..., 0], block["trg_pos"][..., 0]

    def pad_bias(word):  # [b, t] ids -> [b, 1, 1, t], -1e9 at pad id 0
        return jnp.where(word == 0, -1e9, 0.0)[:, None, None, :]

    src_bias = pad_bias(src)
    causal = jnp.where(trg_pos[:, :, None] < trg_pos[:, None, :], -1e9, 0.0)
    trg_bias = causal[:, None, :, :] + pad_bias(trg)

    def ln(x, prefix):
        return B.layer_norm(x, P[prefix + ".scale"], P[prefix + ".bias"])

    def ffn(x, p):
        return B.feed_forward(dots, x, P[p + ".ffn_in_w"], P[p + ".ffn_in_b"],
                              P[p + ".ffn_out_w"], P[p + ".ffn_out_b"],
                              jax.nn.relu)

    x = P["src_emb"][src] + P["src_pos"][src_pos]
    for i in range(n_layer):
        p = f"enc{i}"
        x = ln(x + B.self_attention(dots, x, P[p + ".qkv_w"], P[p + ".out_w"],
                                    src_bias, n_head), p + ".ln1")
        x = ln(x + ffn(x, p), p + ".ln2")
    mem = x

    x = P["trg_emb"][trg] + P["trg_pos"][trg_pos]
    for i in range(n_layer):
        p = f"dec{i}"
        x = ln(x + B.self_attention(dots, x, P[p + ".qkv_w"], P[p + ".out_w"],
                                    trg_bias, n_head), p + ".ln1")
        x = ln(x + B.cross_attention(
            dots, x, mem, P[p + ".cross_q_w"], P[p + ".cross_k_w"],
            P[p + ".cross_v_w"], P[p + ".cross_out_w"], src_bias, n_head),
            p + ".ln2")
        x = ln(x + ffn(x, p), p + ".ln3")

    logits = dots.mm(x, P["predict_w"]) + P["predict_b"]
    return B.weighted_cross_entropy_sum(
        logits, block["lbl_word"][..., 0], block["lbl_weight"][..., 0])
