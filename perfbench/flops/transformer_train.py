"""Matmul FLOPs per trained target token of the encoder-decoder
transformer: 2 FLOPs per multiply-add, training = 3 x forward, no
recomputation counted.  A copy of `bench.transformer_train_flops_per_token`
(perfbench/tests/test_peaks_flops.py holds the two together)."""


def transformer_train_flops_per_token(n_layer, d_model, d_ff, n_head, d_key,
                                      seq_len, vocab):
    dh = n_head * d_key
    attn = 4 * d_model * dh + 2 * seq_len * dh
    ffn = 2 * d_model * d_ff
    enc = n_layer * (attn + ffn)
    dec = n_layer * (2 * attn + ffn)
    fwd_macs = enc + dec + d_model * vocab
    return 3 * 2 * fwd_macs


def flops_per_token(cfg, traffic):
    return transformer_train_flops_per_token(
        cfg["n_layer"], cfg["d_model"], cfg["d_inner_hid"], cfg["n_head"],
        cfg["d_key"], traffic["trg_len"], cfg["trg_vocab_size"])
