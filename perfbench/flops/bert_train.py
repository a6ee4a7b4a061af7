"""Matmul FLOPs per trained input token of the BERT encoder with its
masked-LM head: 2 FLOPs per multiply-add, training = 3 x forward.  A copy
of `bench.bert_train_flops_per_token` (perfbench/tests/test_peaks_flops.py
holds the two together)."""


def bert_train_flops_per_token(n_layer, d_model, d_ff, seq_len, vocab):
    attn = 4 * d_model * d_model + 2 * seq_len * d_model
    fwd_macs = n_layer * (attn + 2 * d_model * d_ff) + d_model * vocab
    return 3 * 2 * fwd_macs


def flops_per_token(cfg, traffic):
    return bert_train_flops_per_token(
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["intermediate_size"], traffic["seq_len"], cfg["vocab_size"])
