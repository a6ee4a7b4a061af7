"""Transformer (reference: python/paddle/fluid/tests/unittests/
transformer_model.py — multi_head_attention:44, positionwise_feed_forward,
pre/post_process_layer, encoder_layer, decoder_layer, transformer:396).

TPU-first: static padded sequences + additive attention bias (instead of the
reference's LoD-free padded path), bf16-friendly; the fused flash-attention
path lives in kernels/attention.py and is switched in via use_flash."""

from __future__ import annotations

import numpy as np

from .. import layers
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr


def multi_head_attention(
    queries,
    keys,
    values,
    attn_bias,
    d_key,
    d_value,
    d_model,
    n_head=1,
    dropout_rate=0.0,
    use_flash=False,
    use_ring=False,
    ring_causal=False,
    ring_axis="sp",
):
    """reference transformer_model.py:44.

    use_ring (context parallelism, self-attention only): the sequence axis
    shards over mesh axis `ring_axis` and K/V circulate via ppermute —
    attn_bias is ignored on this path (pad-free batches / pure-causal via
    ring_causal), see ops/fused_ops.py ring_attention."""
    is_self = keys is None and values is None
    keys = queries if keys is None else keys
    values = keys if values is None else values

    # stable param names: the Megatron TP rules (parallel/sharding.py
    # transformer_tp_rules) address these by regex
    from ..core.framework import unique_name

    if is_self and d_key == d_value and use_flash and not use_ring:
        # ONE op a flash self-attention site: the projections are XLA dots
        # that read and write the bthd kernels' [b, t, h, dh] straight (no
        # [b, t, 3hd] array to slice, no gradient to concatenate), and the
        # grad op reads q, k, v, the context and the logsumexp the forward
        # kept (kernels/attention.py flash_qkv_attention; PERF.md PR 30).
        # Parameter names and shapes are EXACTLY those of the fc + split
        # + fused_attention + fc branch below (the same unique_name draws,
        # the same packed [d_model, 3hd] / [hd, d_model] fc layouts), so
        # checkpoints load either way.
        from ..layers.contrib import fused_qkv_attention

        return fused_qkv_attention(
            queries, n_head=n_head, d_key=d_key, d_model=d_model,
            bias=attn_bias, scale=d_key**-0.5,
            dropout_rate=dropout_rate,
            qkv_param_attr=ParamAttr(name=unique_name("attn_qkv_w")),
            out_param_attr=ParamAttr(name=unique_name("attn_out_w")),
        )

    if is_self and d_key == d_value:
        # ONE fused [d_model, 3*h*d] projection for self-attention: a
        # single dot (fewer custom-call-adjacent layout boundaries —
        # PERF.md r04 lead 2: the split q/k/v dots paid ~1.2 GB/step of
        # relayout copies between dot-preferred and kernel layouts)
        qkv = layers.fc(input=queries, size=3 * d_key * n_head,
                        bias_attr=False, num_flatten_dims=2,
                        param_attr=ParamAttr(name=unique_name("attn_qkv_w")))
        q, k, v = layers.split(qkv, 3, dim=-1)
    else:
        q = layers.fc(input=queries, size=d_key * n_head, bias_attr=False,
                      num_flatten_dims=2,
                      param_attr=ParamAttr(name=unique_name("attn_q_w")))
        k = layers.fc(input=keys, size=d_key * n_head, bias_attr=False,
                      num_flatten_dims=2,
                      param_attr=ParamAttr(name=unique_name("attn_k_w")))
        v = layers.fc(input=values, size=d_value * n_head, bias_attr=False,
                      num_flatten_dims=2,
                      param_attr=ParamAttr(name=unique_name("attn_v_w")))

    def split_heads(x, d):
        b, t, _ = x.shape
        r = layers.reshape(x, [b, t, n_head, d])
        return layers.transpose(r, [0, 2, 1, 3])

    def to_bthd(x, d):
        b, t, _ = x.shape
        return layers.reshape(x, [b, t, n_head, d])

    def merge_and_project(ctx):
        """[b, t, h, d] context -> output projection (the shared tail of
        the transpose-free bthd paths: the reshape is a bitcast)."""
        b, t, h, d = ctx.shape
        ctx = layers.reshape(ctx, [b, t, h * d])
        return layers.fc(input=ctx, size=d_model, bias_attr=False,
                         num_flatten_dims=2,
                         param_attr=ParamAttr(name=unique_name("attn_out_w")))

    if use_flash and not use_ring:
        # transpose-free path: [b,t,h*d] -> [b,t,h,d] is a bitcast, the
        # kernel indexes heads via its grid, and the output reshapes
        # straight back — no split/merge-head transposes exist, so XLA
        # inserts no relayout copies at the custom-call boundary
        # (round-3 profile: ~5.5 GB/step of them on the [b,h,t,d] path)
        from ..layers.contrib import fused_attention

        # weights_dropout (in-kernel, reference semantics) is on at every
        # sequence length: the kernels draw mask bits from the TPU
        # hardware PRNG (kernels/attention.py _keep_tile_prng), which
        # removed the O(T²·H) hash-regeneration cost that made seq-256 a
        # −2.5 MFU-pt loss in r05 and forced a per-length selection hack
        ctx = fused_attention(
            to_bthd(q, d_key), to_bthd(k, d_key), to_bthd(v, d_value),
            attn_bias, scale=d_key**-0.5, dropout_rate=dropout_rate,
            fmt="bthd",
        )
        return merge_and_project(ctx)

    if use_ring:
        # context-parallel path on the same transpose-free convention:
        # the ring chunks reuse the single-device bthd whole-head block
        # specs (kernels/ring_attention.py) — CP re-introduces NO
        # split/merge-head transposes
        from ..layers.contrib import ring_attention

        ctx = ring_attention(
            to_bthd(q, d_key), to_bthd(k, d_key), to_bthd(v, d_value),
            scale=d_key**-0.5, causal=ring_causal, axis_name=ring_axis,
            fmt="bthd")
        return merge_and_project(ctx)

    q = split_heads(q, d_key)
    k = split_heads(k, d_key)
    v = split_heads(v, d_value)

    product = layers.matmul(q, k, transpose_y=True, alpha=d_key**-0.5)
    if attn_bias is not None:
        product = layers.elementwise_add(product, attn_bias)
    weights = layers.softmax(product)
    if dropout_rate:
        weights = layers.dropout(
            weights, dropout_prob=dropout_rate,
            dropout_implementation="upscale_in_train",
        )
    ctx = layers.matmul(weights, v)

    b, h, t, d = ctx.shape
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    ctx = layers.reshape(ctx, [b, t, h * d])
    return layers.fc(input=ctx, size=d_model, bias_attr=False,
                     num_flatten_dims=2,
                     param_attr=ParamAttr(name=unique_name("attn_out_w")))


def positionwise_feed_forward(x, d_inner_hid, d_hid):
    from ..core.framework import unique_name

    hidden = layers.fc(input=x, size=d_inner_hid, act="relu",
                       num_flatten_dims=2,
                       param_attr=ParamAttr(name=unique_name("ffn_in_w")),
                       bias_attr=ParamAttr(name=unique_name("ffn_in_b")))
    return layers.fc(input=hidden, size=d_hid, num_flatten_dims=2,
                     param_attr=ParamAttr(name=unique_name("ffn_out_w")),
                     bias_attr=ParamAttr(name=unique_name("ffn_out_b")))


def pre_post_process_layer(prev_out, out, process_cmd, dropout_rate=0.0):
    """reference transformer_model.py pre_post_process_layer: a=add, n=norm,
    d=dropout.

    A leading "da" (dropout then residual-add — the post-process pattern
    of every encoder/decoder sub-layer) lowers as ONE fused dropout-add
    op (layers.dropout_add -> kernels/dropout_epilogue.py) under
    FLAGS.fused_dropout_add: the keep-mask is generated in-kernel and
    regenerated in the backward, so it never exists in HBM.  With the
    flag off, or without a residual, the reference's separate
    dropout + elementwise_add ops are emitted unchanged."""
    from ..flags import FLAGS

    if (dropout_rate and prev_out is not None
            and process_cmd.startswith("da") and FLAGS.fused_dropout_add):
        out = layers.dropout_add(out, prev_out, dropout_rate)
        process_cmd = process_cmd[2:]
    for cmd in process_cmd:
        if cmd == "a":
            out = layers.elementwise_add(out, prev_out) if prev_out is not None else out
        elif cmd == "n":
            out = layers.layer_norm(
                out, begin_norm_axis=len(out.shape) - 1,
                param_attr=ParamAttr(initializer=None),
            )
        elif cmd == "d":
            if dropout_rate:
                out = layers.dropout(
                    out, dropout_prob=dropout_rate,
                    dropout_implementation="upscale_in_train",
                )
    return out


def prepare_encoder(
    src_word,
    src_pos,
    src_vocab_size,
    src_emb_dim,
    src_max_len,
    dropout_rate=0.0,
    word_emb_param_name=None,
    pos_enc_param_name=None,
):
    """Word + sinusoid position embedding (reference prepare_encoder)."""
    src_word_emb = layers.embedding(
        src_word,
        size=[src_vocab_size, src_emb_dim],
        param_attr=ParamAttr(
            name=word_emb_param_name,
            initializer=NormalInitializer(0.0, src_emb_dim**-0.5),
        ),
    )
    src_pos_enc = layers.embedding(
        src_pos,
        size=[src_max_len, src_emb_dim],
        param_attr=ParamAttr(
            name=pos_enc_param_name,
            initializer=NormalInitializer(0.0, src_emb_dim**-0.5),
            trainable=False,
        ),
    )
    src_pos_enc.stop_gradient = True
    enc_input = layers.elementwise_add(src_word_emb, src_pos_enc)
    if dropout_rate:
        enc_input = layers.dropout(
            enc_input, dropout_prob=dropout_rate,
            dropout_implementation="upscale_in_train",
        )
    return enc_input


def encoder_layer(enc_input, attn_bias, n_head, d_key, d_value, d_model,
                  d_inner_hid, dropout_rate=0.0, use_flash=False,
                  use_ring=False):
    attn_output = multi_head_attention(
        enc_input, None, None, attn_bias, d_key, d_value, d_model, n_head,
        dropout_rate, use_flash=use_flash, use_ring=use_ring,
    )
    attn_output = pre_post_process_layer(enc_input, attn_output, "dan",
                                         dropout_rate)
    ffd_output = positionwise_feed_forward(attn_output, d_inner_hid, d_model)
    return pre_post_process_layer(attn_output, ffd_output, "dan", dropout_rate)


def encoder(enc_input, attn_bias, n_layer, n_head, d_key, d_value, d_model,
            d_inner_hid, dropout_rate=0.0, use_flash=False, use_ring=False):
    for i in range(n_layer):
        enc_output = encoder_layer(
            enc_input, attn_bias, n_head, d_key, d_value, d_model,
            d_inner_hid, dropout_rate, use_flash=use_flash,
            use_ring=use_ring,
        )
        enc_input = enc_output
    return enc_output


def decoder_layer(dec_input, enc_output, slf_attn_bias, dec_enc_attn_bias,
                  n_head, d_key, d_value, d_model, d_inner_hid,
                  dropout_rate=0.0, use_flash=False, use_ring=False):
    slf_attn_output = multi_head_attention(
        dec_input, None, None, slf_attn_bias, d_key, d_value, d_model, n_head,
        dropout_rate, use_flash=use_flash, use_ring=use_ring,
        ring_causal=True,
    )
    slf_attn_output = pre_post_process_layer(dec_input, slf_attn_output, "dan",
                                             dropout_rate)
    enc_attn_output = multi_head_attention(
        slf_attn_output, enc_output, enc_output, dec_enc_attn_bias, d_key,
        d_value, d_model, n_head, dropout_rate, use_flash=use_flash,
    )
    enc_attn_output = pre_post_process_layer(
        slf_attn_output, enc_attn_output, "dan", dropout_rate
    )
    ffd_output = positionwise_feed_forward(enc_attn_output, d_inner_hid, d_model)
    return pre_post_process_layer(enc_attn_output, ffd_output, "dan", dropout_rate)


def decoder(dec_input, enc_output, dec_slf_attn_bias, dec_enc_attn_bias,
            n_layer, n_head, d_key, d_value, d_model, d_inner_hid,
            dropout_rate=0.0, use_flash=False, use_ring=False):
    for i in range(n_layer):
        dec_output = decoder_layer(
            dec_input, enc_output, dec_slf_attn_bias, dec_enc_attn_bias,
            n_head, d_key, d_value, d_model, d_inner_hid, dropout_rate,
            use_flash=use_flash, use_ring=use_ring,
        )
        dec_input = dec_output
    return dec_output


def transformer(
    src_vocab_size=10000,
    trg_vocab_size=10000,
    max_length=256,
    n_layer=6,
    n_head=8,
    d_key=64,
    d_value=64,
    d_model=512,
    d_inner_hid=2048,
    dropout_rate=0.1,
    batch_size=None,
    src_seq_len=None,
    trg_seq_len=None,
    use_flash=False,
    use_ring=False,
    device_biases=True,
):
    """Full encoder-decoder Transformer-base (reference
    transformer_model.py:396).  Declares padded-sequence data vars; returns
    (avg_cost, predict, feed_names).

    device_biases (TPU-first, default): attention biases are computed ON
    DEVICE inside the compiled step — padding masks from the word ids
    (pad id 0) and the causal mask as a program constant.  The reference
    feeds dense [b, n_head, t, t] bias tensors from the host
    (transformer_model.py prepare_batch_input), which costs O(b·h·t²)
    host->HBM bandwidth per step — at (b=32, h=8, t=256) that is ~200 MB
    per step, orders of magnitude more than the token ids themselves.
    Set device_biases=False for reference-parity feeding."""
    src_seq_len = src_seq_len or max_length
    trg_seq_len = trg_seq_len or max_length

    src_word = layers.data(name="src_word", shape=[src_seq_len, 1], dtype="int64")
    src_pos = layers.data(name="src_pos", shape=[src_seq_len, 1], dtype="int64")
    trg_word = layers.data(name="trg_word", shape=[trg_seq_len, 1], dtype="int64")
    trg_pos = layers.data(name="trg_pos", shape=[trg_seq_len, 1], dtype="int64")
    if device_biases:
        neg_inf = -1e9

        def pad_bias(word, t):
            # [b, t, 1] ids -> [b, 1, 1, t] additive bias (-inf at pad id 0)
            zero = layers.fill_constant([1], "int64", 0)
            is_pad = layers.cast(layers.equal(word, zero), "float32")
            bias = layers.scale(is_pad, scale=neg_inf)
            bias = layers.reshape(bias, [-1, 1, 1, t])
            bias.stop_gradient = True
            return bias

        src_pad = pad_bias(src_word, src_seq_len)
        # causal mask from the (already fed) position ids: bias[q, k] = -inf
        # where k_pos > q_pos — computed on device, no O(t^2) IR constant
        qpos = layers.reshape(trg_pos, [-1, trg_seq_len, 1])
        kpos = layers.reshape(trg_pos, [-1, 1, trg_seq_len])
        future = layers.cast(layers.less_than(qpos, kpos), "float32")
        causal = layers.reshape(
            layers.scale(future, scale=neg_inf),
            [-1, 1, trg_seq_len, trg_seq_len],
        )
        causal.stop_gradient = True
        src_slf_attn_bias = src_pad
        trg_slf_attn_bias = layers.elementwise_add(
            causal, pad_bias(trg_word, trg_seq_len)
        )
        trg_slf_attn_bias.stop_gradient = True
        trg_src_attn_bias = src_pad
    else:
        src_slf_attn_bias = layers.data(
            name="src_slf_attn_bias", shape=[n_head, src_seq_len, src_seq_len],
            dtype="float32",
        )
        trg_slf_attn_bias = layers.data(
            name="trg_slf_attn_bias", shape=[n_head, trg_seq_len, trg_seq_len],
            dtype="float32",
        )
        trg_src_attn_bias = layers.data(
            name="trg_src_attn_bias", shape=[n_head, trg_seq_len, src_seq_len],
            dtype="float32",
        )
    gold = layers.data(name="lbl_word", shape=[trg_seq_len, 1], dtype="int64")
    weights = layers.data(name="lbl_weight", shape=[trg_seq_len, 1], dtype="float32")

    enc_input = prepare_encoder(
        src_word, src_pos, src_vocab_size, d_model, max_length, dropout_rate,
        word_emb_param_name="src_word_emb_table",
        pos_enc_param_name="src_pos_enc_table",
    )
    enc_output = encoder(
        enc_input, src_slf_attn_bias, n_layer, n_head, d_key, d_value,
        d_model, d_inner_hid, dropout_rate, use_flash=use_flash,
        use_ring=use_ring,
    )

    dec_input = prepare_encoder(
        trg_word, trg_pos, trg_vocab_size, d_model, max_length, dropout_rate,
        word_emb_param_name="trg_word_emb_table",
        pos_enc_param_name="trg_pos_enc_table",
    )
    dec_output = decoder(
        dec_input, enc_output, trg_slf_attn_bias, trg_src_attn_bias,
        n_layer, n_head, d_key, d_value, d_model, d_inner_hid, dropout_rate,
        use_flash=use_flash, use_ring=use_ring,
    )

    predict = layers.fc(input=dec_output, size=trg_vocab_size,
                        num_flatten_dims=2,
                        param_attr=ParamAttr(name="predict_w"),
                        bias_attr=ParamAttr(name="predict_b"))
    b, t, v = predict.shape
    predict_2d = layers.reshape(predict, [-1, v])
    gold_2d = layers.reshape(gold, [-1, 1])
    cost = layers.softmax_with_cross_entropy(logits=predict_2d, label=gold_2d)
    w2d = layers.reshape(weights, [-1, 1])
    weighted_cost = layers.elementwise_mul(cost, w2d)
    sum_cost = layers.reduce_sum(weighted_cost)
    token_count = layers.reduce_sum(w2d)
    avg_cost = layers.elementwise_div(sum_cost, token_count)

    feed_names = ["src_word", "src_pos", "trg_word", "trg_pos",
                  "lbl_word", "lbl_weight"]
    if not device_biases:
        feed_names[4:4] = [
            "src_slf_attn_bias", "trg_slf_attn_bias", "trg_src_attn_bias"
        ]
    return avg_cost, predict, feed_names


def make_batch(batch_size, src_len, trg_len, n_head, src_vocab, trg_vocab,
               rng=None, device_biases=True):
    """Synthetic padded batch.  With device_biases (default) only token
    streams are produced — the model builds attention biases on device; pass
    device_biases=False for the reference-parity dense-bias feed."""
    rng = rng or np.random.RandomState(0)
    neg_inf = -1e9

    def pos(n, t):
        return np.tile(np.arange(t, dtype=np.int64)[None, :, None], (n, 1, 1))

    src_word = rng.randint(1, src_vocab, (batch_size, src_len, 1)).astype("int64")
    trg_word = rng.randint(1, trg_vocab, (batch_size, trg_len, 1)).astype("int64")
    lbl_word = rng.randint(1, trg_vocab, (batch_size, trg_len, 1)).astype("int64")
    lbl_weight = np.ones((batch_size, trg_len, 1), "float32")
    batch = {
        "src_word": src_word,
        "src_pos": pos(batch_size, src_len),
        "trg_word": trg_word,
        "trg_pos": pos(batch_size, trg_len),
        "lbl_word": lbl_word,
        "lbl_weight": lbl_weight,
    }
    if not device_biases:
        causal = np.triu(np.full((trg_len, trg_len), neg_inf, "float32"), 1)
        batch["src_slf_attn_bias"] = np.zeros(
            (batch_size, n_head, src_len, src_len), "float32")
        batch["trg_slf_attn_bias"] = np.tile(
            causal[None, None], (batch_size, n_head, 1, 1))
        batch["trg_src_attn_bias"] = np.zeros(
            (batch_size, n_head, trg_len, src_len), "float32")
    return batch


def _log_softmax(x, axis_dim):
    """logits [.., V] -> log-probs, numerically stable, built from layer ops."""
    m = layers.reduce_max(x, dim=axis_dim, keep_dim=True)
    shifted = layers.elementwise_sub(x, m)
    lse = layers.log(
        layers.reduce_sum(layers.exp(shifted), dim=axis_dim, keep_dim=True))
    return layers.elementwise_sub(shifted, lse)


# ---------------------------------------------------------------------------
# KV-cached decoding (paddle_tpu/generation): the single-token decoder step
# and the prefill/decode program pair.  Parameter names are drawn through
# the SAME unique_name sequences as transformer()/build_decoder, so a scope
# trained with the train net decodes through the cache directly.
# ---------------------------------------------------------------------------


def _cache_rows(n):
    """Ring-buffer row count rounded up to the flash-decode block quantum:
    the plan gate (kernels/decode_attention.py _decode_plan) wants
    max_t % block == 0 with 128 the smallest compiled block, so cache
    buffers are allocated in 128-row steps (the tail rows are dead weight
    the length mask never reads)."""
    return ((int(n) + 127) // 128) * 128


def _src_token_lengths(src_word, src_seq_len):
    """[b, Ts, 1] int64 ids -> [b] int32 length = 1 + LAST non-pad
    position (pad id 0).  Length-masking the cross cache to this value
    is equivalent to the reference's -1e9 pad bias for TRAILING padding
    (the framework's sequence contract); computing the trailing run —
    rather than counting zeros — means an out-of-contract mid-sequence 0
    can never truncate real tokens off the tail (it is attended like any
    token, where the bias route would mask that one position)."""
    zero = layers.fill_constant([1], "int64", 0)
    nonpad = layers.cast(layers.not_equal(src_word, zero), "float32")
    ones_t = layers.fill_constant([src_seq_len, 1], "float32", 1.0)
    pos1 = layers.reshape(layers.cumsum(ones_t, axis=0),
                          [1, src_seq_len, 1])  # 1..Ts
    last = layers.reduce_max(layers.elementwise_mul(nonpad, pos1),
                             dim=[1, 2])  # [b] = 1 + last non-pad pos
    return layers.cast(last, "int32")


def _flat_beam_parents(parent_idx, b, k):
    """[b, k] within-group beam parents -> [b, k] int64 FLAT lane indices
    (group offset b_idx*k + parent) — the kv_cache_reorder gather
    contract shared by the build_decoder While route and the per-token
    beam decode program."""
    ones_b = layers.fill_constant([b, 1], "float32", 1.0)
    offs = layers.scale(
        layers.elementwise_sub(layers.cumsum(ones_b, axis=0), ones_b),
        scale=float(k))
    return layers.cast(
        layers.elementwise_add(layers.cast(parent_idx, "float32"),
                               layers.expand(offs, [1, k])),
        "int64")


def _prefill_cross_cache(enc_output, cross_cache, n_layer, n_head, d_key,
                         d_value, active=None):
    """Project the (possibly beam-tiled) encoder output into per-layer
    cross-attention K/V and write them at row 0 of every sequence's cache
    slot.  Draws attn_k_w/attn_v_w in layer order — the same per-key
    unique_name sequence the in-loop recompute route draws."""
    from ..core.framework import unique_name

    # ts is the SOURCE length (what the encoder produced); the cache may
    # hold more rows (128-row allocation quantum) — the tail stays zero
    # and the cross length mask never reads it
    b, ts = cross_cache.batch, int(enc_output.shape[1])
    zero_pos = layers.fill_constant([b], "int32", 0)
    for i in range(n_layer):
        k = layers.fc(input=enc_output, size=d_key * n_head,
                      bias_attr=False, num_flatten_dims=2,
                      param_attr=ParamAttr(name=unique_name("attn_k_w")))
        v = layers.fc(input=enc_output, size=d_value * n_head,
                      bias_attr=False, num_flatten_dims=2,
                      param_attr=ParamAttr(name=unique_name("attn_v_w")))
        k4 = layers.reshape(k, [b, ts, n_head, d_key])
        v4 = layers.reshape(v, [b, ts, n_head, d_value])
        cross_cache.write(k4, v4, zero_pos, layer=i, active=active)


def cached_decoder_step(dec_input, self_cache, cross_cache, write_pos,
                        self_lens, cross_lens, n_layer, n_head, d_key,
                        d_value, d_model, d_inner_hid, active=None,
                        dropout_rate=0.0):
    """ONE decoder step over a single embedded token [b, 1, d_model]:
    per layer, project q/k/v, append k/v to the self cache at write_pos,
    single-query attention over the first self_lens rows, then cached
    cross-attention over cross_lens rows of the prefilled cross cache,
    then the feed-forward — the op-for-op cached counterpart of
    decoder_layer (same post-process "dan" chain, same parameter-name
    draws), minus the O(T²) full-prefix recompute.

    Under FLAGS_fused_decode_step (default on) each layer lowers to ONE
    fused_decode_step op instead of the ~10-op composition below —
    kernels/decode_step.py runs the whole layer per Pallas launch (or
    its numerically-identical XLA fallback off-contract/off-TPU).
    Parameter names, shapes and draw order are EXACTLY the flag-off
    path's, so checkpoints interop across the flag; flag-off graphs are
    op-for-op identical to the pre-fusion ones (asserted in
    tests/test_decode_step.py)."""
    from ..core.framework import unique_name
    from ..flags import FLAGS

    if FLAGS.fused_decode_step and dropout_rate == 0.0 and d_key == d_value:
        return _fused_cached_decoder_step(
            dec_input, self_cache, cross_cache, write_pos, self_lens,
            cross_lens, n_layer, n_head, d_key, d_value, d_model,
            d_inner_hid, active=active)

    x = dec_input
    b = x.shape[0]
    for i in range(n_layer):
        # self-attention against the growing cache
        qkv = layers.fc(input=x, size=3 * d_key * n_head, bias_attr=False,
                        num_flatten_dims=2,
                        param_attr=ParamAttr(name=unique_name("attn_qkv_w")))
        q, k, v = layers.split(qkv, 3, dim=-1)
        q4 = layers.reshape(q, [b, 1, n_head, d_key])
        k4 = layers.reshape(k, [b, 1, n_head, d_key])
        v4 = layers.reshape(v, [b, 1, n_head, d_value])
        self_cache.write(k4, v4, write_pos, layer=i, active=active)
        ctx = self_cache.attend(q4, self_lens, layer=i, scale=d_key**-0.5)
        attn_out = layers.fc(
            input=layers.reshape(ctx, [b, 1, n_head * d_value]),
            size=d_model, bias_attr=False, num_flatten_dims=2,
            param_attr=ParamAttr(name=unique_name("attn_out_w")))
        x = pre_post_process_layer(x, attn_out, "dan", dropout_rate)
        # cross-attention against the prefilled encoder K/V
        cq = layers.fc(input=x, size=d_key * n_head, bias_attr=False,
                       num_flatten_dims=2,
                       param_attr=ParamAttr(name=unique_name("attn_q_w")))
        cq4 = layers.reshape(cq, [b, 1, n_head, d_key])
        cctx = cross_cache.attend(cq4, cross_lens, layer=i,
                                  scale=d_key**-0.5)
        cross_out = layers.fc(
            input=layers.reshape(cctx, [b, 1, n_head * d_value]),
            size=d_model, bias_attr=False, num_flatten_dims=2,
            param_attr=ParamAttr(name=unique_name("attn_out_w")))
        x = pre_post_process_layer(x, cross_out, "dan", dropout_rate)
        ffd = positionwise_feed_forward(x, d_inner_hid, d_model)
        x = pre_post_process_layer(x, ffd, "dan", dropout_rate)
    return x


def _fused_cached_decoder_step(dec_input, self_cache, cross_cache,
                               write_pos, self_lens, cross_lens, n_layer,
                               n_head, d_key, d_value, d_model,
                               d_inner_hid, active=None):
    """The FLAGS_fused_decode_step lowering of cached_decoder_step: one
    fused_decode_step op per layer (ops/generation_ops.py ->
    kernels/decode_step.py).  Parameters are created through the SAME
    LayerHelper recipes and unique_name draws as the composition —
    attn_qkv_w, attn_out_w, layer_norm, attn_q_w, attn_out_w,
    layer_norm, ffn_in_w/b, ffn_out_w/b, layer_norm per layer — so a
    scope trained with `transformer(...)` runs either path and the
    flag-off graph's names never shift."""
    from ..core.framework import unique_name
    from ..initializer import ConstantInitializer
    from ..layer_helper import LayerHelper

    x = dec_input
    dtype = x.dtype

    def fc_param(key, shape):
        helper = LayerHelper(
            "fc", param_attr=ParamAttr(name=unique_name(key)))
        return helper.create_parameter(helper.param_attr(), shape=shape,
                                       dtype=dtype)

    def fc_bias(key, shape):
        helper = LayerHelper(
            "fc", bias_attr=ParamAttr(name=unique_name(key)))
        return helper.create_parameter(helper.bias_attr(), shape=shape,
                                       dtype=dtype, is_bias=True)

    def ln_params():
        helper = LayerHelper("layer_norm",
                             param_attr=ParamAttr(initializer=None))
        scale = helper.create_parameter(
            helper.param_attr(), shape=[d_model], dtype=dtype,
            default_initializer=ConstantInitializer(1.0))
        bias = helper.create_parameter(
            helper.bias_attr(), shape=[d_model], dtype=dtype,
            is_bias=True)
        return scale, bias

    cache_k, cache_v, _ = self_cache.vars_in()
    cross_k, cross_v, _ = cross_cache.vars_in()
    # paged caches (FLAGS_paged_kv_cache) route to the paged op form,
    # which adds the graph-read-only block tables to the inputs — the
    # weight draws and attrs are identical, so flag-on fused/unfused
    # programs stay numerically interchangeable
    paged = hasattr(self_cache, "table_in")
    step_op = "fused_decode_step_paged" if paged else "fused_decode_step"
    for i in range(n_layer):
        w_qkv = fc_param("attn_qkv_w", [d_model, 3 * d_key * n_head])
        w_out = fc_param("attn_out_w", [n_head * d_value, d_model])
        ln1_s, ln1_b = ln_params()
        w_cq = fc_param("attn_q_w", [d_model, d_key * n_head])
        w_cout = fc_param("attn_out_w", [n_head * d_value, d_model])
        ln2_s, ln2_b = ln_params()
        ffn_iw = fc_param("ffn_in_w", [d_model, d_inner_hid])
        ffn_ib = fc_bias("ffn_in_b", [d_inner_hid])
        ffn_ow = fc_param("ffn_out_w", [d_inner_hid, d_model])
        ffn_ob = fc_bias("ffn_out_b", [d_model])
        ln3_s, ln3_b = ln_params()

        helper = LayerHelper(step_op)
        out = helper.create_variable_for_type_inference(dtype)
        inputs = {
            "X": [x], "WQkv": [w_qkv], "WOut": [w_out],
            "Ln1Scale": [ln1_s], "Ln1Bias": [ln1_b], "WCq": [w_cq],
            "WCOut": [w_cout], "Ln2Scale": [ln2_s], "Ln2Bias": [ln2_b],
            "FfnInW": [ffn_iw], "FfnInB": [ffn_ib], "FfnOutW": [ffn_ow],
            "FfnOutB": [ffn_ob], "Ln3Scale": [ln3_s], "Ln3Bias": [ln3_b],
            "CacheK": [cache_k], "CacheV": [cache_v],
            "CrossK": [cross_k], "CrossV": [cross_v],
            "Pos": [write_pos], "Lengths": [self_lens],
            "CrossLengths": [cross_lens],
        }
        if paged:
            inputs["SelfTable"] = [self_cache.table_in()]
            inputs["CrossTable"] = [cross_cache.table_in()]
        if active is not None:
            inputs["Active"] = [active]
        # cache outputs carry the SAME var objects — the persistable
        # read-then-write the executor donates (kv_cache_update contract
        # verbatim)
        helper.append_op(
            step_op, inputs=inputs,
            outputs={"Out": [out], "CacheKOut": [cache_k],
                     "CacheVOut": [cache_v]},
            attrs={"layer": i, "n_head": n_head, "scale": d_key ** -0.5,
                   "epsilon": 1e-5})
        out.shape = list(x.shape)
        x = out
    return x


def build_decoder(
    src_vocab_size=10000,
    trg_vocab_size=10000,
    max_length=256,
    n_layer=6,
    n_head=8,
    d_key=64,
    d_value=64,
    d_model=512,
    d_inner_hid=2048,
    batch_size=4,
    src_seq_len=None,
    max_out_len=16,
    beam_size=4,
    bos_id=0,
    eos_id=1,
    use_flash=False,
):
    """Beam-search inference net (reference:
    tests/book/test_machine_translation.py decode + layers.beam_search
    nn.py:3833).  Shares parameter names with `transformer(...)` so a scope
    trained with the train net decodes directly.

    TPU-first shape: beams are a static [batch, beam] lane; the While loop
    compiles to one XLA while_loop.  The decode step inside the loop is
    chosen by FLAGS.kv_cache:

      * on (default): per-layer K/V ring buffers ride the loop carry
        (cached_decoder_step + ops/generation_ops.py) — each step embeds
        ONE token, appends its K/V at position t, reorders the cache by
        the beam parents, and attends the single query row over the
        t+1-row prefix (O(T) per token; kernels/decode_attention.py).
      * off: the legacy full-prefix recompute — every step re-runs the
        causal decoder over the static [T+1]-padded prefix (O(T²)
        recompute per token).  Kept as the parity oracle: both routes are
        output-identical (asserted in tests/test_generation.py).

    The While-free per-token generation drivers (one Executor.run per
    token, serving-grade) live in paddle_tpu/generation — this builder is
    the single-program book-test/batch path.

    Returns (sentence_ids [b, beam, T], sentence_scores [b, beam],
    feed_names).
    """
    from ..flags import FLAGS
    src_seq_len = src_seq_len or max_length
    if max_length < max_out_len + 1 or max_length < src_seq_len:
        # same position-table NaN footgun as build_generation_programs
        raise ValueError(
            f"max_length={max_length} position table is smaller than the "
            f"decode buffer (max_out_len+1={max_out_len + 1}) or the "
            f"source length ({src_seq_len})")
    t_buf = max_out_len + 1  # position 0 is BOS
    b, k = batch_size, beam_size
    bk = b * k

    src_word = layers.data(name="src_word", shape=[src_seq_len, 1],
                           dtype="int64")
    src_pos = layers.data(name="src_pos", shape=[src_seq_len, 1],
                          dtype="int64")

    # ---- encoder (runs once, before the loop) ---------------------------
    neg_inf = -1e9
    zero = layers.fill_constant([1], "int64", 0)
    is_pad = layers.cast(layers.equal(src_word, zero), "float32")
    src_bias = layers.reshape(layers.scale(is_pad, scale=neg_inf),
                              [-1, 1, 1, src_seq_len])
    src_bias.stop_gradient = True
    enc_input = prepare_encoder(
        src_word, src_pos, src_vocab_size, d_model, max_length,
        word_emb_param_name="src_word_emb_table",
        pos_enc_param_name="src_pos_enc_table",
    )
    enc_output = encoder(
        enc_input, src_bias, n_layer, n_head, d_key, d_value, d_model,
        d_inner_hid, use_flash=use_flash,
    )
    # tile per beam: [b, Ts, d] -> [b*k, Ts, d] (beam-major within batch)
    enc_output = layers.reshape(
        layers.expand(
            layers.reshape(enc_output, [b, 1, src_seq_len, d_model]),
            [1, k, 1, 1],
        ),
        [bk, src_seq_len, d_model],
    )
    # ---- loop state -----------------------------------------------------
    t = layers.fill_constant([1], "int64", 0)
    limit = layers.fill_constant([1], "int64", max_out_len)
    cond = layers.less_than(t, limit)

    pre_ids = layers.fill_constant([b, k], "int64", bos_id)
    beam0 = layers.one_hot(layers.fill_constant([1], "int64", 0), k)  # [k]
    pre_scores = layers.expand(
        layers.reshape(layers.scale(beam0, scale=1e9, bias=neg_inf),
                       [1, k]),
        [b, 1],
    )  # beam 0 -> 0, others -> -1e9

    ids_arr = layers.create_array("int64", element_shape=[b, k],
                                  capacity=max_out_len)
    parents_arr = layers.create_array("int64", element_shape=[b, k],
                                      capacity=max_out_len)

    if FLAGS.kv_cache:
        # ---- KV-cached route (default): the caches ride the loop carry
        from ..core import framework as fw
        from ..generation.kv_cache import KVCache

        def _zeroed_cache(prefix_name, max_t):
            cache = KVCache(prefix_name, n_layer, bk, max_t, n_head, d_key)
            kv_vars = cache.vars_in(persistable=False)
            for var in kv_vars[:2]:
                zeros = layers.fill_constant(list(cache.shape), "float32",
                                             0.0)
                layers.assign(zeros, output=var)
            return cache

        uid = fw.unique_name("dec_cache")
        self_cache = _zeroed_cache(f"{uid}_self", _cache_rows(t_buf))
        cross_cache = _zeroed_cache(f"{uid}_cross",
                                    _cache_rows(src_seq_len))
        _prefill_cross_cache(enc_output, cross_cache, n_layer, n_head,
                             d_key, d_value)
        # cross length = true (untiled-then-tiled) source token count
        src_lens = _src_token_lengths(src_word, src_seq_len)  # [b] int32
        cross_lens = layers.reshape(
            layers.expand(layers.reshape(src_lens, [b, 1]), [1, k]), [bk])
        # flat beam-parent carry, identity at step 0 (slot i -> slot i)
        ones_bk = layers.fill_constant([bk, 1], "float32", 1.0)
        identity = layers.cast(
            layers.reshape(
                layers.elementwise_sub(layers.cumsum(ones_bk, axis=0),
                                       ones_bk),
                [bk]),
            "int64")
        pre_parents = layers.fill_constant([bk], "int64", 0)
        layers.assign(identity, output=pre_parents)

        w = layers.While(cond)
        with w.block():
            # continue from the parent beam's prefix: gather the cache
            # slots the selected tokens actually extended
            self_cache.reorder(pre_parents)
            write_pos = layers.cast(
                layers.reshape(layers.expand(layers.reshape(t, [1, 1]),
                                             [bk, 1]), [bk]),
                "int32")
            att_len = layers.elementwise_add(
                write_pos, layers.fill_constant([bk], "int32", 1))
            tpos_ids = layers.expand(layers.reshape(t, [1, 1, 1]),
                                     [bk, 1, 1])
            dec_input = prepare_encoder(
                layers.reshape(pre_ids, [bk, 1, 1]), tpos_ids,
                trg_vocab_size, d_model, max_length,
                word_emb_param_name="trg_word_emb_table",
                pos_enc_param_name="trg_pos_enc_table",
            )
            dec_output = cached_decoder_step(
                dec_input, self_cache, cross_cache, write_pos, att_len,
                cross_lens, n_layer, n_head, d_key, d_value, d_model,
                d_inner_hid)
            logits = layers.fc(input=dec_output, size=trg_vocab_size,
                               num_flatten_dims=2,
                               param_attr=ParamAttr(name="predict_w"),
                               bias_attr=ParamAttr(name="predict_b"))
            step_logits = layers.reshape(logits, [b, k, trg_vocab_size])
            log_probs = _log_softmax(step_logits, axis_dim=2)

            sel_ids, sel_scores, parent_idx = layers.beam_search(
                pre_ids, pre_scores, None, log_probs, beam_size=k,
                end_id=eos_id)

            # flat parents for the NEXT step's cache gather
            layers.assign(
                layers.reshape(_flat_beam_parents(parent_idx, b, k),
                               [bk]),
                output=pre_parents)

            layers.array_write(sel_ids, t, array=ids_arr)
            layers.array_write(parent_idx, t, array=parents_arr)
            layers.assign(sel_ids, output=pre_ids)
            layers.assign(sel_scores, output=pre_scores)
            layers.increment(t, value=1.0, in_place=True)
            layers.less_than(t, limit, cond=cond)

        sent_ids, sent_scores = layers.beam_search_decode(
            ids_arr, pre_scores, beam_size=k, end_id=eos_id,
            parents=parents_arr)
        return sent_ids, sent_scores, ["src_word", "src_pos"]

    # ---- flag-off route: full-prefix recompute (the parity oracle) ------
    # causal self-attention bias over the prefix buffer: [1, 1, T, T]
    ones_t = layers.fill_constant([t_buf, 1], "float32", 1.0)
    arange_t = layers.elementwise_sub(
        layers.cumsum(ones_t, axis=0), ones_t)  # [T,1] = 0..T-1
    qpos = layers.reshape(arange_t, [1, t_buf, 1])
    kpos = layers.reshape(arange_t, [1, 1, t_buf])
    future = layers.cast(layers.less_than(qpos, kpos), "float32")
    causal_bias = layers.reshape(layers.scale(future, scale=neg_inf),
                                 [1, 1, t_buf, t_buf])
    causal_bias.stop_gradient = True

    trg_pos_ids = layers.cast(
        layers.expand(layers.reshape(arange_t, [1, t_buf, 1]), [bk, 1, 1]),
        "int64")

    # beam-tiled source pad bias (the cached route masks the cross cache
    # by true source lengths instead)
    src_bias_bk = layers.reshape(
        layers.expand(layers.reshape(src_bias, [b, 1, 1, 1, src_seq_len]),
                      [1, k, 1, 1, 1]),
        [bk, 1, 1, src_seq_len],
    )

    prefix = layers.fill_constant([b, k, t_buf], "int64", bos_id)

    w = layers.While(cond)
    with w.block():
        dec_input = prepare_encoder(
            layers.reshape(prefix, [bk, t_buf, 1]), trg_pos_ids,
            trg_vocab_size, d_model, max_length,
            word_emb_param_name="trg_word_emb_table",
            pos_enc_param_name="trg_pos_enc_table",
        )
        dec_output = decoder(
            dec_input, enc_output, causal_bias, src_bias_bk,
            n_layer, n_head, d_key, d_value, d_model, d_inner_hid,
            use_flash=use_flash,
        )
        logits = layers.fc(input=dec_output, size=trg_vocab_size,
                           num_flatten_dims=2,
                           param_attr=ParamAttr(name="predict_w"),
                           bias_attr=ParamAttr(name="predict_b"))
        # logits at position t: [bk, T, V] -> [bk, V]
        t_idx = layers.cast(
            layers.expand(layers.reshape(t, [1, 1, 1]),
                          [bk, 1, trg_vocab_size]),
            "int64")
        step_logits = layers.reshape(
            layers.take_along_axis(logits, t_idx, axis=1),
            [b, k, trg_vocab_size])
        log_probs = _log_softmax(step_logits, axis_dim=2)

        sel_ids, sel_scores, parent_idx = layers.beam_search(
            pre_ids, pre_scores, None, log_probs, beam_size=k,
            end_id=eos_id)

        # reorder prefixes by parent beam, write new token at position t+1
        par3 = layers.expand(layers.reshape(parent_idx, [b, k, 1]),
                             [1, 1, t_buf])
        prefix_re = layers.take_along_axis(prefix, par3, axis=1)
        tpos = layers.increment(layers.assign(t), value=1.0, in_place=False)
        oh = layers.one_hot(tpos, t_buf)  # [T] f32, 1 at position t+1
        keep = layers.elementwise_mul(
            layers.cast(prefix_re, "float32"),
            layers.scale(oh, scale=-1.0, bias=1.0))
        put = layers.elementwise_mul(
            layers.cast(layers.reshape(sel_ids, [b, k, 1]), "float32"), oh)
        new_prefix = layers.cast(layers.elementwise_add(keep, put), "int64")

        layers.array_write(sel_ids, t, array=ids_arr)
        layers.array_write(parent_idx, t, array=parents_arr)
        layers.assign(new_prefix, output=prefix)
        layers.assign(sel_ids, output=pre_ids)
        layers.assign(sel_scores, output=pre_scores)
        layers.increment(t, value=1.0, in_place=True)
        layers.less_than(t, limit, cond=cond)

    sent_ids, sent_scores = layers.beam_search_decode(
        ids_arr, pre_scores, beam_size=k, end_id=eos_id,
        parents=parents_arr)
    return sent_ids, sent_scores, ["src_word", "src_pos"]


# ---------------------------------------------------------------------------
# Generation program pair: the While-FREE serving path.  One compiled
# prefill program (encoder -> cross cache) + ONE compiled per-token decode
# program stepped by the host (paddle_tpu/generation/sampler.py drives it,
# paddle_tpu/serving/generation.py continuous-batches it).
# ---------------------------------------------------------------------------


class GenerationPrograms:
    """Program pair + cache contract handed to the generation drivers."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def build_generation_programs(
    src_vocab_size=10000,
    trg_vocab_size=10000,
    max_length=256,
    n_layer=6,
    n_head=8,
    d_key=64,
    d_value=64,
    d_model=512,
    d_inner_hid=2048,
    batch_size=4,
    src_seq_len=None,
    max_out_len=16,
    bos_id=0,
    eos_id=1,
    use_flash=False,
    beam_size=None,
    strategy="greedy",
    temperature=1.0,
    top_k=0,
    cache_prefix="gen",
    kv_cache=None,
):
    """Build the (prefill, decode[, hyps]) program set for autoregressive
    generation.  Parameter names are drawn through the same unique_name
    sequences as `transformer(...)` (fresh generator inside), so a scope
    trained with the train net generates directly.

    kv_cache=None follows FLAGS.kv_cache:
      * cached (default): prefill runs the encoder once and writes the
        per-layer cross-attention K/V into the `<prefix>_cross` ring
        buffer; the decode program embeds ONE token, appends its K/V to
        the `<prefix>_self` cache at the per-sequence length counters,
        and attends a single query row (decode_attention) — O(T) per
        token, all cache state scope-resident + donated, compile key
        length-independent.
      * recompute (flag-off parity oracle, non-beam only): prefill
        stores enc_output + the source pad bias; the decode program
        re-runs the full causal decoder over the host-maintained
        [max_out_len+1]-token prefix and samples at position t — O(T²)
        per token, token-identical outputs.

    Both decode programs feed fixed shapes every step, so the executor
    compiles each exactly once (asserted in tests/test_generation.py and
    bench.py --model decode).

    beam_size=None builds the sampling pair ("greedy"/"sample" via
    sample_token); an int builds the beam pair: the decode program runs
    one cached step + a beam_search op + the kv_cache_reorder parent
    gather, and `hyps` backtracks the stacked steps via
    beam_search_decode.
    """
    from ..core import framework as fw
    from ..flags import FLAGS
    from ..generation.kv_cache import KVCache, PagedKVCache

    if kv_cache is None:
        kv_cache = FLAGS.kv_cache
    src_seq_len = src_seq_len or max_length
    if max_length < max_out_len + 1 or max_length < src_seq_len:
        # position-table rows gate BOTH streams; an out-of-range lookup
        # NaN-fills (jnp.take) and one NaN poisons every softmax row
        # through the additive masks — fail loudly at build time instead
        raise ValueError(
            f"max_length={max_length} position table is smaller than the "
            f"decode buffer (max_out_len+1={max_out_len + 1}) or the "
            f"source length ({src_seq_len})")
    b = batch_size
    k = beam_size or 1
    lanes = b * k
    if beam_size is not None and not kv_cache:
        raise ValueError(
            "build_generation_programs: the beam pair requires the "
            "KV-cache route (FLAGS_kv_cache); the flag-off recompute "
            "oracle for beams is models/transformer.py build_decoder")

    t_buf = max_out_len + 1  # position 0 is BOS
    prefill = fw.Program()
    decode = fw.Program()
    hyps = fw.Program() if beam_size is not None else None
    startup = fw.Program()

    # FLAGS_paged_kv_cache swaps the ring buffers for block pools +
    # per-slot tables; the op surface (write/attend/reorder) is drawn
    # from the cache object, so the rest of the build is layout-blind.
    # Flag OFF keeps the ring construction byte-for-byte (parameter and
    # state names unchanged — checkpoints interop).
    paged = bool(kv_cache and FLAGS.paged_kv_cache)
    if paged:
        self_cache = PagedKVCache(
            f"{cache_prefix}_self", n_layer, lanes, _cache_rows(t_buf),
            n_head, d_key, block_t=int(FLAGS.kv_block_t),
            num_blocks=int(FLAGS.kv_cache_blocks))
        cross_cache = PagedKVCache(
            f"{cache_prefix}_cross", n_layer, lanes,
            _cache_rows(src_seq_len), n_head, d_key,
            block_t=int(FLAGS.kv_block_t),
            num_blocks=int(FLAGS.kv_cache_blocks))
    else:
        self_cache = KVCache(f"{cache_prefix}_self", n_layer, lanes,
                             _cache_rows(t_buf), n_head, d_key)
        cross_cache = KVCache(f"{cache_prefix}_cross", n_layer, lanes,
                              _cache_rows(src_seq_len), n_head, d_key)
    enc_out_name = f"{cache_prefix}_enc_out"
    src_bias_name = f"{cache_prefix}_src_bias"
    last_tok_name = f"{cache_prefix}_last_tok"
    finished_name = f"{cache_prefix}_finished"
    # greedy self-feed (FLAGS_fused_decode_step tail trim): the decode
    # program reads its own last sampled token from scope state instead
    # of a host feed, and latches eos in-graph exactly like the host
    # loop's masking — the per-token host round-trip of the argmax
    # disappears.  Sampled/beam paths are unchanged (they need the host
    # token stream / beam state anyway).
    use_self_feed = bool(kv_cache and beam_size is None
                         and strategy == "greedy"
                         and FLAGS.fused_decode_step)

    def state_var(name, shape, dtype):
        return fw.default_main_program().global_block().create_var(
            name=name, shape=list(shape), dtype=dtype,
            persistable=True, stop_gradient=True)

    def aux_var(name, shape):
        return state_var(name, shape, "float32")

    with fw.guard_unique_name():
        # ---- prefill ----------------------------------------------------
        with fw.program_guard(prefill, startup):
            src_word = layers.data(name="src_word",
                                   shape=[src_seq_len, 1], dtype="int64")
            src_pos = layers.data(name="src_pos", shape=[src_seq_len, 1],
                                  dtype="int64")
            active = (layers.data(name="gen_active", shape=[1],
                                  dtype="float32") if kv_cache else None)
            neg_inf = -1e9
            zero = layers.fill_constant([1], "int64", 0)
            is_pad = layers.cast(layers.equal(src_word, zero), "float32")
            src_bias = layers.reshape(
                layers.scale(is_pad, scale=neg_inf),
                [-1, 1, 1, src_seq_len])
            src_bias.stop_gradient = True
            enc_input = prepare_encoder(
                src_word, src_pos, src_vocab_size, d_model, max_length,
                word_emb_param_name="src_word_emb_table",
                pos_enc_param_name="src_pos_enc_table",
            )
            enc_output = encoder(
                enc_input, src_bias, n_layer, n_head, d_key, d_value,
                d_model, d_inner_hid, use_flash=use_flash,
            )
            src_lens = _src_token_lengths(src_word, src_seq_len)  # [b]
            if k > 1:  # tile per beam (beam-major within batch)
                enc_output = layers.reshape(
                    layers.expand(
                        layers.reshape(enc_output,
                                       [b, 1, src_seq_len, d_model]),
                        [1, k, 1, 1]),
                    [lanes, src_seq_len, d_model])
                src_lens = layers.reshape(
                    layers.expand(layers.reshape(src_lens, [b, 1]),
                                  [1, k]), [lanes])
            if kv_cache:
                if k > 1:
                    active_l = layers.reshape(
                        layers.expand(layers.reshape(active, [b, 1]),
                                      [1, k]), [lanes])
                else:
                    active_l = layers.reshape(active, [lanes])
                a32 = layers.cast(active_l, "int32")
                inv = layers.elementwise_sub(
                    layers.fill_constant([lanes], "int32", 1), a32)
                _prefill_cross_cache(enc_output, cross_cache, n_layer,
                                     n_head, d_key, d_value, active=a32)
                _, _, cross_len = cross_cache.vars_in()
                _, _, self_len = self_cache.vars_in()
                # joined sequences: cross len = true source length,
                # self len resets to 0; others keep their counters
                layers.assign(
                    layers.elementwise_add(
                        layers.elementwise_mul(a32, src_lens),
                        layers.elementwise_mul(inv, cross_len)),
                    output=cross_len)
                layers.assign(layers.elementwise_mul(inv, self_len),
                              output=self_len)
                if use_self_feed:
                    # self-feed state: joining lanes restart from BOS
                    # with a cleared finished latch; the rest keep their
                    # in-flight token (continuous batching's late joins)
                    last_tok = state_var(last_tok_name, (lanes, 1),
                                         "int64")
                    fin = state_var(finished_name, (lanes,), "int32")
                    a64 = layers.cast(a32, "int64")
                    inv64 = layers.cast(inv, "int64")
                    bos_c = layers.fill_constant([lanes], "int64",
                                                 bos_id)
                    layers.assign(
                        layers.reshape(
                            layers.elementwise_add(
                                layers.elementwise_mul(a64, bos_c),
                                layers.elementwise_mul(
                                    inv64,
                                    layers.reshape(last_tok, [lanes]))),
                            [lanes, 1]),
                        output=last_tok)
                    layers.assign(layers.elementwise_mul(inv, fin),
                                  output=fin)
            else:
                layers.assign(enc_output,
                              output=aux_var(enc_out_name,
                                             (lanes, src_seq_len,
                                              d_model)))
                layers.assign(src_bias,
                              output=aux_var(src_bias_name,
                                             (lanes, 1, 1, src_seq_len)))
            prefill_fetch = [src_lens.name]

        # ---- decode -----------------------------------------------------
        with fw.program_guard(decode, startup):
            if beam_size is None:
                if use_self_feed:
                    # scope-resident token state (read-then-written, so
                    # the executor donates it like the cache counters)
                    token = state_var(last_tok_name, (lanes, 1), "int64")
                    fin = state_var(finished_name, (lanes,), "int32")
                else:
                    token = layers.data(name="gen_token", shape=[1],
                                        dtype="int64")
                dactive = layers.data(name="gen_active", shape=[1],
                                      dtype="float32")
                if kv_cache:
                    _, _, self_len = self_cache.vars_in()
                    _, _, cross_len = cross_cache.vars_in()
                    da32 = layers.cast(layers.reshape(dactive, [lanes]),
                                       "int32")
                    att_len = layers.elementwise_add(self_len, da32)
                    pos_ids = layers.cast(
                        layers.reshape(self_len, [lanes, 1, 1]), "int64")
                    dec_input = prepare_encoder(
                        layers.reshape(token, [lanes, 1, 1]), pos_ids,
                        trg_vocab_size, d_model, max_length,
                        word_emb_param_name="trg_word_emb_table",
                        pos_enc_param_name="trg_pos_enc_table",
                    )
                    dec_output = cached_decoder_step(
                        dec_input, self_cache, cross_cache,
                        write_pos=self_len, self_lens=att_len,
                        cross_lens=cross_len, n_layer=n_layer,
                        n_head=n_head, d_key=d_key, d_value=d_value,
                        d_model=d_model, d_inner_hid=d_inner_hid,
                        active=da32)
                    logits = layers.fc(
                        input=dec_output, size=trg_vocab_size,
                        num_flatten_dims=2,
                        param_attr=ParamAttr(name="predict_w"),
                        bias_attr=ParamAttr(name="predict_b"))
                    next_tok = layers.sample_token(
                        layers.reshape(logits, [lanes, trg_vocab_size]),
                        strategy=strategy, temperature=temperature,
                        top_k=top_k)
                    if use_self_feed:
                        # in-graph eos latch — the exact host masking of
                        # GenerationSession.generate: finished lanes
                        # keep emitting (and self-feeding) eos, the
                        # latch ORs in fresh eos hits.  The masked token
                        # both writes the self-feed state and is the
                        # fetch, so host and device streams stay
                        # bit-identical.
                        eos_c = layers.fill_constant([lanes, 1], "int64",
                                                     eos_id)
                        one_c = layers.fill_constant([lanes, 1], "int64",
                                                     1)
                        fin64 = layers.cast(
                            layers.reshape(fin, [lanes, 1]), "int64")
                        not_fin = layers.elementwise_sub(one_c, fin64)
                        masked = layers.elementwise_add(
                            layers.elementwise_mul(fin64, eos_c),
                            layers.elementwise_mul(not_fin, next_tok))
                        is_eos = layers.reshape(
                            layers.cast(layers.equal(masked, eos_c),
                                        "int32"), [lanes])
                        layers.assign(
                            layers.elementwise_sub(
                                layers.elementwise_add(fin, is_eos),
                                layers.elementwise_mul(fin, is_eos)),
                            output=fin)
                        layers.assign(masked, output=token)
                        next_tok = masked
                    # advance the counters of the stepped sequences LAST
                    # (every read above wants the pre-step lengths)
                    layers.assign(att_len, output=self_len)
                else:
                    # full-prefix recompute oracle: the host maintains
                    # the [t_buf] prefix and feeds the step index
                    prefix = layers.data(name="gen_prefix",
                                         shape=[t_buf, 1], dtype="int64")
                    t_step = layers.data(name="gen_t", shape=[1],
                                         dtype="int64")
                    neg_inf = -1e9
                    ones_t = layers.fill_constant([t_buf, 1], "float32",
                                                  1.0)
                    arange_t = layers.elementwise_sub(
                        layers.cumsum(ones_t, axis=0), ones_t)
                    qpos = layers.reshape(arange_t, [1, t_buf, 1])
                    kpos = layers.reshape(arange_t, [1, 1, t_buf])
                    future = layers.cast(layers.less_than(qpos, kpos),
                                         "float32")
                    causal_bias = layers.reshape(
                        layers.scale(future, scale=neg_inf),
                        [1, 1, t_buf, t_buf])
                    causal_bias.stop_gradient = True
                    trg_pos_ids = layers.cast(
                        layers.expand(
                            layers.reshape(arange_t, [1, t_buf, 1]),
                            [lanes, 1, 1]),
                        "int64")
                    dec_input = prepare_encoder(
                        prefix, trg_pos_ids, trg_vocab_size, d_model,
                        max_length,
                        word_emb_param_name="trg_word_emb_table",
                        pos_enc_param_name="trg_pos_enc_table",
                    )
                    enc_out_v = aux_var(enc_out_name,
                                        (lanes, src_seq_len, d_model))
                    src_bias_v = aux_var(src_bias_name,
                                         (lanes, 1, 1, src_seq_len))
                    dec_output = decoder(
                        dec_input, enc_out_v, causal_bias, src_bias_v,
                        n_layer, n_head, d_key, d_value, d_model,
                        d_inner_hid, use_flash=use_flash,
                    )
                    logits = layers.fc(
                        input=dec_output, size=trg_vocab_size,
                        num_flatten_dims=2,
                        param_attr=ParamAttr(name="predict_w"),
                        bias_attr=ParamAttr(name="predict_b"))
                    t_idx = layers.cast(
                        layers.expand(layers.reshape(t_step, [1, 1, 1]),
                                      [lanes, 1, trg_vocab_size]),
                        "int64")
                    step_logits = layers.reshape(
                        layers.take_along_axis(logits, t_idx, axis=1),
                        [lanes, trg_vocab_size])
                    next_tok = layers.sample_token(
                        step_logits, strategy=strategy,
                        temperature=temperature, top_k=top_k)
                decode_fetch = [next_tok.name]
            else:
                pre_ids = layers.data(name="gen_pre_ids", shape=[k],
                                      dtype="int64")
                pre_scores = layers.data(name="gen_pre_scores",
                                         shape=[k], dtype="float32")
                parents = layers.data(name="gen_parents", shape=[1],
                                      dtype="int64")
                _, _, self_len = self_cache.vars_in()
                _, _, cross_len = cross_cache.vars_in()
                flat_parents = layers.reshape(parents, [lanes])
                self_cache.reorder(flat_parents)
                ones_l = layers.fill_constant([lanes], "int32", 1)
                att_len = layers.elementwise_add(self_len, ones_l)
                pos_ids = layers.cast(
                    layers.reshape(self_len, [lanes, 1, 1]), "int64")
                dec_input = prepare_encoder(
                    layers.reshape(pre_ids, [lanes, 1, 1]), pos_ids,
                    trg_vocab_size, d_model, max_length,
                    word_emb_param_name="trg_word_emb_table",
                    pos_enc_param_name="trg_pos_enc_table",
                )
                dec_output = cached_decoder_step(
                    dec_input, self_cache, cross_cache,
                    write_pos=self_len, self_lens=att_len,
                    cross_lens=cross_len, n_layer=n_layer, n_head=n_head,
                    d_key=d_key, d_value=d_value, d_model=d_model,
                    d_inner_hid=d_inner_hid)
                logits = layers.fc(
                    input=dec_output, size=trg_vocab_size,
                    num_flatten_dims=2,
                    param_attr=ParamAttr(name="predict_w"),
                    bias_attr=ParamAttr(name="predict_b"))
                log_probs = _log_softmax(
                    layers.reshape(logits, [b, k, trg_vocab_size]),
                    axis_dim=2)
                sel_ids, sel_scores, parent_idx = layers.beam_search(
                    pre_ids, pre_scores, None, log_probs, beam_size=k,
                    end_id=eos_id)
                next_parents = _flat_beam_parents(parent_idx, b, k)
                layers.assign(att_len, output=self_len)
                decode_fetch = [sel_ids.name, sel_scores.name,
                                next_parents.name]

        # ---- hyps (beam backtrack) --------------------------------------
        if hyps is not None:
            with fw.program_guard(hyps, startup):
                ids_steps = layers.data(name="gen_steps_ids",
                                        shape=[b, k], dtype="int64")
                parent_steps = layers.data(name="gen_steps_parents",
                                           shape=[b, k], dtype="int64")
                final_scores = layers.data(name="gen_final_scores",
                                           shape=[k], dtype="float32")
                sent_ids, sent_scores = layers.beam_search_decode(
                    ids_steps, final_scores, beam_size=k, end_id=eos_id,
                    parents=parent_steps)
                hyps_fetch = [sent_ids.name, sent_scores.name]

    if beam_size is not None:
        decode_feeds = ["gen_pre_ids", "gen_pre_scores", "gen_parents"]
    elif not kv_cache:
        decode_feeds = ["gen_prefix", "gen_t"]
    elif use_self_feed:
        decode_feeds = ["gen_active"]
    else:
        decode_feeds = ["gen_token", "gen_active"]
    return GenerationPrograms(
        prefill=prefill, decode=decode, hyps=hyps, startup=startup,
        self_cache=self_cache, cross_cache=cross_cache,
        enc_out_name=enc_out_name, src_bias_name=src_bias_name,
        self_feed_token=use_self_feed, last_tok_name=last_tok_name,
        finished_name=finished_name, decode_feeds=decode_feeds,
        prefill_fetch=prefill_fetch, decode_fetch=decode_fetch,
        # the pre-sampling [lanes, 1, vocab] logits: fetchable by name for
        # logit-level parity checks (chip_smoke.py), never fetched in
        # serving
        logits_name=logits.name,
        hyps_fetch=hyps_fetch if hyps is not None else None,
        batch_size=b, beam_size=beam_size, lanes=lanes,
        src_seq_len=src_seq_len, max_out_len=max_out_len, t_buf=t_buf,
        bos_id=bos_id, eos_id=eos_id, kv_cache=kv_cache, paged=paged,
        kv_block_t=self_cache.block_t if paged else 0,
        src_vocab_size=src_vocab_size, trg_vocab_size=trg_vocab_size,
        d_model=d_model, strategy=strategy)
