"""The system under test for `lfm2_8b_a1b_ep4`: the training program that
the repository's own builder (`models.hybrid_conv_decoder`, Adam inside)
and amp give for one chip's share of the model.  The only file of this
configuration that imports `paddle_tpu`."""


def build(cfg, traffic):
    import paddle_tpu as pt
    from paddle_tpu.models import hybrid_conv_decoder as M

    opt = cfg["optimizer"]
    if (opt["beta1"], opt["beta2"], opt["epsilon"]) != (0.9, 0.999, 1e-8):
        raise ValueError("build_train_net takes Adam's defaults only")
    if cfg["dropout_rate"] or cfg["conv_bias"] or not (
            cfg["norm_topk_prob"] and cfg["use_expert_bias"]
            and cfg["tie_word_embeddings"]):
        raise ValueError(
            "the builder has no dropout and no convolution bias; the chosen "
            "weights sum to one, the router has its expert bias and the "
            "head is the embedding")
    if traffic["ids_len"] != traffic["seq_len"] + 1:
        raise ValueError("next-token rows carry seq_len + 1 ids")
    heads = cfg["num_attention_heads"]
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _ = M.build_train_net(
            vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
            batch=traffic["batch"],
            layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
            num_dense_layers=cfg["num_dense_layers"],
            d_model=cfg["hidden_size"], n_head=heads,
            n_kv_head=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // heads,
            conv_taps=cfg["conv_L_cache"],
            d_ff_dense=cfg["intermediate_size"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_experts=cfg["router_experts"], n_held=cfg["num_experts"],
            expert_offset=cfg["expert_offset"],
            top_k=cfg["num_experts_per_tok"],
            routed_scale=float(cfg["routed_scaling_factor"]),
            rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["norm_eps"],
            init_std=cfg["initializer_range"],
            bias_std=cfg["router_bias_std"], lr=traffic["learning_rate"],
            train_router=cfg.get("router_trained", True))
    if cfg["amp"]:
        pt.amp.enable(prog)
    return prog, startup, loss
