"""The system under test for `sdar_30b_a3b_ep8`: the training program that
the repository's own builder (`models.block_diffusion_decoder`, Adam
inside) and amp give for one chip's share of the model.  The only file of
this configuration that imports `paddle_tpu`."""


def build(cfg, traffic):
    import paddle_tpu as pt
    from paddle_tpu.models import block_diffusion_decoder as M

    opt = cfg["optimizer"]
    if (opt["beta1"], opt["beta2"], opt["epsilon"]) != (0.9, 0.999, 1e-8):
        raise ValueError("build_train_net takes Adam's defaults only")
    if cfg["dropout_rate"] or cfg["decoder_sparse_step"] != 1 or cfg[
            "mlp_only_layers"] or not cfg["norm_topk_prob"]:
        raise ValueError("the builder has no dropout, every layer is an "
                         "expert layer and the chosen weights sum to one")
    for key in ("block_length", "noise_level"):
        if traffic[key] != cfg[key]:
            raise ValueError(f"the traffic's {key} {traffic[key]} is not "
                             f"the configuration's {cfg[key]}")
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _ = M.build_train_net(
            vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
            batch=traffic["batch"], block_length=cfg["block_length"],
            noise_level=cfg["noise_level"],
            mask_token_id=cfg["mask_token_id"], d_model=cfg["hidden_size"],
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            n_layer=cfg["num_hidden_layers"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_experts=cfg["router_experts"], n_held=cfg["num_experts"],
            expert_offset=cfg["expert_offset"],
            top_k=cfg["num_experts_per_tok"],
            rope_theta=float(cfg["rope_theta"]),
            rms_eps=cfg["rms_norm_eps"], init_std=cfg["initializer_range"],
            lr=traffic["learning_rate"],
            train_router=cfg.get("router_trained", True))
    if cfg["amp"]:
        pt.amp.enable(prog)
    return prog, startup, loss
