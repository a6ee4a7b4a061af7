"""The system under test for `joyai_llm_flash_ep16`: the training program
that the repository's own builder (`models.mla_moe_decoder`, Adam inside)
and amp give for one chip's share of the model.  The only file of this
configuration that imports `paddle_tpu`."""


def build(cfg, traffic):
    import paddle_tpu as pt
    from paddle_tpu.models import mla_moe_decoder as M

    opt = cfg["optimizer"]
    if (opt["beta1"], opt["beta2"], opt["epsilon"]) != (0.9, 0.999, 1e-8):
        raise ValueError("build_train_net takes Adam's defaults only")
    if cfg["dropout_rate"] or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the builder has no dropout and no group limit")
    dense = cfg["first_k_dense_replace"]
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _ = M.build_train_net(
            vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
            batch=traffic["batch"], d_model=cfg["hidden_size"],
            n_head=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], n_dense=dense,
            n_moe=cfg["num_hidden_layers"] - dense,
            d_ff_dense=cfg["intermediate_size"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_experts=cfg["router_experts"],
            n_held=cfg["n_routed_experts"],
            expert_offset=cfg["expert_offset"],
            top_k=cfg["num_experts_per_tok"],
            routed_scale=cfg["routed_scaling_factor"],
            n_shared=cfg["n_shared_experts"],
            n_mtp=cfg["num_nextn_predict_layers"],
            mtp_weight=cfg["mtp_loss_weight"],
            rope_theta=float(cfg["rope_theta"]),
            rms_eps=cfg["rms_norm_eps"],
            init_std=cfg["initializer_range"],
            bias_std=cfg["router_bias_std"], lr=traffic["learning_rate"])
    if cfg["amp"]:
        pt.amp.enable(prog)
    return prog, startup, loss
