"""The system under test for `bert_base`: the masked-LM pre-training
program that the repository's own builder (Adam inside) and amp give.  The
only file of this configuration that imports `paddle_tpu`."""


def build(cfg, traffic):
    import paddle_tpu as pt
    from paddle_tpu.models import bert as B

    opt = cfg["optimizer"]
    if (opt["beta1"], opt["beta2"], opt["epsilon"]) != (0.9, 0.999, 1e-8):
        raise ValueError("build_pretrain_net takes Adam's defaults only")
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _ = B.build_pretrain_net(
            vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
            n_layer=cfg["num_hidden_layers"],
            n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
            d_ff=cfg["intermediate_size"],
            dropout_rate=cfg["dropout_rate"], use_flash=True,
            lr=traffic["learning_rate"])
    if cfg["amp"]:
        pt.amp.enable(prog)
    return prog, startup, loss
