"""The system under test for `transformer_base`: the training program that
the repository's own builder, optimizer and amp give.  The only file of
this configuration that imports `paddle_tpu`."""


def build(cfg, traffic):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as T

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _, _ = T.transformer(
            src_vocab_size=cfg["src_vocab_size"],
            trg_vocab_size=cfg["trg_vocab_size"],
            max_length=max(traffic["src_len"], traffic["trg_len"]),
            n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_key=cfg["d_key"], d_value=cfg["d_value"],
            d_model=cfg["d_model"], d_inner_hid=cfg["d_inner_hid"],
            dropout_rate=cfg["dropout_rate"],
            src_seq_len=traffic["src_len"], trg_seq_len=traffic["trg_len"],
            use_flash=True)
        opt = cfg["optimizer"]
        pt.optimizer.Adam(learning_rate=traffic["learning_rate"],
                          beta1=opt["beta1"], beta2=opt["beta2"],
                          epsilon=opt["epsilon"]).minimize(loss)
    if cfg["amp"]:
        pt.amp.enable(prog)
    return prog, startup, loss
