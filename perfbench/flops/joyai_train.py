"""Matmul FLOPs of what ONE CHIP of the `joyai_llm_flash_ep16` deployment
computes: per trained token for `step_mfu.train` (2 FLOPs a multiply-add,
training = 3 x forward, no recomputation counted), and per step for the
two kernel families' roofline shares.

Counted per token, forward multiply-adds: each block's latent-attention
projections and its causal attention at the mean length (seq + 1) / 2;
the dense layer's SwiGLU; in every expert layer the router's 256 outputs,
the shared expert, and the routed experts at top_k x held / routed
experts a token (8 x 16 / 256 = half an expert: the rest of a token's
experts lie on other chips); the MTP module's projection and block; and
BOTH head products over the vocabulary slice."""


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _blocks(cfg):
    """(dense blocks, expert blocks), the MTP modules' among the latter."""
    dense = cfg["first_k_dense_replace"]
    return dense, (cfg["num_hidden_layers"] - dense
                   + cfg["num_nextn_predict_layers"])


def forward_macs_per_token(cfg, seq_len):
    d, h, dqk, dv = _dims(cfg)
    ql, kvl, rot = (cfg["q_lora_rank"], cfg["kv_lora_rank"],
                    cfg["qk_rope_head_dim"])
    mla = (d * ql + ql * h * dqk + d * (kvl + rot)
           + kvl * h * (cfg["qk_nope_head_dim"] + dv) + h * dv * d)
    attn = h * (dqk + dv) * (seq_len + 1) / 2
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["router_experts"])
    moe_ffn = (d * cfg["router_experts"]
               + (cfg["n_shared_experts"] + routed) * expert)
    n_dense, n_moe = _blocks(cfg)
    n_mtp = cfg["num_nextn_predict_layers"]
    return ((n_dense + n_moe) * (mla + attn)
            + n_dense * 3 * d * cfg["intermediate_size"] + n_moe * moe_ffn
            + n_mtp * 2 * d * d + (1 + n_mtp) * d * cfg["vocab_size"])


def flops_per_token(cfg, traffic):
    return 3 * 2 * forward_macs_per_token(cfg, traffic["seq_len"])


def attention_flops_per_step(cfg, traffic):
    """Exact causal FLOPs of the flash kernels a step, forward + backward:
    S (S + 1) / 2 (query, key) pairs a sequence, head and block; a pair
    costs 2 (d_qk + d_v) forward (q.k and p.v) and 2 (3 d_qk + 2 d_v)
    backward (the scores again, dp, dq, dk, dv).  What the kernels
    compute beyond that (whole tiles on the diagonal, the scores and dp a
    second time in the dkv walk) is not counted: the share reads low, never
    high."""
    _, h, dqk, dv = _dims(cfg)
    s = traffic["seq_len"]
    pairs = traffic["batch"] * h * sum(_blocks(cfg)) * s * (s + 1) // 2
    return pairs * (2 * (dqk + dv) + 2 * (3 * dqk + 2 * dv))


def _expert_macs(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def gmm_flops_per_step(cfg, pairs):
    """The three grouped-matmul passes (forward, dX, dW) over `pairs`
    routed (token, expert) pairs a step, all expert layers together: each
    pass is one multiply-add an expert weight a pair."""
    return 3 * 2 * _expert_macs(cfg) * pairs


def gmm_bytes_per_step(cfg, pairs, itemsize=2):
    """HBM bytes of the same three passes: each held expert's weights once
    a pass (read by forward and dX, written by dW), and each pair's rows:
    a pass moves 2 d + 3 f elements a pair (forward: x in, gate|up out,
    the activation in, y out; dX and dW move as many)."""
    _, n_moe = _blocks(cfg)
    weights = n_moe * cfg["n_routed_experts"] * _expert_macs(cfg)
    rows = pairs * (2 * cfg["hidden_size"]
                    + 3 * cfg["moe_intermediate_size"])
    return 3 * itemsize * (weights + rows)
