"""Matmul FLOPs and kernel bytes of what ONE CHIP of the `lfm2_8b_a1b_ep4`
deployment computes: per trained token for `step_mfu.train` (2 FLOPs a
multiply-add, training = 3 x forward, no recomputation counted), and per
step for the three kernel families' roofline shares.

Counted per token, forward multiply-adds: a convolution block's in- and
out-projection (d x 3d + d x d; the three taps and the two gates are no
matrix product and are not counted); an attention block's q, k, v, o
projections (32 query and 8 key/value heads of 64) and its causal
attention at the mean length (seq + 1) / 2; the two leading blocks' dense
SwiGLU; in every expert layer the router's 32 outputs (where the
configuration does not train the router, `router_trained` false, its two
backward products are not computed and not counted) and the routed experts
at top_k x held / routed experts a token (4 x 8 / 32 = one expert: the
rest of a token's experts lie on other chips); the head over the
vocabulary slice (the embedding transposed: one product)."""


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], d // h


def _layers(cfg):
    """(convolution blocks, attention blocks, dense blocks, expert
    blocks) of the layers held."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    dense = min(cfg["num_dense_layers"], len(kinds))
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            len(kinds) - dense)


def _expert_macs(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_macs_per_token(cfg, seq_len):
    d, h, hk, dh = _dims(cfg)
    n_conv, n_attn, n_dense, n_moe = _layers(cfg)
    conv = 4 * d * d
    attn = 2 * d * h * dh + 2 * d * hk * dh + h * 2 * dh * (seq_len + 1) / 2
    routed = (cfg["num_experts_per_tok"] * cfg["num_experts"]
              / cfg["router_experts"])
    moe_ffn = d * cfg["router_experts"] + routed * _expert_macs(cfg)
    return (n_conv * conv + n_attn * attn
            + n_dense * 3 * d * cfg["intermediate_size"] + n_moe * moe_ffn
            + d * cfg["vocab_size"])


def flops_per_token(cfg, traffic):
    flops = 3 * 2 * forward_macs_per_token(cfg, traffic["seq_len"])
    if not cfg.get("router_trained", True):
        flops -= 2 * 2 * (_layers(cfg)[3] * cfg["hidden_size"]
                          * cfg["router_experts"])
    return flops


def attention_flops_per_step(cfg, traffic):
    """Exact causal FLOPs of the flash kernels a step, forward + backward:
    S (S + 1) / 2 (query, key) pairs a sequence, query head and attention
    block; a pair costs 2 (d + d) forward (q.k and p.v) and 2 (3 d + 2 d)
    backward (the scores again, dp, dq, dk, dv).  What the kernels compute
    beyond that (whole tiles on the diagonal, the scores and dp a second
    time in the dkv walk) is not counted: the share reads low, never
    high."""
    _, h, _, dh = _dims(cfg)
    s = traffic["seq_len"]
    pairs = traffic["batch"] * h * _layers(cfg)[1] * s * (s + 1) // 2
    return pairs * (2 * (dh + dh) + 2 * (3 * dh + 2 * dh))


def short_conv_bytes_per_step(cfg, traffic, itemsize=2):
    """HBM bytes of `short_conv_fwd` + `short_conv_bwd` a step, every
    convolution block: operands and results once a pass at their HBM
    dtypes.  Forward: X [T, 3d] in, Out [T, d] out, the filter [d, L] in
    float32.  Backward: X and dOut in, dX [T, 3d] out, the filter in and
    its gradient out in float32.  The halo rows a block reads beside its
    own, and the backward's partial filter sums a grid step, are the
    kernel's overhead and are not counted: the share reads low, never
    high."""
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    tokens = traffic["batch"] * traffic["seq_len"]
    rows = itemsize * tokens * d * ((3 + 1) + (3 + 1 + 3))
    return _layers(cfg)[0] * (rows + 4 * 3 * d * taps)


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
    weights = _layers(cfg)[3] * cfg["num_experts"] * _expert_macs(cfg)
    rows = pairs * (2 * cfg["hidden_size"]
                    + 3 * cfg["moe_intermediate_size"])
    return 3 * itemsize * (weights + rows)
