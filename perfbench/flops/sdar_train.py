"""Matmul FLOPs of what ONE CHIP of the `sdar_30b_a3b_ep8` deployment
computes: per COUNTED token for `step_mfu.train` (2 FLOPs a multiply-add,
training = 3 x forward, no recomputation counted), and per step for the
two kernel families' roofline shares.

Block-diffusion training runs every counted token through the stack as
TWO positions ([x_t ; x0]), so a counted token costs, forward, twice a
layer's work a position and the head once (the head reads the noisy half
only).  A position's forward multiply-adds a layer: the q, k, v, o
projections (32 query and 4 key/value heads of 128); its visible (query,
key) pairs a head, (L B + L^2) / (2 L) on average over the 2L rows, each
one q.k and one p.v product; the router's 128 outputs (where the
configuration does not train the router, `router_trained` false, its two
backward products are not computed and not counted); the routed
experts at top_k x held / routed experts a position (8 x 16 / 128 = one
expert: the rest of a position's experts lie on other chips)."""


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def visible_pairs(seq_len, block_length):
    """(query, key) pairs a head and row of 2 seq_len positions that the
    block-diffusion mask lets through: L B within the noisy blocks, L (L -
    B) / 2 noisy-to-clean, L (L + B) / 2 clean-to-clean."""
    return seq_len * block_length + seq_len * seq_len


def _expert_macs(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_macs_per_token(cfg, seq_len):
    d, h, hk, dh = _dims(cfg)
    proj = 2 * d * h * dh + 2 * d * hk * dh
    attn = h * 2 * dh * visible_pairs(seq_len, cfg["block_length"]) / (
        2 * seq_len)
    routed = (cfg["num_experts_per_tok"] * cfg["num_experts"]
              / cfg["router_experts"])
    layer = (proj + attn + d * cfg["router_experts"]
             + routed * _expert_macs(cfg))
    return 2 * cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def flops_per_token(cfg, traffic):
    flops = 3 * 2 * forward_macs_per_token(cfg, traffic["seq_len"])
    if not cfg.get("router_trained", True):
        # no backward of the router: two positions a token, every layer
        flops -= 2 * 2 * (2 * cfg["num_hidden_layers"] * cfg["hidden_size"]
                          * cfg["router_experts"])
    return flops


def attention_flops_per_step(cfg, traffic):
    """Exact visible FLOPs of the flash kernels a step, forward +
    backward: `visible_pairs` a row, head and layer; a pair costs 2 (d +
    d) forward (q.k and p.v) and 2 (3 d + 2 d) backward (the scores again,
    dp, dq, dk, dv).  What the kernels compute beyond that (whole tiles on
    the block diagonals, the scores and dp a second time in the dkv walk)
    is not counted: the share reads low, never high."""
    _, h, _, dh = _dims(cfg)
    pairs = (traffic["batch"] * h * cfg["num_hidden_layers"]
             * visible_pairs(traffic["seq_len"], cfg["block_length"]))
    return pairs * (2 * (dh + dh) + 2 * (3 * dh + 2 * dh))


def gmm_flops_per_step(cfg, pairs):
    """The three grouped-matmul passes (forward, dX, dW) over `pairs`
    routed (position, expert) pairs a step, all layers together: each pass
    is one multiply-add an expert weight a pair."""
    return 3 * 2 * _expert_macs(cfg) * pairs


def gmm_bytes_per_step(cfg, pairs, itemsize=2):
    """HBM bytes of the same three passes: each held expert's weights once
    a pass (read by forward and dX, written by dW), and each pair's rows:
    a pass moves 2 d + 3 f elements a pair (forward: x in, gate|up out,
    the activation in, y out; dX and dW move as many)."""
    weights = (cfg["num_hidden_layers"] * cfg["num_experts"]
               * _expert_macs(cfg))
    rows = pairs * (2 * cfg["hidden_size"]
                    + 3 * cfg["moe_intermediate_size"])
    return 3 * itemsize * (weights + rows)
