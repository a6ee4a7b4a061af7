"""Why a training cell with a top-k router reads a large `grad_diff`: a
diagnostic, not part of a benchmark run, and it decides nothing.

A bf16-amp program and the float32 plain reference each route by their own
scores, so the tokens whose k-th and (k+1)-th scores lie within rounding
are sent to different experts.  This tool (1) counts, expert layer by
expert layer, the (token, expert) pairs the two choose differently in the
frozen call, with the reference's margin between its k-th and (k+1)-th
score on those tokens, and (2) reads the frozen numbers of `correct` twice:
against the reference routed by its own scores (what `correct` compares),
and against the reference routed as the program routed (`route_as`).  If
the flips are the cause, the second reading of the expert leaves falls to
what the other leaves read.

    python3 tools/router_flips.py --workload W --seed N [--out FILE]
        [--free-weights]

The feeds follow `--seed` always; a configuration that states a
`weights_seed` keeps its weights unless `--free-weights` sets the key
aside (as perfbench/calibrate.py's does).
"""

import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
FROZEN = ("grad_diff", "grad_diff_median", "loss0_rms", "loss0_gap")


def router_outputs(program):
    """The `TopkIdx` of every router, in program order: the main stack's
    expert layers, then the prediction modules', as the reference counts
    them."""
    return [op.output("TopkIdx")[0] for op in program.global_block().ops
            if op.type == "moe_router"]


def reference_choices(ref_mod, dots, cfg, params, ids):
    """The plain reference's own choice in every expert layer: [(idx [b,
    seq, top_k], margin [b, seq] between the last score chosen and the
    first left out)], from the reference's own blocks (the walk of its
    `hidden_states`, opened at each router)."""
    import jax
    import jax.numpy as jnp

    R, P, eps = ref_mod, params, cfg["rms_norm_eps"]
    k = cfg["num_experts_per_tok"]
    n_mtp = cfg["num_nextn_predict_layers"]
    seq = ids.shape[1] - 1 - n_mtp
    out = []

    def expert_block(x, p):
        x = x + R.mla(dots, cfg, R.rms_norm(x, P[p + ".attn_norm.scale"],
                                            eps), P, p)
        y = R.rms_norm(x, P[p + ".ffn_norm.scale"], eps)
        biased = jax.nn.sigmoid(dots.mm(y, P[p + ".router_w"])) \
            + P[p + ".router_bias"]
        top, idx = jax.lax.top_k(biased, k + 1)
        out.append((idx[..., :k], top[..., k - 1] - top[..., k]))
        return x + R.moe(dots, cfg, y, P, p)

    x = P["embed_w"][ids[:, :seq]]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        if i >= cfg["first_k_dense_replace"]:
            x = expert_block(x, p)
        else:
            x = R.block(dots, cfg, x, P, p, False)
    for j in range(n_mtp):
        p = f"mtp{j}"
        both = jnp.concatenate(
            [R.rms_norm(x, P[p + ".hnorm.scale"], eps),
             R.rms_norm(P["embed_w"][ids[:, j + 1:j + 1 + seq]],
                        P[p + ".enorm.scale"], eps)], axis=-1)
        x = expert_block(dots.mm(both, P[p + ".proj_w"]), p + ".block")
    return out


def block_diffusion_choices(ref_mod, dots, cfg, params, ids, noise):
    """`reference_choices` for a stack of the block-diffusion kind
    (perfbench/configs/sdar_30b_a3b_ep8_reference.py): every layer is an
    expert layer over the 2L positions [x_t ; x0], the router a softmax
    with no bias.  [(idx [b, 2L, top_k], margin [b, 2L])]."""
    import jax
    import jax.numpy as jnp

    R, P, eps = ref_mod, params, cfg["rms_norm_eps"]
    k = cfg["num_experts_per_tok"]
    out = []
    noisy = jnp.where(noise > 0, cfg["mask_token_id"], ids)
    x = P["embed_w"][jnp.concatenate([noisy, ids], axis=1)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        x = x + R.attention(dots, cfg, R.rms_norm(
            x, P[p + ".attn_norm.scale"], eps), P, p)
        y = R.rms_norm(x, P[p + ".ffn_norm.scale"], eps)
        top, idx = jax.lax.top_k(
            jax.nn.softmax(dots.mm(y, P[p + ".router_w"]), axis=-1), k + 1)
        out.append((idx[..., :k], top[..., k - 1] - top[..., k]))
        x = x + R.moe(dots, cfg, y, P, p)
    return out


def hybrid_conv_choices(ref_mod, dots, cfg, params, ids):
    """`reference_choices` for a stack whose operator follows a table
    (perfbench/configs/lfm2_8b_a1b_ep4_reference.py): `num_dense_layers`
    dense blocks, then expert layers under a sigmoid router whose bias
    enters the choice only; the stack sees all but the row's last id.
    [(idx [b, seq, top_k], margin [b, seq])]."""
    import jax

    R, P, eps = ref_mod, params, cfg["norm_eps"]
    k = cfg["num_experts_per_tok"]
    out = []
    x = P["embed_w"][ids[:, :-1]]
    for i, kind in enumerate(R.layer_types(cfg)):
        if i < cfg["num_dense_layers"]:
            x = R.block(dots, cfg, x, P, i)
            continue
        p = f"layer{i}"
        op = R.short_conv if kind == "conv" else R.attention
        x = x + op(dots, cfg, R.rms_norm(x, P[p + ".attn_norm.scale"], eps),
                   P, p)
        y = R.rms_norm(x, P[p + ".ffn_norm.scale"], eps)
        top, idx = jax.lax.top_k(
            jax.nn.sigmoid(dots.mm(y, P[p + ".router_w"]))
            + P[p + ".router_bias"], k + 1)
        out.append((idx[..., :k], top[..., k - 1] - top[..., k]))
        x = x + R.moe(dots, cfg, y, P, p)
    return out


def count_flips(mine, theirs, margin, n_experts, offset, held):
    """`mine`, `theirs` [tokens, top_k] expert ids, `margin` [tokens]:
    how far the two choices differ, over all experts and over those held
    here."""
    def chosen(idx):
        mask = np.zeros((idx.shape[0], n_experts), bool)
        mask[np.arange(idx.shape[0])[:, None], idx] = True
        return mask

    a, b = chosen(mine), chosen(theirs)
    differ = a ^ b
    here = slice(offset, offset + held)
    flipped = differ.any(axis=1)
    return {
        "tokens": int(a.shape[0]), "tokens_flipped": int(flipped.sum()),
        "pairs": int(a.sum()), "pairs_flipped": int(differ.sum()) // 2,
        "held_pairs": int(b[:, here].sum()),
        "held_pairs_flipped": int(differ[:, here].sum()),
        "margin_median": float(np.median(margin)),
        "margin_median_flipped": float(np.median(margin[flipped]))
        if flipped.any() else None,
        "margin_max_flipped": float(margin[flipped].max())
        if flipped.any() else None,
    }


def leaf_diffs(first, ref):
    """{leaf: |m_program - m_reference| / max(the reference's norm of the
    leaf, of the median leaf)}: what `grad_diff` takes the worst of."""
    mine, theirs = first["frozen"]["m_host"], ref["frozen"]["m_host"]
    norms = ref["frozen"]["m_norm"]
    floor = statistics.median(norms.values())
    return {k: float(np.linalg.norm((mine[k].astype(np.float64)
                                     - theirs[k]).ravel()))
            / max(norms[k], floor, 1e-30) for k in theirs}


def diagnose(cell, seed):
    """Both readings and the flip counts of one seed of a training cell."""
    import jax
    import jax.numpy as jnp

    import registry
    import traffic_gen

    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    cfg = cell.cfg
    feeds = traffic_gen.train_feeds(cell.traffic, cfg, seed)
    idx_vars = router_outputs(tc.prog)
    seen = []

    def call(feed):
        outs = tc.exe.run_steps(tc.prog, feed=feed,
                                fetch_list=[tc.loss] + idx_vars,
                                scope=tc.scope)
        seen.append([np.asarray(o) for o in outs[1:]])
        return np.asarray(outs[0], np.float64).reshape(-1)

    first = tc.first_calls(seed, feeds, call=call)
    tc.free()
    steps, rows = feeds[0]["ids"].shape[:2]
    # the frozen call's choices: [steps, rows, expert layers, seq, top_k]
    mine = np.stack([x.reshape(steps, rows, -1, x.shape[-1])
                     for x in seen[0]], axis=2)

    dots = tc.blocks.Dots("f32")
    # a traffic with a `noise` field trains by block diffusion; a
    # configuration with a table of operators is of the hybrid kind
    fields, choices = ("ids",), reference_choices
    if "noise" in feeds[0]:
        fields, choices = ("ids", "noise"), block_diffusion_choices
    elif "layer_types" in cfg:
        choices = hybrid_conv_choices
    look = jax.jit(lambda P, *rows: choices(tc.ref_mod, dots, cfg, P, *rows))
    params = tc.init_params(seed)
    layers = [[] for _ in idx_vars]
    held = cfg.get("n_routed_experts", cfg.get("num_experts"))
    for t in range(steps):
        theirs = look(params, *(jnp.asarray(feeds[0][f][t, ..., 0])
                                for f in fields))
        for j, (idx, margin) in enumerate(theirs):
            layers[j].append(count_flips(
                mine[t, :, j].reshape(-1, mine.shape[-1]),
                np.asarray(idx).reshape(-1, mine.shape[-1]),
                np.asarray(margin).reshape(-1), cfg["router_experts"],
                cfg["expert_offset"], held))
    del params, look

    def summed(per_step):
        out = {k: sum(s[k] for s in per_step) for k in per_step[0]
               if not k.startswith("margin")}
        for k in ("margin_median", "margin_median_flipped"):
            vals = [s[k] for s in per_step if s[k] is not None]
            out[k] = statistics.median(vals) if vals else None
        out["margin_max_flipped"] = max(
            (s["margin_max_flipped"] for s in per_step
             if s["margin_max_flipped"] is not None), default=None)
        return out

    result = {"workload": cell.name, "seed": seed, "steps": steps,
              "flips": [summed(x) for x in layers]}
    for how in ("own", "as_program"):
        fed = feeds
        if how == "as_program":
            fed = [dict(feeds[0], route_as=mine)] + list(feeds[1:])
        tc._ref_steps.clear()  # one reference step loaded at a time
        ref = tc.reference(seed, fed)
        numbers, where = train.numbers_of(first, ref)
        by_leaf = leaf_diffs(first, ref)
        result[how] = {
            **{k: numbers[k] for k in FROZEN},
            "worst_leaf": where["grad_diff"],
            "expert_leaves_worst": max(
                v for k, v in by_leaf.items() if ".experts_" in k),
            "other_leaves_worst": max(
                v for k, v in by_leaf.items() if ".experts_" not in k),
            "by_leaf": by_leaf}
        del ref
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--free-weights", action="store_true")
    args = ap.parse_args()

    import registry
    import report
    from paddle_tpu.inference import enable_compile_cache

    cell = registry.load_cell(args.workload)
    if args.free_weights:
        cell.cfg.pop("weights_seed", None)
    if report.describe_device(cell.chips) is None and not args.allow_cpu:
        print("router_flips: no accelerator", file=sys.stderr)
        return 3
    enable_compile_cache()
    result = diagnose(cell, args.seed)
    print(json.dumps({k: ({n: v for n, v in x.items() if n != "by_leaf"}
                          if isinstance(x, dict) else x)
                      for k, x in result.items()}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
