"""Reads, in one process on the chip, what a training cell's limits are
set from: the program's numbers over many seeds (the lower readings), the
control's (the reference in fp8, put in the program's place) and the
planted faults'.  Not part of a benchmark run.

    python3 perfbench/calibrate.py --workload W --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--free-weights]
        [--out FILE]

A configuration that states a `weights_seed` gives every seed the same
weights, and the seeds then differ in their feeds alone; with
`--free-weights` the key is set aside and the weights follow each seed,
which is how a limit is read across draws of the weights.  A traffic
mix's `data_seed` is not read here: the feeds follow each seed always.
"""

import argparse
import json
import sys
import time

import compare
import registry


def load_cell(workload, free_weights):
    cell = registry.load_cell(workload)
    if free_weights:
        cell.cfg.pop("weights_seed", None)
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--free-weights", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    def ints(s):
        return [int(x) for x in s.split(",") if x]

    import report
    import traffic_gen
    from paddle_tpu.inference import enable_compile_cache

    cell = load_cell(args.workload, args.free_weights)
    if report.describe_device(cell.chips) is None and not args.allow_cpu:
        print("calibrate: no accelerator", file=sys.stderr)
        return 3
    enable_compile_cache()
    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    rows = []

    def emit(row):
        ok, compared = compare.verdict(row, cell.limits)
        row["correct"] = ok
        row["failed"] = [n for n, c in compared.items()
                         if not c["value"] <= c["limit"]]
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)

    seeds = ints(args.seeds)
    controls, faults = ints(args.control_seeds), ints(args.fault_seeds)
    for seed in sorted(set(seeds) | set(controls) | set(faults)):
        feeds = traffic_gen.train_feeds(cell.traffic, cell.cfg, seed)
        t0 = time.time()
        ref = tc.reference(seed, feeds)
        t_ref = time.time() - t0
        if seed in seeds:
            t0 = time.time()
            obs = tc.first_calls(seed, feeds)
            numbers, where = train.numbers_of(obs, ref)
            emit({"seed": seed, "what": "program", **numbers, "where": where,
                  "calls_s": time.time() - t0, "reference_s": t_ref,
                  "losses0": obs["frozen"]["losses"],
                  "ref_losses0": ref["frozen"]["losses"]})
        if seed in faults:
            bad = [train.half_batch(f, tc.ref_mod.WEIGHTS_FIELD)
                   for f in feeds]
            obs = tc.first_calls(seed, bad)
            numbers, where = train.numbers_of(obs, ref)
            emit({"seed": seed, "what": "fault_half_batch", **numbers,
                  "where": where})
        if seed in controls:
            ctl = tc.reference(seed, feeds,
                               mode=cell.cfg["control_precision"])
            numbers, where = train.numbers_of(ctl, ref)
            emit({"seed": seed, "what": "control_" +
                  cell.cfg["control_precision"], **numbers, "where": where,
                  "losses0": ctl["frozen"]["losses"],
                  "ref_losses0": ref["frozen"]["losses"]})
    # the program's rows have to be correct, the control's and the faults' not
    odd = [(r["seed"], r["what"]) for r in rows
           if r["correct"] != (r["what"] == "program")]
    if odd:
        print(f"calibrate: verdicts not as they have to be: {odd}",
              file=sys.stderr)
    return 1 if odd else 0


if __name__ == "__main__":
    sys.exit(main())
