"""The benchmark's one command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output.  With
`--trace 0` its metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.  Exits with another code than 0, and
prints no result, where JAX finds no accelerator or too few chips."""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import registry

    cell = registry.load_cell(args.workload, t_start=T_START)
    try:
        import jax

        import paddle_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"perfbench: the program is not importable here: {e}",
              file=sys.stderr)
        return 4
    import report

    device = report.describe_device(cell.chips)
    if device is None:
        print(f"perfbench: no accelerator, or fewer than {cell.chips} chips: "
              f"{jax.devices()}", file=sys.stderr)
        return 3
    driver = registry.load_driver(cell.traffic["kind"])
    result = driver.run(cell, args.seed, args.seconds, bool(args.trace))
    line = report.result_line(cell, result, device, bool(args.trace))
    if result.get("where"):  # which leaf each worst-leaf number was read at
        print(f"where {json.dumps(result['where'])}", file=sys.stderr)
    if result.get("seeds"):  # what the weights and the feeds were drawn from
        print(f"seeds {json.dumps(result['seeds'])}", file=sys.stderr)
    report.print_compared(result["compared"], sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
