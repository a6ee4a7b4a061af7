"""From a driver's result to the one line the benchmark prints."""

import glob
import os
import shutil

import registry
import trace_reduce


def describe_device(chips):
    """The device as JAX reports it, or None where there is no accelerator
    or there are fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def print_compared(compared, stream):
    for name, c in compared.items():
        print(f"compared {name} value {c['value']!r} limit {c['limit']!r}",
              file=stream)


def reduce_trace(traced, chips):
    paths = sorted(glob.glob(os.path.join(
        traced["dir"], "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler left no trace in {traced['dir']}")
    red = trace_reduce.reduce_file(paths[-1], chips)
    shutil.rmtree(traced["dir"], ignore_errors=True)
    return red


def result_line(cell, result, device, trace):
    device = dict(device, memory_peak_bytes=result["memory_peak_bytes"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    metrics = {}
    if trace:
        red = reduce_trace(result["traced"], cell.chips)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"cell": cell, "result": result, "device": device, "trace": red}
        for m in cell.metrics("per_layer"):
            value = registry.load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["breakdown"] = red["breakdown"]
    else:
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": result[m["name"]],
                                  "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["window"] = result["window"]
    line["memory_parts"] = result["memory_parts"]
    line["compared"] = result["compared"]
    return line
