"""One Kimi Delta Attention layer at a cell's shape: the device time of the
forward and backward kernels by name (`kda_fwd`, `kda_bwd`), at each chunk
length the kernels take (`kernels/kda.py CHUNKS`).  A diagnostic: no
benchmark cell runs it.

    python3 tools/kda_bench.py [--batch 2] [--seq 4096] [--heads 32]
        [--head-dim 128] [--reps 8] [--check] [--out FILE]

The defaults are Kimi-Linear's cell (`kimi_linear_ep32_train`): batch 2 x
4096 rows, 32 heads of 128, bf16 q, k, v, a float32 log-decay whose scale
differs by head as the model's `A_log` does.  For each chunk the forward
and the backward are compiled and run once, then `--reps` times each in a
profiler session; the device's `XLA Ops` are summed by kernel name and
divided by the reps.  The backward is handed whatever the forward returns
beside o, so the tool runs on any version of `kernels/kda.py` that keeps
`kda(...)[1:]` as `kda_bwd`'s residuals.  `--check` adds the largest
difference of o and the five gradients from the XLA composition's.

One JSON line a chunk on stdout: `kda_fwd_ms`, `kda_bwd_ms` (device time a
call, null where the trace holds no TPU plane, as on the CPU), the wall
`fwd_ms`, `bwd_ms` (median over the reps), and with `--check` `max_diff`.
Off the TPU the kernels run interpreted (small shapes only).
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))


def operands(batch, seq, heads, dk):
    """q, k, v [b, t, h*dk] bf16, g [b, t, h*dk] float32 (-exp(A_log[h]) x
    softplus(N(0, 1)), A_log ~ N(0, 2)), beta [b, t, h] = sigmoid(N(0, 1)),
    do [b, t, h*dk] bf16."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(0)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    width = heads * dk
    bf = [normal(i, (batch, seq, width)).astype(jnp.bfloat16)
          for i in range(3)]
    a_log = jnp.repeat(2.0 * normal(3, (heads,)), dk)
    g = -jnp.exp(a_log) * jax.nn.softplus(normal(4, (batch, seq, width)))
    beta = jax.nn.sigmoid(normal(5, (batch, seq, heads)))
    do = normal(6, (batch, seq, width)).astype(jnp.bfloat16)
    return (*bf, g, beta), do


def device_ms(trace_dir, reps):
    """{kernel name prefix: device ms a call} from the trace's first TPU
    plane, {} where there is none."""
    import trace_reduce

    planes = {}
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                planes.update(trace_reduce.read_planes(
                    os.path.join(base, f)))
    tpus = sorted(n for n in planes if n.startswith("/device:TPU:"))
    if not tpus:
        return {}
    out = {}
    for name, _, dur in trace_reduce.leaves_only(
            planes[tpus[0]].get("XLA Ops", [])):
        for kernel in ("kda_fwd", "kda_bwd"):
            if trace_reduce.short_name(name).startswith(kernel):
                out[kernel] = out.get(kernel, 0.0) + dur / 1e6 / reps
    return out


def bench(chunk, args, ops, do):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import kda as K

    interpret = None if jax.default_backend() == "tpu" else True
    fwd = jax.jit(lambda *a: K.kda(*a, chunk=chunk, interpret=interpret))
    bwd = jax.jit(lambda *a: K.kda_bwd(*a, chunk=chunk,
                                       interpret=interpret))
    outs = jax.block_until_ready(fwd(*ops))
    grads = jax.block_until_ready(bwd(*ops, *outs[1:], do))

    def timed(fn, *a):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        return (time.perf_counter() - t0) * 1e3

    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            fwd_ms = [timed(fwd, *ops) for _ in range(args.reps)]
            bwd_ms = [timed(bwd, *ops, *outs[1:], do)
                      for _ in range(args.reps)]
        by_name = device_ms(trace_dir, args.reps)
    line = {"chunk": chunk, "batch": args.batch, "seq": args.seq,
            "heads": args.heads, "head_dim": args.head_dim,
            "device": str(jax.devices()[0].device_kind),
            "kda_fwd_ms": by_name.get("kda_fwd"),
            "kda_bwd_ms": by_name.get("kda_bwd"),
            "fwd_ms": statistics.median(fwd_ms),
            "bwd_ms": statistics.median(bwd_ms)}
    if args.check:
        ref = K.reference_kda(*ops, chunk=chunk)
        want = (ref[0],) + tuple(K.reference_kda_bwd(*ops, *ref[1:], do,
                                                     chunk=chunk))
        got = (outs[0],) + tuple(grads)
        line["max_diff"] = {
            n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
            for n, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                               got, want)}
    return line


def main(argv=None):
    from paddle_tpu.kernels.kda import CHUNKS

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seq", type=int, default=4096)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--reps", type=int, default=8)
    p.add_argument("--check", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    ops, do = operands(args.batch, args.seq, args.heads, args.head_dim)
    lines = []
    for chunk in CHUNKS:
        lines.append(bench(chunk, args, ops, do))
        print(json.dumps(lines[-1]), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
