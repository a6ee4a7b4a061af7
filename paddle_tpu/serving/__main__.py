"""CLI: `python -m paddle_tpu.serving --model name=/path/to/export ...`

Boots an InferenceServer, warms every model's bucket ladder, prints ONE
machine-readable ready line to stdout —

    {"event": "serving_ready", "port": N, "models": [...]}

— then serves until SIGTERM/SIGINT (the CI gate and subprocess tests
parse the ready line for the ephemeral port).

SIGTERM triggers a GRACEFUL DRAIN (the load-balancer contract): /health
flips to "draining" (503 — LBs stop sending), new requests get 503,
in-flight and queued-admitted work completes up to
FLAGS_serving_drain_timeout_s, the flight recorder dumps with trigger
"drain", and the process exits 0.  SIGINT stops immediately (interactive
use).
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving",
        description="multi-model inference server with dynamic batching")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=DIR",
                   help="serve the exported model at DIR as NAME "
                        "(repeatable)")
    p.add_argument("--demo-generation", action="append", default=[],
                   metavar="NAME",
                   help="also serve the seeded tiny transformer "
                        "generation model as NAME (continuous "
                        "token-level batching at "
                        "POST /v1/models/NAME:generate; the CI smoke "
                        "and loadgen --generate target)")
    p.add_argument("--gen-slots", type=int, default=None,
                   help="cache-slot count (decode batch) for "
                        "--demo-generation models "
                        "(default FLAGS_serving_decode_slots)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000,
                   help="0 picks an ephemeral port (printed in the ready "
                        "line)")
    p.add_argument("--buckets", default=None,
                   help="pad-to-bucket ladder, e.g. 1,2,4,8,16 "
                        "(default FLAGS_serving_buckets)")
    p.add_argument("--max-batch", type=int, default=None)
    p.add_argument("--max-wait-ms", type=float, default=None)
    p.add_argument("--use-aot", action="store_true",
                   help="load serialized AOT executable bundles — TRUSTED "
                        "artifacts only (pickle-based deserialization)")
    p.add_argument("--int8", action="append", default=[], metavar="NAME",
                   help="also serve an int8 replica of NAME (QAT-exported "
                        "models; selectable per request via precision)")
    p.add_argument("--no-optimize", action="store_true",
                   help="skip the BN-fold inference pass")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip bucket-ladder pre-compilation (first "
                        "requests then pay the compiles)")
    p.add_argument("--replicas", type=int, default=0,
                   help="fleet mode: supervise N replica subprocesses "
                        "(each serving the same models on an ephemeral "
                        "port) behind a health-driven router; this "
                        "process becomes supervisor + router and prints "
                        'a {"event": "router_ready", ...} line instead')
    p.add_argument("--router-port", type=int, default=None,
                   help="fleet mode: router listen port "
                        "(default FLAGS_router_port; 0 = ephemeral)")
    args = p.parse_args(argv)

    from paddle_tpu.flags import FLAGS
    from paddle_tpu.serving import InferenceServer, ModelConfig

    int8_names = set(args.int8)
    configs = []
    for spec in args.model:
        name, sep, dirname = spec.partition("=")
        if not sep or not name or not dirname:
            p.error(f"--model expects NAME=DIR, got {spec!r}")
        configs.append(ModelConfig(
            name=name, dirname=dirname, use_aot=args.use_aot,
            optimize=not args.no_optimize, int8=name in int8_names,
            buckets=args.buckets, max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms))
    unknown = int8_names - {c.name for c in configs}
    if unknown:
        p.error(f"--int8 names not among --model entries: {sorted(unknown)}")
    if not configs and not args.demo_generation:
        p.error("nothing to serve: pass --model and/or --demo-generation")

    if args.replicas > 0:
        return _run_fleet(args)

    # warmup compiles survive restarts; replicas of a fleet inherit
    # JAX_COMPILATION_CACHE_DIR (or share <checkout>/.jax_cache)
    from paddle_tpu.inference import enable_compile_cache

    enable_compile_cache()
    server = InferenceServer(configs, host=args.host, port=args.port)
    if args.demo_generation:
        from paddle_tpu.serving.generation import \
            build_demo_generation_model

        for name in args.demo_generation:
            server.add_generation_model(
                build_demo_generation_model(name, slots=args.gen_slots))
    server.start(warmup=not args.no_warmup)
    print(json.dumps({
        "event": "serving_ready",
        "port": server.port,
        "host": args.host,
        "models": server.model_names,
    }), flush=True)

    done = threading.Event()
    sigs = []

    def _shutdown(signum, frame):
        sigs.append(signum)
        done.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _shutdown)
        except (ValueError, OSError):
            pass
    try:
        done.wait()
    finally:
        if sigs and sigs[0] == signal.SIGTERM:
            # graceful drain: readiness -> draining, new requests 503,
            # admitted work completes (bounded), flight dump, exit 0
            from paddle_tpu.monitor import flight

            drained = server.drain(reason="sigterm")
            flight.record("serving.drain_complete", drained=drained)
            flight.dump(trigger="drain",
                        extra={"drained": drained, "signal": "SIGTERM"})
        else:
            server.stop()
    return 0


def _replica_args(args) -> list:
    """Rebuild the per-replica CLI from the parsed fleet CLI (everything
    except the fleet-only and port arguments — the supervisor owns
    ports)."""
    out = []
    for spec in args.model:
        out += ["--model", spec]
    for name in args.demo_generation:
        out += ["--demo-generation", name]
    if args.gen_slots is not None:
        out += ["--gen-slots", str(args.gen_slots)]
    if args.buckets is not None:
        out += ["--buckets", args.buckets]
    if args.max_batch is not None:
        out += ["--max-batch", str(args.max_batch)]
    if args.max_wait_ms is not None:
        out += ["--max-wait-ms", str(args.max_wait_ms)]
    if args.use_aot:
        out += ["--use-aot"]
    for name in args.int8:
        out += ["--int8", name]
    if args.no_optimize:
        out += ["--no-optimize"]
    if args.no_warmup:
        out += ["--no-warmup"]
    return out


def _run_fleet(args) -> int:
    """Fleet mode: this process is supervisor + router; the replicas are
    subprocesses of the SAME CLI without --replicas."""
    from paddle_tpu.monitor import flight
    from paddle_tpu.serving.fleet import ReplicaSupervisor
    from paddle_tpu.serving.router import Router

    from paddle_tpu.flags import FLAGS

    FLAGS.monitor = True  # a blind router is undebuggable (same stance
    #                       as the replica server)
    router = Router(host=args.host, port=args.router_port)
    sup = ReplicaSupervisor(_replica_args(args), n=args.replicas,
                            router=router, host=args.host)
    sup.start()
    print(json.dumps({
        "event": "router_ready",
        "port": router.port,
        "host": args.host,
        "replicas": args.replicas,
        "replica_ports": [sup.replica_port(f"r{i}")
                          for i in range(args.replicas)],
    }), flush=True)

    done = threading.Event()

    def _shutdown(signum, frame):
        done.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _shutdown)
        except (ValueError, OSError):
            pass
    try:
        done.wait()
    finally:
        flight.record("router.fleet_stop", replicas=args.replicas)
        sup.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
