#!/usr/bin/env python
"""CI router gate: a 3-replica fleet survives a chaos SIGKILL mid-flood.

A CPU gate, never a chip measurement: the replicas are JAX_PLATFORMS=cpu
subprocesses (a chip belongs to one process; fleet mode is CPU-only
until replicas are pinned one per chip, ROADMAP R6), and the "router
tax" it gates is a CPU-host number.

Driven by tools/run_ci.sh (the scale-out serving step).  One fleet
session, three phases:

  1. boot     — ReplicaSupervisor spawns 3 `python -m paddle_tpu.serving`
     replicas (shared JAX_COMPILATION_CACHE_DIR) behind an in-process
     Router.  Replica index 2 is chaos-armed via per_replica_env
     (FLAGS_chaos_kill_replica_after): it SIGKILLs itself after serving
     its K-th request — i.e. mid-flood, the way preemption would.
  2. overhead — the router-tax A/B at --max-batch 1: the same sequential
     single-row stream direct-to-replica vs through the router (the
     sequential stream pins to one replica, so both legs measure the
     same backend).  Gate: router p50 - direct p50 < 5 ms.
  3. flood    — a 16-worker closed-loop flood; the armed replica dies
     partway through.  Gates: ZERO non-429 client-visible errors (every
     connect-error failed over inside its deadline), router
     failover_total > 0, the flight record carries BOTH a router.evict
     and a router.readmit for the victim, and the supervisor's crash
     restart brought it back (restart_count > 0, back in rotation).

Artifact: <out-dir>/router_smoke.json — flood status table, router
counters, per-replica snapshots, the overhead A/B, and every gate
verdict — archived by CI next to the single-replica serving artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time
import urllib.error
import urllib.request

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

IN_DIM = 8
ARMED_INDEX = 2  # chaos-armed replica (sequential traffic pins to r0)
KILL_AFTER = 40  # requests the armed replica serves before SIGKILL


def export_demo_model(dirname: str) -> str:
    import paddle_tpu as pt
    from paddle_tpu import layers

    prog, startup = pt.Program(), pt.Program()
    prog.random_seed = startup.random_seed = 3
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[IN_DIM], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        out = layers.fc(h, size=2)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup, scope=scope)
        pt.io.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=prog, scope=scope)
    return dirname


def _post(url: str, timeout: float = 20.0):
    body = json.dumps({"inputs": {"x": [[0.1] * IN_DIM]},
                       "timeout_s": 15}).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            r.read()
            return r.status
    except urllib.error.HTTPError as e:
        e.read()
        return e.code
    except Exception as e:  # noqa: BLE001 — a connect error IS the finding
        return repr(e)


def measure_p50_ms(url: str, n: int) -> float:
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        status = _post(url)
        lat.append((time.perf_counter() - t0) * 1e3)
        assert status == 200, f"warm sequential request failed: {status}"
    return statistics.median(lat)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out-dir", default="ci_artifacts/serving")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--flood-n", type=int, default=400)
    ap.add_argument("--flood-workers", type=int, default=16)
    ap.add_argument("--ab-n", type=int, default=60)
    ap.add_argument("--overhead-ms", type=float, default=5.0)
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    from paddle_tpu.flags import FLAGS
    from paddle_tpu.monitor import default_registry, flight
    from paddle_tpu.serving.fleet import ReplicaSupervisor
    from paddle_tpu.serving.router import IN_ROTATION, Router

    FLAGS.monitor = True
    FLAGS.router_probe_interval_s = 0.3  # evict faster than the respawn
    model_dir = export_demo_model(os.path.join(args.out_dir,
                                               "router_demo_model"))
    cache_dir = os.path.join(args.out_dir, "router_xla_cache")
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
    }
    armed_rid = f"r{ARMED_INDEX}"
    sup = ReplicaSupervisor(
        ["--model", f"demo={model_dir}", "--buckets", "1",
         "--max-batch", "1", "--max-wait-ms", "1"],
        n=args.replicas, router=Router(), env=env,
        per_replica_env={ARMED_INDEX: {
            "FLAGS_chaos": "1",
            "FLAGS_chaos_kill_replica_after": str(KILL_AFTER)}},
        cwd=REPO_ROOT, restart_base_delay_s=0.2)
    print(f"[router_smoke] booting {args.replicas} replicas "
          f"({armed_rid} armed: SIGKILL after {KILL_AFTER} requests)...")
    router = sup.start()
    try:
        url = router.url
        predict = f"{url}/v1/models/demo:predict"

        # -- phase 2: router-tax A/B (sequential stream pins to r0) ----
        direct = (f"http://127.0.0.1:{sup.replica_port('r0')}"
                  f"/v1/models/demo:predict")
        measure_p50_ms(direct, 10)  # warm both paths' code + conns
        measure_p50_ms(predict, 10)
        direct_p50 = measure_p50_ms(direct, args.ab_n)
        router_p50 = measure_p50_ms(predict, args.ab_n)
        overhead_ms = router_p50 - direct_p50
        print(f"[router_smoke] overhead A/B: direct p50 "
              f"{direct_p50:.2f}ms, via router {router_p50:.2f}ms "
              f"(+{overhead_ms:.2f}ms)")

        # -- phase 3: flood with a mid-flood SIGKILL -------------------
        results: list = []
        lock = threading.Lock()
        per_worker = args.flood_n // args.flood_workers

        def worker():
            for _ in range(per_worker):
                status = _post(predict)
                with lock:
                    results.append(status)

        threads = [threading.Thread(target=worker)
                   for _ in range(args.flood_workers)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        flood_s = time.monotonic() - t0
        by_status: dict = {}
        for s in results:
            by_status[str(s)] = by_status.get(str(s), 0) + 1
        errors = [s for s in results if s != 200 and s != 429]
        print(f"[router_smoke] flood: {len(results)} requests in "
              f"{flood_s:.1f}s -> {by_status}")

        # the armed replica must come back before the books are checked
        deadline = time.monotonic() + 60
        while ((sup.restart_count(armed_rid) < 1
                or router.replica_state(armed_rid) != IN_ROTATION)
               and time.monotonic() < deadline):
            time.sleep(0.2)

        reg = default_registry()

        def cval(name):
            m = reg.get(name)
            return m.value if m is not None else 0

        evict_rids = {e.get("replica") for e in
                      flight.default_recorder().events(
                          kind="router.evict")}
        readmit_rids = {e.get("replica") for e in
                        flight.default_recorder().events(
                            kind="router.readmit")}
        gates = {
            "non_429_error_rate_zero": not errors,
            "failover_engaged": cval("router.failover_total") > 0,
            "victim_evicted": armed_rid in evict_rids,
            "victim_readmitted": armed_rid in readmit_rids,
            "supervisor_restarted_victim":
                sup.restart_count(armed_rid) >= 1,
            "victim_back_in_rotation":
                router.replica_state(armed_rid) == IN_ROTATION,
            "router_overhead_under_bound":
                overhead_ms < args.overhead_ms,
        }
        artifact = {
            "gate": "router_smoke",
            "replicas": args.replicas,
            "armed_replica": armed_rid,
            "kill_after_requests": KILL_AFTER,
            "flood": {"requests": len(results),
                      "wall_s": round(flood_s, 2),
                      "by_status": by_status,
                      "non_429_errors": [str(e) for e in errors[:10]]},
            "overhead_ab": {"direct_p50_ms": round(direct_p50, 3),
                            "router_p50_ms": round(router_p50, 3),
                            "overhead_ms": round(overhead_ms, 3),
                            "bound_ms": args.overhead_ms},
            "counters": {n: cval(f"router.{n}") for n in (
                "requests_total", "failover_total", "evictions_total",
                "readmissions_total", "replica_restarts_total")},
            "restart_counts": {f"r{i}": sup.restart_count(f"r{i}")
                               for i in range(args.replicas)},
            "replicas_final": router.replicas_info(),
            "gates": gates,
        }
        out = os.path.join(args.out_dir, "router_smoke.json")
        with open(out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"[router_smoke] artifact: {out}")
        for name, ok in gates.items():
            print(f"[router_smoke]   {'PASS' if ok else 'FAIL'}  {name}")
        if not all(gates.values()):
            print("[router_smoke] GATE RED", file=sys.stderr)
            return 1
        print(f"[router_smoke] GATE OK: {len(results)} flooded, "
              f"{cval('router.failover_total')} failovers, victim "
              f"evicted+readmitted+restarted, router tax "
              f"{overhead_ms:+.2f}ms")
        return 0
    finally:
        sup.stop()


if __name__ == "__main__":
    sys.exit(main())
