#!/usr/bin/env python
"""CI serving gate: export a model, boot the server, prove the batcher.

A CPU gate, never a chip measurement: every server it boots is a
JAX_PLATFORMS=cpu subprocess, and its QPS ratios are pinned by injected
chaos latency, not by any device.

Driven by tools/run_ci.sh (the serving smoke step).  Three phases, all
against `python -m paddle_tpu.serving` subprocesses driven by
tools/loadgen.py:

  1. smoke    — a few hundred shape-varying requests (batch sizes cycle
     1,2,3,4) against a batched server; asserts the request-latency p99
     and batch-fill histograms appear in the scraped /metrics, and that
     the executor compile counter stayed FLAT during the load (warm
     bucket ladder: zero recompiles across the shape-varying stream).
  2. A/B      — the acceptance demonstration: the SAME single-row
     request stream against a batched server vs a --max-batch 1 server
     (both warm, same compiled-signature ladder).  Dynamic batching must
     deliver >= --ab-target x the QPS of batch-size-1 serving.  BOTH
     servers are chaos-latency-armed (FLAGS_chaos_serve_latency_s pins
     the per-batch cost at AB_CHAOS_LAT_S), so capacity is determined by
     the injected latency, not the CI box: batch1 serves ~1/L rows/s
     while the batched server coalesces ~concurrency rows per L —
     the expected ratio is ~min(concurrency, max_batch), and the 2x
     gate is box-independent (the earlier uninjected gate measured
     1.2x-3.3x for the SAME build depending on the box).  Trials are
     interleaved pairs and the gate takes the best pair, stopping early
     once the target is met.
  3. artifact — every loadgen JSON + an ab_summary.json with the
     per-trial QPS table lands in --out-dir for CI archiving.
  4. overload — the robustness gate (overload_gate): an open-loop flood
     at ~4x MEASURED capacity against a chaos-latency-armed server with
     bounded queues must shed (429 + Retry-After), drop expired
     requests before dispatch (expired_dropped_total delta > 0), serve
     zero crash-5xx with a FLAT compile counter, keep accepted-request
     p99 under a stated bound — and a SIGTERM mid-load must drain
     in-flight work (200s), 503 new requests, dump a drain-trigger
     flight record and exit 0; artifact overload_smoke.json.
  5. generation — the continuous token-level batching gate against a
     `--demo-generation` server (generation_gate): staggered
     prompt-in/tokens-out stream with the compile counter FLAT and TTFT
     histograms served, a late-joining request that must neither retrace
     nor stall the in-flight long generation, and the throughput A/B
     (concurrent streams >= 2x one sequential stream's tokens/sec);
     artifacts loadgen_gen*.json + gen_ab_summary.json.
  6. tracing — the request-scoped distributed-tracing gate
     (tracing_gate): a FLAGS_trace_requests server must echo the
     client's traceparent, serve /v1/traces with full span trees for a
     predict AND a multi-token generation whose latency decompositions
     sum to the measured wall clock within 5%, expose SLO burn-rate
     gauges on /metrics, and close the loadgen --trace correlation loop;
     artifact trace_sample.json (one trace per kind, all span kinds).

Both servers stay resident across trials (warmup is paid once) and
requests ride keep-alive connections, so the measurement sees the
serving tier, not process startup or TCP churn.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def export_demo_model(dirname: str, in_dim: int = 32, hidden: int = 256,
                      nlayers: int = 32, out_dim: int = 4) -> str:
    """A deep-but-narrow fc stack: per-dispatch cost is dominated by the
    layer count (weight reads + dispatch overhead), nearly flat in batch
    size on CPU — the regime where coalescing visibly pays."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    prog, startup = pt.Program(), pt.Program()
    prog.random_seed = startup.random_seed = 3
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[in_dim], dtype="float32")
        h = x
        for _ in range(nlayers):
            h = layers.fc(h, size=hidden, act="relu")
        out = layers.fc(h, size=out_dim)
    scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup, scope=scope)
        pt.io.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=prog, scope=scope)
    return dirname


class Server:
    """One `python -m paddle_tpu.serving` subprocess on an ephemeral
    port; parses the ready line, kills the process on close()."""

    def __init__(self, model_dir, extra_args, extra_env=None):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO_ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        env.update(extra_env or {})
        model_args = ([] if model_dir is None
                      else ["--model", f"demo={model_dir}"])
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving",
             "--port", "0"] + model_args + list(extra_args),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        line = self.proc.stdout.readline().decode()
        try:
            ready = json.loads(line)
        except ValueError:
            err = self.proc.stderr.read().decode()[-2000:]
            raise RuntimeError(
                f"server did not print a ready line: {line!r}\n{err}")
        self.url = f"http://127.0.0.1:{ready['port']}"
        # Drain both pipes for the life of the server: an undrained PIPE
        # fills at ~64KB and blocks the server's writer (e.g. verbose
        # jax warnings), stalling requests until the loadgen timeout.
        for stream in (self.proc.stdout, self.proc.stderr):
            threading.Thread(target=self._drain, args=(stream,),
                             daemon=True).start()

    @staticmethod
    def _drain(stream):
        for _ in iter(stream.readline, b""):
            pass

    def close(self):
        self.proc.terminate()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()


def run_loadgen(url: str, out: str, requests: int, concurrency: int,
                batch_sizes: str, model: str = "demo",
                extra=()) -> dict:
    cmd = [sys.executable, os.path.join(REPO_ROOT, "tools", "loadgen.py"),
           "--url", url, "--model", model,
           "--requests", str(requests), "--concurrency", str(concurrency),
           "--batch-sizes", batch_sizes, "--out", out] + list(extra)
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise RuntimeError(f"loadgen failed:\n{r.stderr[-3000:]}")
    with open(out) as f:
        return json.load(f)


def http_generate(url: str, prompt, max_tokens: int,
                  timeout: float = 60.0, headers=None) -> dict:
    import urllib.request

    body = json.dumps({"prompt": prompt,
                       "max_tokens": max_tokens}).encode()
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        f"{url}/v1/models/gendemo:generate", data=body, headers=hdrs)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def generation_gate(args) -> None:
    """Continuous token-level batching gate (PR-11 acceptance):

      1. loadgen --generate smoke: staggered prompt-in/tokens-out stream
         with the executor compile counter FLAT and TTFT p50/p99 in the
         artifact;
      2. late-join: a short request submitted while a long generation is
         mid-flight must finish FIRST (no head-of-line stall) and add
         ZERO compiles (no retrace);
      3. throughput A/B: >= --gen-ab-target x tokens/sec from
         concurrent streams (continuous batching fills the decode batch)
         vs one sequential stream (batch-1 decode), interleaved trials.
    """
    import urllib.request

    server = Server(None, ["--demo-generation", "gendemo",
                           "--gen-slots", "4"])
    try:
        # -- phase 1: staggered stream, compile counter flat ------------
        smoke = run_loadgen(
            server.url, os.path.join(args.out_dir, "loadgen_gen.json"),
            40, 6, "1", model="gendemo",
            extra=["--generate", "--max-tokens", "8"])
        assert smoke["errors"] == 0, smoke
        gen = smoke["generation"]
        assert gen["tokens_received"] > 0, smoke
        assert gen["ttft_ms"] and gen["ttft_ms"]["p99"] > 0, smoke
        assert smoke["server_metrics"][
            "executor_compiles_during_load"] == 0, \
            f"retrace during generation load: {smoke['server_metrics']}"
        prom = scrape(server.url)
        assert "serving_gen_gendemo_ttft_seconds_bucket" in prom, \
            "ttft histogram missing from /metrics"
        print(f"generation smoke OK: {gen['tokens_received']} tokens, "
              f"{gen['tokens_per_sec']} tok/s, "
              f"ttft p50={gen['ttft_ms']['p50']}ms "
              f"p99={gen['ttft_ms']['p99']}ms, recompiles=0", flush=True)

        # -- phase 2: late join must neither retrace nor stall ----------
        c0 = _prom_scalar(scrape(server.url), "executor_compiles")
        done = {}

        def long_req():
            done["long"] = (http_generate(server.url, [3, 5, 7], 64),
                            time.perf_counter())

        t_long = threading.Thread(target=long_req)
        t_long.start()
        time.sleep(0.01)  # let the long request start decoding
        short, t_short_done = (http_generate(server.url, [9, 2], 2),
                               time.perf_counter())
        t_long.join(timeout=60)
        long_rec, t_long_done = done["long"]
        assert len(short["tokens"]) == 2, short
        assert len(long_rec["tokens"]) == 64, long_rec
        assert t_short_done < t_long_done, \
            "late-joining short request stalled behind the long one"
        assert _prom_scalar(scrape(server.url),
                            "executor_compiles") == c0, \
            "late join retraced"
        print(f"late-join OK: short ttft "
              f"{short['meta']['ttft_ms']}ms while long in flight, "
              f"0 compiles", flush=True)

        # -- phase 3: continuous batching >= target x batch-1 decode ----
        trials, best = [], None
        for t in range(args.ab_trials):
            multi = run_loadgen(
                server.url,
                os.path.join(args.out_dir, "loadgen_gen_multi.json"),
                16, 4, "1", model="gendemo",
                extra=["--generate", "--max-tokens", "16"])
            single = run_loadgen(
                server.url,
                os.path.join(args.out_dir, "loadgen_gen_single.json"),
                8, 1, "1", model="gendemo",
                extra=["--generate", "--max-tokens", "16"])
            for rec in (multi, single):
                assert rec["errors"] == 0, rec
                assert rec["server_metrics"][
                    "executor_compiles_during_load"] == 0, rec
            tps_m = multi["generation"]["tokens_per_sec"]
            tps_s = single["generation"]["tokens_per_sec"]
            ratio = tps_m / max(tps_s, 1e-9)
            trials.append({"trial": t, "multi_tok_s": tps_m,
                           "single_tok_s": tps_s,
                           "ratio": round(ratio, 3)})
            print(f"gen A/B trial {t}: {tps_m} vs {tps_s} tok/s -> "
                  f"{ratio:.2f}x", flush=True)
            if best is None or ratio > best["ratio"]:
                best = trials[-1]
            if ratio >= args.gen_ab_target:
                break
            time.sleep(1.0)
        summary = {
            "tool": "serving_smoke.generation",
            "slots": 4,
            "target_ratio": args.gen_ab_target,
            "trials": trials,
            "best": best,
            "passed": best["ratio"] >= args.gen_ab_target,
        }
        with open(os.path.join(args.out_dir,
                               "gen_ab_summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
        if not summary["passed"]:
            raise AssertionError(
                f"generation A/B gate FAILED: best "
                f"{best['ratio']}x < {args.gen_ab_target}x")
        print(f"generation A/B gate OK: continuous batching "
              f"{best['ratio']}x over batch-1 decode", flush=True)
    finally:
        server.close()


def overload_gate(args) -> None:
    """[robustness] The overload gate (ISSUE 13 acceptance criteria).

    A chaos-armed server (deterministic per-batch latency pins capacity
    so the gate is CI-box-independent; --max-batch 1 disables coalescing
    so queue wait is load-proportional; bounded queue) faces an
    open-loop flood at ~4x its MEASURED capacity with a short propagated
    client deadline.  Asserted:

      * shedding engaged: 429s with Retry-After at the client, server
        shed counter delta > 0;
      * deadline propagation: expired_dropped_total delta > 0 — admitted
        requests whose deadline passed while queued were dropped BEFORE
        dispatch, never executed;
      * zero crash-5xx (no 500s) and a FLAT executor compile counter;
      * accepted-request p99 under the stated bound: whatever the server
        ACCEPTS stays fast (deadline + one batch + scheduling slack);
      * SIGTERM mid-load: admitted in-flight work completes 200, a
        request during the drain gets 503, the flight dump names trigger
        "drain", and the process exits 0 inside the drain budget.

    Artifact: overload_smoke.json.
    """
    import glob
    import signal
    import urllib.error
    import urllib.request

    CHAOS_LAT_S = 0.15      # injected per-batch latency -> capacity ~6.7qps
    QUEUE_DEPTH = 12        # bounded queue: max wait ~ 12 x 0.15 = 1.8s
    DEADLINE_S = 1.2        # propagated client deadline < max queue wait
    DRAIN_TIMEOUT_S = 10.0
    # stated accepted-p99 bound: a request the server ACCEPTS waited at
    # most its deadline, plus one chaos-slowed batch, plus slack
    P99_BOUND_MS = (DEADLINE_S + CHAOS_LAT_S) * 1e3 + 1500

    model_dir = os.path.join(args.out_dir, "demo_model")
    flight_dir = os.path.join(args.out_dir, "flight")
    os.makedirs(flight_dir, exist_ok=True)
    chaos_env = {
        "FLAGS_chaos": "1",
        "FLAGS_chaos_serve_latency_s": str(CHAOS_LAT_S),
        "FLAGS_serving_max_queue_depth": str(QUEUE_DEPTH),
        "FLAGS_serving_drain_timeout_s": str(DRAIN_TIMEOUT_S),
        "FLAGS_flight_dir": flight_dir,
    }
    policy = ["--buckets", "1", "--max-batch", "1", "--max-wait-ms", "1"]
    artifact = {"tool": "serving_smoke.overload",
                "chaos_latency_s": CHAOS_LAT_S,
                "queue_depth": QUEUE_DEPTH,
                "deadline_s": DEADLINE_S,
                "p99_bound_ms": P99_BOUND_MS}

    server = Server(model_dir, policy, extra_env=chaos_env)
    try:
        # -- phase 1: measure capacity (closed loop, no pressure) -------
        cap = run_loadgen(
            server.url, os.path.join(args.out_dir, "loadgen_capacity.json"),
            16, 4, "1", extra=["--timeout-s", "30"])
        assert cap["errors"] == 0, cap
        cap_qps = max(cap["qps"], 1e-3)
        artifact["capacity_qps"] = cap_qps

        # -- phase 2: open-loop flood at ~4x capacity -------------------
        offered = round(4.0 * cap_qps, 2)
        n = max(80, min(300, int(offered * 6)))
        flood = run_loadgen(
            server.url, os.path.join(args.out_dir, "loadgen_flood.json"),
            n, 16, "1",
            extra=["--mode", "open", "--qps", str(offered),
                   "--timeout-s", str(DEADLINE_S),
                   "--max-retries", "0", "--max-error-rate", "1.0"])
        sm = flood["server_metrics"]
        sc = flood["status_counts"]
        assert flood["sheds"] > 0 and sc.get("429", 0) > 0, \
            f"no shedding at {offered} qps offered: {sc}"
        assert flood["retry_after_seen"] > 0, \
            "429s did not carry a Retry-After"
        assert sm["shed_total"] > 0, sm
        assert sm["expired_dropped_total"] > 0, \
            f"no deadline drops (expired requests were executed?): {sm}"
        assert sc.get("500", 0) == 0, f"crash-5xx under overload: {sc}"
        assert sm["executor_compiles_during_load"] == 0, sm
        assert flood["latency_ms"]["p99"] < P99_BOUND_MS, \
            (f"accepted-request p99 {flood['latency_ms']['p99']}ms over "
             f"the {P99_BOUND_MS}ms bound")
        artifact["flood"] = {
            "offered_qps": offered, "requests": n,
            "accepted": flood["completed"],
            "accepted_p99_ms": flood["latency_ms"]["p99"],
            "sheds_429": sc.get("429", 0),
            "retry_after_seen": flood["retry_after_seen"],
            "server_shed_total": sm["shed_total"],
            "expired_dropped_total": sm["expired_dropped_total"],
            "status_counts": sc,
            "compile_delta": sm["executor_compiles_during_load"],
        }
        print(f"overload flood OK: {offered} qps offered vs "
              f"{cap_qps} capacity -> {flood['completed']} accepted "
              f"(p99 {flood['latency_ms']['p99']}ms), "
              f"{sc.get('429', 0)} shed, "
              f"{sm['expired_dropped_total']:.0f} expired-dropped, "
              f"0 crash-5xx, compiles flat", flush=True)
    finally:
        server.close()

    # -- phase 3: SIGTERM mid-load drains and exits 0 -------------------
    server = Server(model_dir, policy, extra_env=chaos_env)
    results = []

    def one_request():
        body = json.dumps({"inputs": {"x": [[0.5] * 32]},
                           "timeout_s": 30}).encode()
        req = urllib.request.Request(
            f"{server.url}/v1/models/demo:predict", data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                results.append(r.status)
        except urllib.error.HTTPError as e:
            results.append(e.code)
        except Exception as e:  # noqa: BLE001 — recorded for the assert
            results.append(f"{type(e).__name__}: {e}")

    try:
        # ~10 x 0.15s of admitted work = the drain window
        threads = [threading.Thread(target=one_request)
                   for _ in range(10)]
        for t in threads:
            t.start()
        # SIGTERM only once every burst request is ADMITTED (the
        # in-flight gauge counts them) — requests that arrive after the
        # drain begins are 503s by design, not members of this assert
        t_wait = time.monotonic() + 10
        while time.monotonic() < t_wait:
            done_200 = sum(1 for r in results if r == 200)
            inflight = _prom_scalar(scrape(server.url),
                                    "serving_demo_inflight")
            if inflight + done_200 >= len(threads):
                break
            time.sleep(0.05)
        t0 = time.monotonic()
        server.proc.send_signal(signal.SIGTERM)
        time.sleep(0.3)
        # a request DURING the drain: 503, not a hang/5xx-crash
        during = None
        body = json.dumps({"inputs": {"x": [[0.5] * 32]}}).encode()
        req = urllib.request.Request(
            f"{server.url}/v1/models/demo:predict", data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                during = r.status
        except urllib.error.HTTPError as e:
            during = e.code
        except Exception as e:  # noqa: BLE001
            during = f"{type(e).__name__}"
        for t in threads:
            t.join(timeout=30)
        rc = server.proc.wait(timeout=DRAIN_TIMEOUT_S + 10)
        drain_s = round(time.monotonic() - t0, 3)
    finally:
        server.close()
    assert rc == 0, f"drain exit code {rc} (want 0)"
    assert during == 503, f"request during drain got {during!r} (want 503)"
    assert all(r == 200 for r in results), \
        f"admitted in-flight work did not complete 200: {results}"
    assert drain_s < DRAIN_TIMEOUT_S + 5, drain_s
    dumps = glob.glob(os.path.join(flight_dir, "flight-*-drain.jsonl"))
    assert dumps, f"no drain-trigger flight dump in {flight_dir}"
    with open(dumps[-1]) as f:
        header = json.loads(f.readline())
    assert header.get("trigger") == "drain", header
    artifact["drain"] = {"exit_code": rc, "drain_s": drain_s,
                        "inflight_results": results,
                        "during_drain_status": during,
                        "flight_dump": os.path.basename(dumps[-1])}
    artifact["passed"] = True
    with open(os.path.join(args.out_dir, "overload_smoke.json"), "w") as f:
        json.dump(artifact, f, indent=2)
    print(f"overload gate OK: shed+expired+flat compiles under 4x load; "
          f"SIGTERM drained {len(results)} in-flight in {drain_s}s, "
          f"exit 0, drain flight dump archived", flush=True)


def tracing_gate(args) -> None:
    """[observability] Request-scoped tracing gate (ISSUE 14 acceptance).

    One FLAGS_trace_requests + FLAGS_serving_slo_ms server (predict
    model + demo generation model).  Asserted:

      * loadgen --trace closes the correlation loop: client-generated
        traceparent ids resolve at /v1/traces/<id> with a server-side
        decomposition for the slowest requests in the artifact;
      * a direct predict with a KNOWN traceparent echoes it in the
        response header + meta.trace, and the stored trace carries every
        predict span kind (parse/admission/queue.wait/batch.form/
        batch.pad/batch.exec/debatch/respond + executor.*) with the
        decomposition summing to the request wall clock within 5%;
      * a multi-token :generate trace carries prefill + per-token
        decode.step spans (iteration accounting) under the same 5% sum
        contract;
      * SLO burn-rate gauges + good/bad counters appear on /metrics.

    Artifact: trace_sample.json (the full predict + generate traces).
    """
    import urllib.request

    model_dir = os.path.join(args.out_dir, "demo_model")
    env = {"FLAGS_trace_requests": "1",
           "FLAGS_serving_slo_ms": "demo=2000,gendemo=10000"}
    server = Server(model_dir,
                    ["--buckets", "1,2,4,8", "--max-wait-ms", "4",
                     "--demo-generation", "gendemo", "--gen-slots", "4"],
                    extra_env=env)
    try:
        # -- correlation loop via loadgen --trace -----------------------
        rec = run_loadgen(
            server.url, os.path.join(args.out_dir, "loadgen_trace.json"),
            60, 6, "1,2,3", extra=["--trace"])
        assert rec["errors"] == 0, rec
        st = rec.get("slow_traces")
        assert st, "loadgen --trace produced no slow_traces"
        resolved = [t for t in st
                    if (t.get("server") or {}).get("decomposition")]
        assert resolved, f"no slow trace resolved server-side: {st}"
        print(f"tracing correlation OK: {len(resolved)}/{len(st)} "
              f"slowest-request decompositions resolved via /v1/traces",
              flush=True)

        def fetch_trace(tid):
            with urllib.request.urlopen(
                    f"{server.url}/v1/traces/{tid}", timeout=10) as r:
                return json.loads(r.read())

        def assert_sum(tr, client_ms, label):
            dec = tr["decomposition"]
            total = dec["total_ms"]
            s = sum(dec["components_ms"].values())
            tol = 0.05 * total + 0.5  # 5% + scheduling-jitter floor
            assert abs(s + dec["unattributed_ms"] - total) <= tol, \
                (label, dec)
            assert dec["unattributed_ms"] <= tol, \
                (f"{label}: {dec['unattributed_ms']}ms unattributed of "
                 f"{total}ms", dec)
            assert total <= client_ms + 1.0, \
                (f"{label}: server total exceeds client wall", total,
                 client_ms)

        # -- direct predict with a KNOWN traceparent --------------------
        ptid = "ab" * 16
        body = json.dumps({"inputs": {"x": [[0.5] * 32] * 3}}).encode()
        req = urllib.request.Request(
            f"{server.url}/v1/models/demo:predict", data=body,
            headers={"Content-Type": "application/json",
                     "traceparent": f"00-{ptid}-{'12' * 8}-01"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=30) as r:
            hdr = dict(r.getheaders())
            payload = json.loads(r.read())
        predict_client_ms = (time.perf_counter() - t0) * 1e3
        assert ptid in (hdr.get("traceparent") or ""), hdr
        assert payload["batch"]["trace"]["trace_id"] == ptid, payload
        ptrace = fetch_trace(ptid)
        kinds = {s["name"] for s in ptrace["spans"]}
        need = {"parse", "admission", "queue.wait", "batch.form",
                "batch.pad", "batch.exec", "debatch", "respond"}
        assert need <= kinds, f"predict spans missing: {need - kinds}"
        assert kinds & {"executor.run", "executor.compile"}, kinds
        assert_sum(ptrace, predict_client_ms, "predict")
        pad = ptrace["decomposition"]["padding"]
        assert pad["rows_real"] == 3 and pad["bucket"] == 4 \
            and pad["rows_padded"] == 1, pad
        print(f"predict trace OK: {len(ptrace['spans'])} spans, "
              f"total {ptrace['decomposition']['total_ms']}ms, "
              f"unattributed "
              f"{ptrace['decomposition']['unattributed_ms']}ms, "
              f"padding {pad['rows_padded']}/{pad['bucket']}", flush=True)

        # -- multi-token generation trace -------------------------------
        gtid = "cd" * 16
        t0 = time.perf_counter()
        gen = http_generate(server.url, [3, 5, 7], 16,
                            headers={"traceparent":
                                     f"00-{gtid}-{'34' * 8}-01"})
        gen_client_ms = (time.perf_counter() - t0) * 1e3
        gtrace = fetch_trace(gtid)
        gkinds = {s["name"] for s in gtrace["spans"]}
        gneed = {"parse", "admission", "queue.wait", "prefill",
                 "decode.step", "deliver", "respond"}
        assert gneed <= gkinds, f"generate spans missing: {gneed - gkinds}"
        steps = gtrace["decomposition"].get("decode_steps", 0)
        assert steps >= len(gen["tokens"]) >= 1, (steps, gen)
        assert_sum(gtrace, gen_client_ms, "generate")
        print(f"generation trace OK: {steps} decode iterations, "
              f"total {gtrace['decomposition']['total_ms']}ms, "
              f"ttft linked "
              f"{gtrace['spans'][0]['attrs'].get('ttft_ms')}ms",
              flush=True)

        # -- SLO burn-rate gauges on /metrics ---------------------------
        prom = scrape(server.url)
        for needed in ("serving_demo_slo_burn_rate_5m",
                       "serving_demo_slo_burn_rate_30m",
                       "serving_demo_slo_burn_rate_1h",
                       "serving_demo_slo_good_total",
                       "serving_gendemo_slo_burn_rate_5m"):
            assert needed in prom, f"{needed} missing from /metrics"
        print("SLO burn-rate gauges OK on /metrics", flush=True)

        sample = {
            "tool": "serving_smoke.tracing",
            "predict": ptrace,
            "generate": gtrace,
            "predict_client_ms": round(predict_client_ms, 3),
            "generate_client_ms": round(gen_client_ms, 3),
        }
        with open(os.path.join(args.out_dir, "trace_sample.json"),
                  "w") as f:
            json.dump(sample, f, indent=2)
        print("tracing gate OK: trace_sample.json archived", flush=True)
    finally:
        server.close()


def scrape(url: str) -> str:
    import urllib.request

    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
        return r.read().decode()


def _prom_scalar(text: str, name: str) -> float:
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0] == name:
            return float(parts[1])
    return 0.0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out-dir", default="ci_artifacts/serving")
    p.add_argument("--requests", type=int, default=300,
                   help="smoke-phase request count")
    p.add_argument("--ab-requests", type=int, default=200,
                   help="requests per A/B trial leg")
    p.add_argument("--concurrency", type=int, default=12)
    p.add_argument("--ab-target", type=float, default=2.0,
                   help="required batched/batch1 QPS ratio (best pair)")
    p.add_argument("--ab-trials", type=int, default=8,
                   help="max interleaved trial pairs (early exit on "
                        "target; the budget is sized for noisy shared "
                        "CI boxes where absolute QPS swings ~2x between "
                        "trials — a clean pair usually lands by trial 2)")
    p.add_argument("--gen-ab-target", type=float, default=2.0,
                   help="required concurrent/sequential tokens-per-sec "
                        "ratio for the continuous-batching generation "
                        "gate")
    p.add_argument("--skip-generation", action="store_true",
                   help="skip the generation continuous-batching gate")
    p.add_argument("--skip-overload", action="store_true",
                   help="skip the overload/graceful-drain robustness gate")
    p.add_argument("--skip-tracing", action="store_true",
                   help="skip the request-scoped tracing gate")
    args = p.parse_args(argv)

    os.makedirs(args.out_dir, exist_ok=True)
    model_dir = os.path.join(args.out_dir, "demo_model")
    if not os.path.exists(os.path.join(model_dir, "__model__")):
        export_demo_model(model_dir)

    # The A/B capacity is PINNED by injected per-batch latency
    # (chaos.maybe_serve_latency) on BOTH servers, so the gate ratio is a
    # property of the batching policy, not the CI box: batch1 executes
    # one row per AB_CHAOS_LAT_S (~1/L rows/s) while the batched server
    # coalesces ~concurrency rows into one L-cost batch — the expected
    # ratio is ~min(concurrency, max_batch) >> the 2x target.  (The
    # uninjected gate measured 1.2x-3.3x for the same build across
    # boxes — CHANGES.md PR 13's known box-dependence, resolved here.)
    AB_CHAOS_LAT_S = 0.04
    ab_env = {"FLAGS_chaos": "1",
              "FLAGS_chaos_serve_latency_s": str(AB_CHAOS_LAT_S)}
    policy = ["--buckets", "1,2,4,8,16", "--max-wait-ms", "4"]

    # -- phase 1: shape-varying smoke against an UNARMED server ---------
    # (its own instance: the chaos pin below must not pollute the
    # archived smoke latencies — loadgen_smoke.json measures the real
    # serving path, so a real-latency regression stays visible)
    smoke_srv = Server(model_dir, policy)
    try:
        smoke = run_loadgen(
            smoke_srv.url, os.path.join(args.out_dir, "loadgen_smoke.json"),
            args.requests, args.concurrency, "1,2,3,4")
        assert smoke["errors"] == 0, smoke
        assert smoke["latency_ms"]["p99"] > 0, smoke
        sm = smoke["server_metrics"]
        assert sm["executor_compiles_during_load"] == 0, \
            f"recompile during shape-varying load: {sm}"
        assert sm["unplanned_compiles"] == 0, sm
        assert sm["batch_fill_mean"] is not None, sm
        prom = scrape(smoke_srv.url)
        for needed in ("serving_demo_request_seconds_bucket",
                       "serving_demo_batch_fill_bucket",
                       "serving_demo_queue_seconds_bucket"):
            assert needed in prom, f"{needed} missing from /metrics"
        print(f"serving smoke OK: {smoke['completed']} requests, "
              f"qps={smoke['qps']} p99={smoke['latency_ms']['p99']}ms "
              f"fill={sm['batch_fill_mean']} recompiles=0", flush=True)
    finally:
        smoke_srv.close()

    batched = Server(model_dir, policy, extra_env=ab_env)
    batch1 = Server(model_dir, policy + ["--max-batch", "1"],
                    extra_env=ab_env)
    try:
        # -- phase 2: batched vs batch-size-1 A/B (single-row stream) ---
        trials = []
        best = None
        for t in range(args.ab_trials):
            b = run_loadgen(
                batched.url,
                os.path.join(args.out_dir, "loadgen_batched.json"),
                args.ab_requests, args.concurrency, "1")
            s = run_loadgen(
                batch1.url,
                os.path.join(args.out_dir, "loadgen_batch1.json"),
                args.ab_requests, args.concurrency, "1")
            for rec in (b, s):
                assert rec["errors"] == 0, rec
                assert rec["server_metrics"][
                    "executor_compiles_during_load"] == 0, rec
            ratio = b["qps"] / max(s["qps"], 1e-9)
            trials.append({
                "trial": t, "batched_qps": b["qps"],
                "batch1_qps": s["qps"], "ratio": round(ratio, 3),
                "batched_fill": b["server_metrics"]["batch_fill_mean"],
                "batched_batches": b["server_metrics"]["batches"],
            })
            print(f"A/B trial {t}: batched {b['qps']} qps vs batch1 "
                  f"{s['qps']} qps -> {ratio:.2f}x", flush=True)
            if best is None or ratio > best["ratio"]:
                best = trials[-1]
            if ratio >= args.ab_target:
                break
            time.sleep(1.0)  # let a noisy-neighbour burst pass

        summary = {
            "tool": "serving_smoke",
            "policy": {"buckets": [1, 2, 4, 8, 16], "max_wait_ms": 4.0,
                       "batched_max_batch": 16, "batch1_max_batch": 1},
            "pinned_batch_latency_s": AB_CHAOS_LAT_S,
            "pinned_batch1_capacity_qps": round(1.0 / AB_CHAOS_LAT_S, 1),
            "ab_requests": args.ab_requests,
            "concurrency": args.concurrency,
            "target_ratio": args.ab_target,
            "trials": trials,
            "best": best,
            "passed": best["ratio"] >= args.ab_target,
        }
        with open(os.path.join(args.out_dir, "ab_summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
        print(json.dumps(summary["best"], indent=2))
        if not summary["passed"]:
            print(f"serving A/B gate FAILED: best ratio "
                  f"{best['ratio']}x < {args.ab_target}x "
                  f"across {len(trials)} trials", file=sys.stderr)
            return 1
        print(f"serving A/B gate OK: dynamic batching {best['ratio']}x "
              f"over batch-size-1 at zero recompiles", flush=True)
    finally:
        batched.close()
        batch1.close()

    # -- phase 4: overload shedding + deadline drops + graceful drain ----
    if not args.skip_overload:
        overload_gate(args)

    # -- phase 5: continuous token-level batching (generation tier) ------
    if not args.skip_generation:
        generation_gate(args)

    # -- phase 6: request-scoped tracing + SLO burn rates ----------------
    if not args.skip_tracing:
        tracing_gate(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
