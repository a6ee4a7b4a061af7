#!/usr/bin/env bash
# CI entry (reference role: paddle/scripts/paddle_build.sh — cmake_gen:58,
# run_test:408).  A CPU gate, never a chip measurement: every step runs
# with JAX_PLATFORMS=cpu (exported below), its timings are diffed against
# committed CPU baselines, and its servers/fleets are CPU processes.  The
# chip check is `python chip_smoke.py` through the chip tool.
# Runs the full validation ladder on a plain CPU host:
#   1. lint/format gate (ruff or pyflakes when available, else a
#      compile-all syntax sweep — the gate must exist on a bare image)
#      + repo-specific AST rules (tools/lint_rules.py: every FLAGS_* read
#      declared in flags.py, no host clock reads inside kernels/)
#   2. graph-lint gate: the static-analysis tier (tools/graph_lint.py)
#      over the FULL model matrix incl. the serving bucket-ladder/AOT
#      programs + the Pallas kernel plan linter; fails on ANY finding and
#      archives ci_artifacts/graph_lint.json
#   3. full test suite on the virtual 8-device CPU mesh
#   4. bench smoke (tiny shapes, CPU) with telemetry,
#      flight recorder, and metrics-snapshot artifacts
#   5. bench regression sentry: tools/bench_diff.py diffs every archived
#      smoke artifact against the committed baselines under
#      ci_artifacts/baselines/ (noise-aware: runs[] envelopes + rel-tol;
#      regression only when envelopes separate), asserts every record
#      carries a provenance block, and proves the gate can go RED by
#      chaos-injecting per-token latency into a decode re-run
#   6. chaos kill-and-resume fault-tolerance gate
#   7. numerics observability gate: a chaos-poisoned op output (a REAL
#      NaN in the compiled graph) must trip the watchdog and the
#      FLAGS_check_numerics=locate capture/replay must NAME the injected
#      op in the flight dump — tools/numerics_smoke.py, artifacts under
#      ci_artifacts/numerics/
#   8. serving smoke gate: export a model, boot the inference server,
#      drive tools/loadgen.py — p99/batch-fill histograms on /metrics,
#      zero recompiles across a shape-varying stream, the dynamic-
#      batching A/B (batched >= 2x batch-size-1 QPS), the OVERLOAD gate
#      (open-loop flood at ~4x measured capacity vs a chaos-armed
#      server: 429 shedding + Retry-After, expired-deadline drops before
#      dispatch, zero crash-5xx, bounded accepted p99, flat compile
#      counter, and a mid-load SIGTERM graceful drain exiting 0 with a
#      drain-trigger flight dump — overload_smoke.json), and the
#      generation continuous-batching gate (late joins without
#      retrace/stall, concurrent streams >= 2x batch-1 decode tokens/sec)
#   9. router smoke gate: a 3-replica supervised fleet behind the
#      scale-out router survives a chaos SIGKILL mid-flood with zero
#      non-429 client errors (failover + evict/readmit + crash restart)
#      and < 5ms p50 router tax — tools/router_smoke.py,
#      ci_artifacts/serving/router_smoke.json
#  10. compile-check + multichip dryrun (the driver's graft contract)
# Usage: tools/run_ci.sh [fast]   — "fast" skips the bench smoke.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu  # a CPU gate: never holds, never measures a chip

echo "== [1/10] lint gate"
if command -v ruff >/dev/null 2>&1; then
  ruff check paddle_tpu tools tests bench.py __graft_entry__.py
elif python -c 'import pyflakes' >/dev/null 2>&1; then
  python -m pyflakes paddle_tpu tools tests bench.py __graft_entry__.py
else
  echo "-- no ruff/pyflakes in image; falling back to compileall"
  python -m compileall -q paddle_tpu tools tests bench.py __graft_entry__.py
fi
python tools/lint_rules.py

echo "== [2/10] graph-lint gate (static analysis over the model matrix)"
mkdir -p ci_artifacts
JAX_PLATFORMS=cpu python tools/graph_lint.py \
  --out ci_artifacts/graph_lint.json
echo "-- graph-lint findings artifact: ci_artifacts/graph_lint.json"

echo "== [3/10] test suite (virtual 8-device CPU mesh)"
python -m pytest tests/ -q

if [[ "${1:-}" != "fast" ]]; then
  echo "== [4/10] bench smoke (telemetry on; snapshot + flight artifacts)"
  mkdir -p ci_artifacts
  rm -f ci_artifacts/bench_steps.jsonl  # StepMonitor appends; keep one run
  rm -rf ci_artifacts/flight && mkdir -p ci_artifacts/flight
  # Warnings gate: any Python UserWarning raised during the smoke (e.g.
  # jnp's int64-truncation warning that once fired per trace) FAILS the
  # step.  Allowlist a known-benign warning by appending another filter
  # AFTER the error one (later -W filters take precedence):
  #   -W "ignore:exact message prefix:UserWarning"
  # The JSON metric lines land in ci_artifacts/bench_smoke.json — the
  # per-workload record (runs[]/spread fields) used for A/B comparisons.
  FLAGS_monitor=1 FLAGS_monitor_jsonl=ci_artifacts/bench_steps.jsonl \
    FLAGS_flight_dir=ci_artifacts/flight \
    python -W error::UserWarning bench.py --smoke \
      --monitor-snapshot ci_artifacts/metrics.prom \
    | tee ci_artifacts/bench_smoke.json
  echo "-- A/B bench record artifact: ci_artifacts/bench_smoke.json ($(grep -c '' ci_artifacts/bench_smoke.json) records, streamed above)"
  # conv+BN microbench leg (PERF.md r07 per-lever A/B): tiny shapes under
  # the same warnings gate; the JSON record sits next to bench_smoke.json
  python -W error::UserWarning bench.py --model convbn --smoke \
    | tee ci_artifacts/bench_convbn_smoke.json
  echo "-- convbn A/B record artifact: ci_artifacts/bench_convbn_smoke.json"
  # DeepFM sparse-tier leg (PERF.md r08 A/B): the fused multi-table
  # embedding record next to its FLAGS_fused_embedding=0 per-slot
  # baseline, both under the warnings gate; the paired records (config
  # carries the flag + runs[]/spread) are the launch-collapse A/B artifact
  python -W error::UserWarning bench.py --model deepfm --smoke \
    | tee ci_artifacts/bench_deepfm_smoke.json
  FLAGS_fused_embedding=0 python -W error::UserWarning bench.py \
    --model deepfm --smoke | tee -a ci_artifacts/bench_deepfm_smoke.json
  python - <<'PY'
import json
recs = [json.loads(l) for l in open("ci_artifacts/bench_deepfm_smoke.json")
        if l.strip().startswith("{")]
recs = [r for r in recs if r.get("metric", "").startswith("deepfm")]
flags = {r["config"]["fused_embedding"] for r in recs}
assert flags == {True, False}, f"need a fused AND an unfused record: {flags}"
print("deepfm A/B records OK:", [(r["config"]["fused_embedding"],
                                  r["value"]) for r in recs])
PY
  echo "-- deepfm A/B record artifact: ci_artifacts/bench_deepfm_smoke.json"
  # Recompute A/B leg (PERF.md r12 / ISSUE 15): the activation-recompute
  # rewrite paired against the plain record — the rewritten record must
  # carry a LOWER planner activation peak and the est FLOPs factor, and
  # every dense record now carries activation_peak_bytes (planner) +
  # memory_analysis_peak_bytes (XLA ground truth), both under the
  # warnings gate
  python -W error::UserWarning bench.py --model transformer --smoke \
    --recompute | tee ci_artifacts/bench_recompute_smoke.json
  python -W error::UserWarning bench.py --model transformer --smoke \
    | tee -a ci_artifacts/bench_recompute_smoke.json
  python - <<'PY'
import json
recs = [json.loads(l) for l in open("ci_artifacts/bench_recompute_smoke.json")
        if l.strip().startswith("{")]
recs = [r for r in recs if r.get("metric", "").startswith("transformer")]
flags = {r["config"]["recompute"] for r in recs}
assert flags == {True, False}, f"need a recompute AND a plain record: {flags}"
for r in recs:
    assert "activation_peak_bytes" in r["config"], r["config"]
    assert "memory_analysis_peak_bytes" in r["config"], r["config"]
rc = next(r for r in recs if r["config"]["recompute"])
plain = next(r for r in recs if not r["config"]["recompute"])
assert rc["config"]["activation_peak_bytes"] \
    < plain["config"]["activation_peak_bytes"], (rc, plain)
# the <= 1.35 FLOPs bar is a transformer-BASE property (gated in
# graph_lint's memory builder + tests/test_memory.py); the tiny smoke
# model is less matmul-dominant, so this leg only sanity-bounds it
assert rc["config"]["recompute_flops_ratio"] <= 1.5, rc["config"]
print("recompute A/B records OK:",
      [(r["config"]["recompute"], r["config"]["activation_peak_bytes"],
        r["value"]) for r in recs])
PY
  echo "-- recompute A/B record artifact: ci_artifacts/bench_recompute_smoke.json"
  # Memory report (ISSUE 15 satellite): planner table + memory_analysis
  # ground-truth columns + the donated-param entry-copy row, archived
  # like the copy census
  python tools/hlo_diag.py transformer_smoke \
    ci_artifacts/hlo_memory_probe.txt --memory | tail -25
  rm -f ci_artifacts/hlo_memory_probe.txt  # keep the memory JSON
  echo "-- memory report artifact:"
  ls ci_artifacts/*.memory.json
  # Decode generation leg (PERF.md r10): tokens/sec at two batch sizes
  # through the KV-cache + flash-decode path, paired with the
  # FLAGS_kv_cache=0 full-prefix-recompute baseline record; every record
  # must carry compile_flat=true — the executor compile cache may NOT
  # grow across generated tokens (the length-independent-key contract)
  python -W error::UserWarning bench.py --model decode --smoke --runs 3 \
    | tee ci_artifacts/bench_decode_smoke.json
  FLAGS_kv_cache=0 python -W error::UserWarning bench.py \
    --model decode --smoke | tee -a ci_artifacts/bench_decode_smoke.json
  python - <<'PY'
import json
recs = [json.loads(l) for l in open("ci_artifacts/bench_decode_smoke.json")
        if l.strip().startswith("{")]
recs = [r for r in recs if r.get("metric", "").startswith("decode")]
flags = {r["config"]["kv_cache"] for r in recs}
assert flags == {True, False}, f"need a cached AND a recompute record: {flags}"
bad = [r for r in recs if not r["config"]["compile_flat"]]
assert not bad, f"executor compile cache grew across generated tokens: {bad}"
# megastep gate (PERF.md r15): the cached run emits fused/unfused PAIRS;
# at batch 1 the fused decode program may not lose to the unfused one.
# Noise-aware like bench_diff: red only when the run envelopes SEPARATE
# (best fused repeat below the worst unfused repeat) — CPU-box b1
# tokens/sec jitters +-15% run to run
cached = [r for r in recs if r["config"]["kv_cache"]]
pairs = {r["metric"]: r for r in cached}
fused = pairs.get("decode_tokens_per_sec_b1")
unfused = pairs.get("decode_tokens_per_sec_b1_unfused")
assert fused is not None and unfused is not None, \
    f"need the fused/unfused b1 pair, have {sorted(pairs)}"
assert fused["config"]["fused_decode_step"] is True
assert unfused["config"]["fused_decode_step"] is False
assert max(fused["config"]["runs"]) >= min(unfused["config"]["runs"]), (
    f"fused decode LOST to unfused at b1 beyond noise: fused runs "
    f"{fused['config']['runs']} vs unfused {unfused['config']['runs']}")
print(f"decode megastep gate OK: fused b1 {fused['value']:.1f} vs "
      f"unfused {unfused['value']:.1f} tokens/sec "
      f"(runs {fused['config']['runs']} / {unfused['config']['runs']})")
# paged KV-cache capacity gate (ISSUE 20): at the fixed smoke HBM
# budget the paged layout must admit >= 2x the sequences the ring
# layout does (it charges blocks actually touched, not full rings),
# and the bench's resident-bytes claim must match the memory planner's
# kv_cache row (the hlo_diag --memory number) within 1%
paged = pairs.get("decode_tokens_per_sec_b1_paged")
assert paged is not None, f"need the paged b1 record, have {sorted(pairs)}"
assert paged["config"]["paged"] is True and paged["config"]["compile_flat"]
r_slots = fused["config"]["concurrent_slots_at_budget"]
p_slots = paged["config"]["concurrent_slots_at_budget"]
ratio = p_slots / max(r_slots, 1)
assert ratio >= 2.0, (
    f"paged capacity gate RED: {p_slots} paged vs {r_slots} ring slots "
    f"at {paged['config']['kv_budget_bytes']} bytes (ratio {ratio:.2f} "
    f"< 2.0)")
for rec in (fused, paged):
    resident = rec["config"]["kv_resident_gb"] * 1e9
    row = rec["config"]["planner_kv_cache_bytes"]
    assert abs(row - resident) <= 0.01 * resident, (
        f"planner kv_cache row {row} disagrees with bench resident "
        f"bytes {resident:.0f} ({rec['metric']})")
with open("ci_artifacts/kv_capacity_gate.json", "w") as f:
    json.dump({"ring_slots_at_budget": r_slots,
               "paged_slots_at_budget": p_slots,
               "capacity_ratio": round(ratio, 2),
               "budget_bytes": paged["config"]["kv_budget_bytes"],
               "ring_bytes_per_seq": fused["config"]["kv_bytes_per_seq"],
               "paged_bytes_per_seq": paged["config"]["kv_bytes_per_seq"],
               "paged_tokens_per_sec_per_hbm_gb":
                   paged["config"]["tokens_per_sec_per_hbm_gb"]}, f,
              indent=1)
print(f"paged capacity gate OK: {p_slots} paged vs {r_slots} ring "
      f"slots at budget (ratio {ratio:.2f} >= 2.0)")
print("decode A/B records OK:", [(r["config"]["kv_cache"], r["metric"],
                                  r["value"]) for r in recs])
PY
  echo "-- paged capacity gate artifact: ci_artifacts/kv_capacity_gate.json"
  echo "-- decode A/B record artifact: ci_artifacts/bench_decode_smoke.json"
  # Pipeline-parallel leg (PERF.md r11): pp=2 GPipe vs 1F1B vs single-
  # program run_accumulated on the CPU mesh — every pipeline record must
  # carry state_bit_parity=true (training state may not drift a BIT from
  # the unsplit program) and a fetched-loss trajectory within 1 ulp;
  # bench.py itself raises if they do not, this check keeps the archived
  # artifact honest
  python -W error::UserWarning bench.py --model transformer --pp 2 \
    --smoke | tee ci_artifacts/bench_pipeline_smoke.json
  # transformer-BASE widths (d_model 512, 6 layers; short seq), pp=2 AND
  # pp=4, dropout ON — the base-width pipeline parity gates
  python -W error::UserWarning bench.py --model transformer --pp 2 \
    | tee -a ci_artifacts/bench_pipeline_smoke.json
  python -W error::UserWarning bench.py --model transformer --pp 4 \
    | tee -a ci_artifacts/bench_pipeline_smoke.json
  python - <<'PY'
import json
recs = [json.loads(l) for l in open("ci_artifacts/bench_pipeline_smoke.json")
        if l.strip().startswith("{")]
recs = [r for r in recs if r.get("metric", "").startswith("transformer_pp")]
groups = {}
for r in recs:
    groups.setdefault((r["config"]["pp"], r["config"]["tiny"]),
                      set()).add(r["config"]["schedule"])
assert (2, True) in groups and (2, False) in groups \
    and (4, False) in groups, f"missing pipeline legs: {sorted(groups)}"
for g, scheds in groups.items():
    assert scheds == {"single", "gpipe", "1f1b"}, (g, scheds)
bad = [r["metric"] for r in recs
       if r["config"]["schedule"] != "single"
       and (r["config"]["state_bit_parity"] is not True
            or r["config"]["loss_max_rel_diff"] > 3e-7)]
assert not bad, f"pipeline schedules lost parity: {bad}"
print("pipeline records OK:",
      [(r["config"]["pp"], r["config"]["tiny"], r["config"]["schedule"],
        r["value"]) for r in recs])
PY
  echo "-- pipeline A/B record artifact: ci_artifacts/bench_pipeline_smoke.json"
  # Numerics-observability overhead leg (PERF.md r13): transformer smoke
  # with FLAGS_check_numerics=summary (fused per-param-group stats
  # reductions + one packed [N,4] fetch per step) paired against the
  # plain record, both under the warnings gate.  The <3% bar is gated in
  # PERF.md from a quiet-box measurement; CI only requires the summary
  # record within 15% of the plain one (CPU boxes are noisy) and prints
  # the measured delta for the archived pair.
  python -W error::UserWarning bench.py --model transformer --smoke \
    | tee ci_artifacts/bench_numerics_smoke.json
  FLAGS_check_numerics=summary FLAGS_monitor=1 \
    python -W error::UserWarning bench.py --model transformer --smoke \
    | tee -a ci_artifacts/bench_numerics_smoke.json
  python - <<'PY'
import json
recs = [json.loads(l) for l in open("ci_artifacts/bench_numerics_smoke.json")
        if l.strip().startswith("{")]
recs = [r for r in recs if r.get("metric", "").startswith("transformer")]
by = {r["provenance"]["flags"].get("check_numerics", "off"): r
      for r in recs}
assert set(by) == {"off", "summary"}, \
    f"need an off AND a summary record: {sorted(by)}"
overhead = 1.0 - by["summary"]["value"] / by["off"]["value"]
assert overhead < 0.15, \
    f"check_numerics=summary cost {overhead:.1%} tokens/sec (>15%)"
print(f"numerics A/B records OK: off={by['off']['value']} "
      f"summary={by['summary']['value']} (overhead {overhead:+.2%})")
PY
  echo "-- numerics A/B record artifact: ci_artifacts/bench_numerics_smoke.json"
  # Dispatch microbench (ISSUE 16): per-launch overhead of a cache-hit
  # exe.run — the measured launch constant the static cost model's
  # roofline attribution charges per op (analysis/costmodel.py)
  python -W error::UserWarning bench.py --model dispatch --smoke \
    | tee ci_artifacts/bench_dispatch_smoke.json
  echo "-- dispatch overhead artifact: ci_artifacts/bench_dispatch_smoke.json"
  # Copy census (PERF.md r09 attribution artifact): the automated
  # while-body copy-byte attribution on the smoke transformer; CI
  # archives the JSON for the record
  python tools/hlo_diag.py transformer_smoke \
    ci_artifacts/hlo_transformer_smoke.txt --copy-census \
    | tail -20
  rm -f ci_artifacts/hlo_transformer_smoke_*.txt  # keep the census JSONs
  echo "-- copy-census artifacts:"
  ls ci_artifacts/*.census.json
  # Donated-param entry-copy repro ladder (PERF.md r09): archives the
  # per-variant aliasing/entry-copy report — a CPU box documents the
  # negative result; the driver's chip run pinpoints the culprit rung
  JAX_PLATFORMS=cpu python tools/donation_repro.py \
    ci_artifacts/donation_repro.json
  echo "-- donation repro artifact: ci_artifacts/donation_repro.json"
  echo "-- metrics snapshot:"
  head -40 ci_artifacts/metrics.prom || true
  echo "-- flight record (black box of the smoke run):"
  ls ci_artifacts/flight/
  head -3 ci_artifacts/flight/flight-*-atexit.jsonl || true
fi

if [[ "${1:-}" != "fast" ]]; then
  echo "== [5/10] bench regression sentry (diff vs committed baselines)"
  # Provenance contract (ISSUE 16 satellite): every archived record must
  # say which commit/flags/jax produced it, or the baseline ledger is
  # unreviewable.
  python - <<'PY'
import glob, json
for path in sorted(glob.glob("ci_artifacts/bench_*_smoke.json")) \
        + ["ci_artifacts/bench_smoke.json"]:
    for line in open(path):
        if not line.strip().startswith("{"):
            continue
        rec = json.loads(line)
        p = rec.get("provenance")
        assert p and "git_commit" in p and "flags" in p and "jax" in p, \
            f"{path}: record {rec.get('metric')} lacks a provenance block"
print("provenance blocks OK across all archived smoke artifacts")
PY
  # Noise-aware diff of every archived smoke artifact against the
  # committed baseline ledger.  rel-tol 0.50: CI boxes differ from the
  # baseline box; the runs[]-envelope + 50% padding only separates on
  # real cliffs (the chaos demo below injects -95% and is caught), so a
  # red here is a finding, not weather.  Refresh protocol: rerun the
  # smoke legs on a quiet box and copy the artifacts over
  # ci_artifacts/baselines/ in the SAME commit as an intended perf
  # change.
  for a in bench_smoke bench_convbn_smoke bench_deepfm_smoke \
           bench_recompute_smoke \
           bench_decode_smoke bench_pipeline_smoke bench_dispatch_smoke \
           bench_numerics_smoke
  do
    python tools/bench_diff.py ci_artifacts/baselines/$a.json \
      ci_artifacts/$a.json --rel-tol 0.50
  done
  # RED-gate demo: chaos-inject 20ms per decoded token and require the
  # sentry to fail NAMING the regressed (workload, metric) pair — proof
  # the gate can actually fire, not just pass.
  FLAGS_chaos=1 FLAGS_chaos_serve_latency_s=0.02 \
    python bench.py --model decode --smoke \
    > ci_artifacts/bench_decode_chaos.json
  set +e
  python tools/bench_diff.py ci_artifacts/baselines/bench_decode_smoke.json \
    ci_artifacts/bench_decode_chaos.json --rel-tol 0.50 \
    | tee ci_artifacts/bench_diff_red.txt
  rc=${PIPESTATUS[0]}
  set -e
  if [[ $rc -ne 1 ]]; then
    echo "bench_diff red-gate demo: expected exit 1, got rc=$rc"
    exit 1
  fi
  grep -q "REGRESSION (decode, decode_tokens_per_sec_b1)" \
    ci_artifacts/bench_diff_red.txt
  echo "-- sentry red-gate demo OK (chaos-injected decode regression caught by name)"
fi

if [[ "${1:-}" != "fast" ]]; then
  echo "== [6/10] chaos smoke: kill-and-resume fault-tolerance gate"
  # A training subprocess is SIGKILLed mid-run by the chaos harness, then
  # resumed from the latest verifiable checkpoint; the gate passes when the
  # resumed run reports a non-zero start step and finishes.  Artifacts: the
  # recovered run's checkpoint MANIFEST.json + flight record.
  rm -rf ci_artifacts/chaos && mkdir -p ci_artifacts/chaos/flight
  set +e
  JAX_PLATFORMS=cpu FLAGS_chaos=1 FLAGS_chaos_kill_at_step=6 \
    FLAGS_flight_dir=ci_artifacts/chaos/flight \
    python tools/chaos_train.py --ckpt-dir ci_artifacts/chaos/ckpt \
      --steps 10 --interval 3 > ci_artifacts/chaos/killed_run.json
  rc=$?
  set -e
  if [[ $rc -ne 137 ]]; then
    echo "chaos gate: expected the run to be SIGKILLed (rc 137), got rc=$rc"
    exit 1
  fi
  JAX_PLATFORMS=cpu FLAGS_flight_dir=ci_artifacts/chaos/flight \
    python tools/chaos_train.py --ckpt-dir ci_artifacts/chaos/ckpt \
      --steps 10 --interval 3 | tee ci_artifacts/chaos/resumed_run.json
  python - <<'PY'
import glob, json
rec = json.loads(open("ci_artifacts/chaos/resumed_run.json").read().strip().splitlines()[-1])
assert rec["start"] > 0, f"resume did not pick up a checkpoint: {rec}"
man = max(glob.glob("ci_artifacts/chaos/ckpt/ckpt-*/MANIFEST.json"),
          key=lambda p: int(p.split("ckpt-")[-1].split("/")[0]))
m = json.load(open(man))
print(f"chaos gate OK: resumed at step {rec['start']}, "
      f"latest manifest step {m['step']} trigger {m['trigger']!r}")
PY
  echo "-- recovered manifest artifact:"
  ls ci_artifacts/chaos/ckpt
fi

echo "== [7/10] numerics observability gate (NaN-origin locate red-gate)"
# A REAL NaN is chaos-injected at one known op output in the compiled
# graph; the gate passes only when the watchdog-tripped locate replay
# NAMES that op in the flight dump — under the same warnings gate as the
# bench legs.  Runs in fast mode too: it is seconds of CPU work and it
# is THE proof the tier's flagship path works end to end.
rm -rf ci_artifacts/numerics
JAX_PLATFORMS=cpu python -W error::UserWarning tools/numerics_smoke.py \
  --out-dir ci_artifacts/numerics
echo "-- numerics gate artifacts:"
ls ci_artifacts/numerics/ ci_artifacts/numerics/flight/

if [[ "${1:-}" != "fast" ]]; then
  echo "== [8/10] serving smoke: dynamic-batching inference gate"
  # Exports a demo model, boots two inference servers (batched + forced
  # --max-batch 1), and drives tools/loadgen.py through both:
  #   * a shape-varying stream must finish with the executor compile
  #     counter FLAT (warm bucket ladder, zero recompiles) and the
  #     request-latency p99 / batch-fill histograms on /metrics;
  #   * the A/B: dynamic batching must serve >= 2x the QPS of
  #     batch-size-1 mode on the same single-row stream — BOTH servers
  #     chaos-latency-pinned (FLAGS_chaos_serve_latency_s) so capacity
  #     is set by the injected per-batch cost, not the CI box
  #     (box-independent gate; interleaved trial pairs still absorb
  #     noisy-neighbour variance);
  #   * the overload gate: ~4x-capacity open-loop flood vs a
  #     chaos-latency-armed bounded-queue server — shedding engaged
  #     (429 + Retry-After), expired_dropped_total > 0 (deadline drops
  #     before dispatch, asserted via /metrics delta), zero crash-5xx,
  #     accepted p99 under the stated bound, compile counter FLAT; then
  #     SIGTERM mid-load drains in-flight work and exits 0 with a
  #     drain-trigger flight dump;
  #   * the tracing gate: a FLAGS_trace_requests server echoes the
  #     client traceparent, serves /v1/traces span trees for predict +
  #     generation, exposes SLO burn-rate gauges, and closes the
  #     loadgen --trace correlation loop (trace_sample.json).
  # Artifacts: ci_artifacts/serving/loadgen_*.json + ab_summary.json
  #            + overload_smoke.json + trace_sample.json (+ flight/).
  rm -rf ci_artifacts/serving && mkdir -p ci_artifacts/serving
  JAX_PLATFORMS=cpu python tools/serving_smoke.py \
    --out-dir ci_artifacts/serving
  # Trace-sample contract: every span kind present across the archived
  # predict+generate traces, and each decomposition must SUM to the
  # measured end-to-end latency within tolerance (5% + 0.5ms jitter
  # floor) — the "why was this request slow" story stays trustworthy.
  python - <<'PY'
import json
d = json.load(open("ci_artifacts/serving/trace_sample.json"))
kinds = set()
for key in ("predict", "generate"):
    tr = d[key]
    dec = tr["decomposition"]
    total = dec["total_ms"]
    s = sum(dec["components_ms"].values())
    tol = 0.05 * total + 0.5
    assert abs(s + dec["unattributed_ms"] - total) <= tol, (key, dec)
    assert dec["unattributed_ms"] <= tol, (key, dec)
    kinds |= {sp["name"] for sp in tr["spans"]}
need = {"parse", "admission", "queue.wait", "batch.form", "batch.pad",
        "batch.exec", "debatch", "respond", "prefill", "decode.step",
        "deliver", "executor.run"}
missing = need - kinds
assert not missing, f"span kinds missing from trace sample: {missing}"
print(f"trace sample OK: decompositions sum within tolerance; "
      f"{len(kinds)} span kinds present")
PY
  echo "-- serving artifacts:"
  ls ci_artifacts/serving/
fi

if [[ "${1:-}" != "fast" ]]; then
  echo "== [9/10] router smoke: scale-out fleet fault-tolerance gate"
  # A 3-replica supervised fleet behind the router survives a chaos
  # SIGKILL mid-flood (FLAGS_chaos_kill_replica_after arms one replica):
  # zero non-429 client-visible errors, failover_total > 0, the victim
  # is evicted AND re-admitted (flight events), the supervisor's crash
  # restart brings it back, and the router's proxy tax stays < 5 ms p50
  # over direct-to-replica at --max-batch 1.
  mkdir -p ci_artifacts/serving
  JAX_PLATFORMS=cpu python tools/router_smoke.py \
    --out-dir ci_artifacts/serving
  echo "-- router fleet artifact: ci_artifacts/serving/router_smoke.json"
fi

echo "== [10/10] entry compile-check + multichip dryrun"
python __graft_entry__.py

echo "CI OK"
