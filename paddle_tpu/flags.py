"""Typed runtime flags with environment overrides (reference: the gflags
surface — ~50 FLAGS_* defined at point-of-use, e.g.
FLAGS_check_nan_inf operator.cc:943, FLAGS_fraction_of_gpu_memory_to_use
gpu_info.cc, FLAGS_allocator_strategy allocator_strategy.cc — plus the
Python bootstrap that whitelists FLAGS_* env vars into gflags,
python/paddle/fluid/__init__.py:95-170 __bootstrap__).

TPU-first: one typed registry (SURVEY §5.6 plan) instead of scattered
globals.  Flags are declared with a type + default + help; values resolve
in priority order CLI-set < env (`FLAGS_<name>`) < programmatic set_flag.
`paddle_tpu.flags.FLAGS.<name>` reads; unknown names raise.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


_PARSERS: Dict[type, Callable[[str], Any]] = {
    bool: _parse_bool,
    int: lambda s: int(s, 0),
    float: float,
    str: str,
}


class _FlagDef:
    __slots__ = ("name", "type", "default", "help")

    def __init__(self, name, type_, default, help_):
        self.name = name
        self.type = type_
        self.default = default
        self.help = help_


class _Flags:
    def __init__(self):
        object.__setattr__(self, "_defs", {})
        object.__setattr__(self, "_values", {})

    def define(self, name: str, type_: type, default, help_: str = ""):
        if name in self._defs:
            raise ValueError(f"flag {name!r} already defined")
        if type_ not in _PARSERS:
            raise TypeError(f"unsupported flag type {type_!r}")
        self._defs[name] = _FlagDef(name, type_, default, help_)

    def __getattr__(self, name):
        defs = object.__getattribute__(self, "_defs")
        if name not in defs:
            raise AttributeError(f"unknown flag {name!r}")
        values = object.__getattribute__(self, "_values")
        if name in values:
            return values[name]
        env = os.environ.get(f"FLAGS_{name}")
        if env is not None:
            return _PARSERS[defs[name].type](env)
        return defs[name].default

    def __setattr__(self, name, value):
        self.set(name, value)

    def set(self, name, value):
        defs = object.__getattribute__(self, "_defs")
        if name not in defs:
            raise AttributeError(f"unknown flag {name!r}")
        d = defs[name]
        if not isinstance(value, d.type):
            value = _PARSERS[d.type](str(value))
        object.__getattribute__(self, "_values")[name] = value

    def reset(self, name=None):
        values = object.__getattribute__(self, "_values")
        if name is None:
            values.clear()
        else:
            values.pop(name, None)

    def help(self) -> str:
        defs = object.__getattribute__(self, "_defs")
        lines = []
        for d in sorted(defs.values(), key=lambda d: d.name):
            lines.append(
                f"FLAGS_{d.name} ({d.type.__name__}, default "
                f"{d.default!r}): {d.help}")
        return "\n".join(lines)


FLAGS = _Flags()

# -- the framework's flag surface (reference points cited per flag) ---------

FLAGS.define(
    "check_nan_inf", bool, False,
    "validate every op output for NaN/Inf and name the offending op "
    "(reference FLAGS_check_nan_inf, operator.cc:943)")
FLAGS.define(
    "check_numerics", str, "off",
    "numerics observability tier (analysis/numerics.py + "
    "monitor/numerics.py): 'off' = zero-cost (no graph change, "
    "byte-identical fingerprint), 'summary' = instrument grads / param "
    "updates / loss with one fused stats reduction per tensor, packed "
    "into a single [N,4] device->host fetch per step and published as "
    "per-param-group gauges (grad-norm, weight-norm, update-to-weight "
    "ratio), 'locate' = per-op-output instrumentation naming the first "
    "op in topological order with a non-finite output — the reference "
    "FLAGS_check_nan_inf rebuilt for whole-block XLA; also enables the "
    "watchdog's deterministic failing-step replay on a nan_loss trip")
FLAGS.define(
    "benchmark", bool, False,
    "synchronize after every executor call for stable timing "
    "(reference FLAGS_benchmark, operator.cc:938)")
FLAGS.define(
    "cpu_deterministic", bool, True,
    "kept for parity; determinism is free under XLA "
    "(reference FLAGS_cpu_deterministic)")
FLAGS.define(
    "eager_delete_tensor_gb", float, 0.0,
    "kept for parity; buffer lifetime is XLA's job "
    "(reference FLAGS_eager_delete_tensor_gb)")
FLAGS.define(
    "prefetch_chunk_mb", int, 32,
    "chunk size for double-buffer host->device transfers "
    "(reader/decorator.py device_put_chunked)")
FLAGS.define(
    "prefetch_threads", int, 4,
    "thread-pool width for chunked host->device transfers")
FLAGS.define(
    "synthetic_data", bool, False,
    "datasets yield synthetic offline samples (same as "
    "PADDLE_TPU_SYNTH_DATA=1)")
FLAGS.define(
    "hash_dropout", bool, True,
    "generate dropout masks with the fusible counter-based hash PRNG "
    "(kernels/hash_rng.py) instead of jax.random.bernoulli; the hash "
    "fuses into consumers so no random-bits tensor exists in HBM")
FLAGS.define(
    "tpu_prng_dropout", bool, True,
    "in-kernel dropout masks (flash attention weights-dropout, fused "
    "dropout-add epilogue) draw bits from the TPU hardware PRNG "
    "(pltpu.prng_seed/prng_random_bits, re-seeded per tile) instead of "
    "the lowbias32 hash chain; compiled-TPU only — interpret mode and "
    "the XLA fallbacks always use the hash (kernels/attention.py, "
    "kernels/dropout_epilogue.py)")
FLAGS.define(
    "fused_bn", bool, True,
    "NHWC training batch-norm runs the fused Pallas BN path "
    "(kernels/conv_bn.py): models emit one conv2d_bn op per "
    "conv->bn[->add->relu] chain (1x1 convs as a dot with a BN-stats "
    "epilogue; other convs keep XLA's conv with a one-pass stats kernel), "
    "and standalone NHWC batch_norm uses the one-pass stats + fused "
    "apply kernels with a backward that folds the dgamma/dbeta channel "
    "reductions into the dx pass; off = the reference conv2d + "
    "batch_norm composition with XLA's separate stat reductions "
    "(flag-off graphs are op-for-op identical to the pre-fusion ones)")
FLAGS.define(
    "fused_embedding", bool, True,
    "the sparse embedding tier coalesces same-shape per-slot lookup_table "
    "op groups into one fused multi-table gather launch (ids prefetched "
    "via scalar memory), their grads into one SelectedRows-compatible "
    "fused grad, and the per-table sgd/lazy-adam chains into one "
    "row-sparse group apply (kernels/embedding.py, passes.py "
    "fused_embedding pass; applied by models/deepfm.py); off = the "
    "reference per-slot composition, graphs op-for-op identical to the "
    "pre-fusion ones")
FLAGS.define(
    "kv_cache", bool, True,
    "autoregressive generation rides the KV-cache decode path "
    "(paddle_tpu/generation): prefill writes per-layer K/V into "
    "ring-buffer scope state [L, b, max_t, h, dh] threaded through the "
    "executor's donated rw-state machinery, and every generated token "
    "runs ONE compiled single-query decode program (dynamic-slice cache "
    "writes, length-independent compile key); models/transformer.py "
    "build_decoder carries the same cache through its beam-search While "
    "loop; off = the per-step full-prefix recompute route, output-"
    "identical (parity asserted in tests/test_generation.py)")
FLAGS.define(
    "fused_decode_step", bool, True,
    "cached_decoder_step lowers each decoder layer of the per-token "
    "decode program to ONE fused_decode_step op (kernels/decode_step.py "
    "per-layer Pallas megastep: qkv projection, in-place cache row write "
    "at the runtime counter, single-query online-softmax walk, output "
    "projection, residual+layer-norm epilogues — q/k/v and the attention "
    "context never exist in HBM), and greedy kv-cache decode programs "
    "self-feed the sampled token through scope state (the host stops "
    "round-tripping it); off = the reference per-layer composition "
    "(fc + kv_cache_update + decode_attention + fc + layer_norm chain), "
    "graphs op-for-op identical to the pre-fusion ones and parameter "
    "names unchanged (checkpoints interop); off-contract shapes run the "
    "numerically-identical XLA fallback inside the op")
FLAGS.define(
    "flash_decode", bool, True,
    "the decode_attention op lowers to the Pallas single-query flash-"
    "decode kernel (kernels/decode_attention.py: one q row against the "
    "HBM-resident growing cache, online softmax over DMA'd k/v blocks, "
    "per-sequence lengths scalar-prefetched so masked tail blocks are "
    "never read) when the plan gate accepts; off or off-contract = the "
    "numerically-identical XLA fallback")
FLAGS.define(
    "paged_kv_cache", bool, False,
    "generation programs allocate the KV cache as a paged block pool "
    "(generation/kv_cache.py PagedKVCache: a global [layers, blocks, "
    "block_t, heads, d_head] pool per side plus per-slot int32 block "
    "tables, free-list/ref-count allocator with copy-on-write append) "
    "instead of the contiguous ring buffer; decode attention and the "
    "fused megastep walk blocks through the table. Off (default) = the "
    "ring layout, byte-stable graphs, unchanged parameter names")
FLAGS.define(
    "kv_block_t", int, 16,
    "rows (time steps) per KV-cache block when FLAGS_paged_kv_cache is "
    "on; must be a multiple of 8 (TPU sublane quantum). Small blocks "
    "cut per-sequence HBM waste to <block_t rows (vs the ring's 128-"
    "row quanta) which is the concurrent-slot capacity win; large "
    "blocks amortize DMA issue overhead in the block walk")
FLAGS.define(
    "kv_cache_blocks", int, 0,
    "total blocks in the paged KV pool per side (self/cross); 0 = "
    "auto, sized ring-equivalent (slots x ceil(max_t / block_t)) so "
    "the static identity mapping reproduces the ring capacity exactly. "
    "Serving deployments set this to the HBM budget and let block-"
    "budget admission carry more short sequences than slot-count would")
FLAGS.define(
    "serving_decode_slots", int, 4,
    "default cache-slot count (the decode batch dimension) of a "
    "generation serving model (paddle_tpu/serving/generation.py): the "
    "continuous batcher coalesces decode steps across up to this many "
    "in-flight sequences; per-model override via GenerationConfig.slots")
FLAGS.define(
    "pipelined_feed", bool, True,
    "AsyncExecutor.run_from_files overlaps host ingest with device "
    "compute: batch N+1's feed arrays are device_put while step N "
    "executes, and step N's fetches materialize one step late "
    "(data_feed.py; off = the strict parse->put->run->sync loop)")
FLAGS.define(
    "fused_dropout_add", bool, True,
    "the bundled transformer/BERT models lower their dropout+residual "
    "pairs through the fused dropout-add epilogue kernel "
    "(kernels/dropout_epilogue.py): one Pallas kernel, mask regenerated "
    "from scalar seeds in the backward, no mask or random-bits tensor in "
    "HBM; off = the separate graph-level hash dropout + add ops")
FLAGS.define(
    "recompute", str, "",
    "activation-recompute (gradient checkpointing) policy for the memory "
    "tier (paddle_tpu/memory/recompute.py), applied by "
    "memory.maybe_optimize_memory consumers (bench.py --recompute, user "
    "training scripts): '' = off (the rewrite never runs; graphs are "
    "byte-identical to today — the zero-cost contract), 'auto' = "
    "sqrt(N)-segment boundaries chosen over the planner's activation "
    "watermark to minimize estimated peak, or a comma-separated list of "
    "checkpoint var names (the reference's checkpoints= annotation).  "
    "Each segment's forward ops are cloned in front of their grad ops "
    "instead of stashing intermediates; RNG-deriving ops replay the SAME "
    "step key via their static rng_id (dropout masks bit-identical "
    "between stash and recompute, asserted)")
FLAGS.define(
    "recompute_segments", int, 0,
    "with FLAGS_recompute=auto: explicit segment count; 0 = the "
    "sqrt(N)-over-forward-ops default (Chen et al., sublinear memory)")
FLAGS.define(
    "offload_activations", bool, False,
    "host offload for long-lived stash vars (memory/offload.py): vars "
    "the planner proves have a long fwd->bwd gap and large size get "
    "paired memcpy_d2h/memcpy_h2d ops at their liveness edges — parked "
    "in host memory across the gap, fetched back at the backward's "
    "first read.  Off (default) = the rewrite never runs")
FLAGS.define(
    "offload_min_mb", float, 1.0,
    "offload candidate threshold: minimum var size in MB")
FLAGS.define(
    "offload_min_gap", float, 0.25,
    "offload candidate threshold: minimum fwd->bwd liveness gap as a "
    "fraction of the program's op count")
FLAGS.define(
    "verify_program", bool, True,
    "run the static program verifier (paddle_tpu/analysis) before every "
    "executor compile: def-before-use/SSA across blocks, shape+dtype "
    "contract re-inference, donation/fetch alias conflicts, and the "
    "RNG-determinism lint (key-deriving ops the executor would not "
    "thread the step key for) all raise ProgramVerifyError with named "
    "findings instead of surfacing as late XLA trace errors.  Verified "
    "signatures are memoized per executor, so the cost is one O(program) "
    "walk per compile — zero hot-path cost; the inference server flips "
    "it off once all models are warm (serving/server.py _warmup_verified) "
    "so cold-signature stragglers skip straight to the trace")
FLAGS.define(
    "vlog", int, 0,
    "verbose logging level, like glog's VLOG(n) (reference init.cc "
    "InitGLOG); see paddle_tpu.log")
FLAGS.define(
    "monitor", bool, False,
    "enable the runtime telemetry registry (paddle_tpu.monitor): executor "
    "compile/run/recompile counters, data-feed queue gauges, inference "
    "latency histograms, collective byte counters; off = zero writes on "
    "the hot paths")
FLAGS.define(
    "monitor_jsonl", str, "",
    "path for StepMonitor per-step JSONL records (bench.py/trainer "
    "loops); empty keeps records in memory only")
FLAGS.define(
    "device_model", str, "",
    "device model the static cost model attributes against "
    "(paddle_tpu/analysis/costmodel.py DEVICE_MODELS key, e.g. "
    "'TPU v5e'); empty = auto-detect from the jax backend's device_kind "
    "('cpu-host' on the CPU backend; an unknown accelerator is an error)")
FLAGS.define(
    "peak_flops", float, 0.0,
    "override the device peak FLOP/s used by the cost model and "
    "StepMonitor MFU (per chip); 0 = resolve from the cost model's "
    "device table — and OMIT MFU entirely when the device is unknown "
    "rather than publish a wrong number")
FLAGS.define(
    "launch_overhead_us", float, 0.0,
    "override the per-launch dispatch overhead (microseconds) the cost "
    "model charges each op; 0 = the device-table constant (measure "
    "yours with `python bench.py --model dispatch`)")
FLAGS.define(
    "flight_dir", str, "",
    "directory for flight-recorder JSONL dumps (monitor/flight.py): on "
    "crash, SIGTERM/SIGUSR1, or watchdog trip the in-memory event ring "
    "is written here so a dead run leaves a black box; empty disables "
    "dumping (the ring still records in memory while FLAGS.monitor is on)")
FLAGS.define(
    "flight_events", int, 2048,
    "capacity of the flight-recorder event ring (bounded memory; oldest "
    "events are evicted first)")
FLAGS.define(
    "monitor_port", int, 0,
    "TCP port for the scrape endpoint (monitor/serve.py): /metrics "
    "Prometheus text, /health, /flight last-N events; 0 disables the "
    "server")
FLAGS.define(
    "health_stall_s", float, 600.0,
    "/health reports a trainer as stalled (HTTP 503) when a step monitor "
    "exists but no step completed for this many seconds; a process that "
    "never stepped (a pure inference server) is never 'stalled' — its "
    "health comes from serving READINESS (monitor/serve.py)")
FLAGS.define(
    "serving_buckets", str, "1,2,4,8,16",
    "default pad-to-bucket batch-size ladder for the inference server "
    "(paddle_tpu/serving): requests coalesce and pad up to the smallest "
    "bucket >= total rows, so the executor compile cache sees a BOUNDED "
    "set of feed signatures; per-model override via ModelConfig.buckets")
FLAGS.define(
    "serving_max_batch", int, 16,
    "default dynamic-batcher cap on coalesced rows per executed batch "
    "(paddle_tpu/serving); effective cap is min(this, largest bucket)")
FLAGS.define(
    "serving_max_wait_ms", float, 5.0,
    "default dynamic-batcher deadline: a queued request is executed at "
    "most this many ms after arrival even if its batch is not full "
    "(latency/fill tradeoff knob of the batching policy)")
FLAGS.define(
    "serving_max_queue_depth", int, 128,
    "admission control: a model's batcher sheds new requests (HTTP 429 "
    "with a Retry-After derived from the observed queue-latency EWMA, "
    "serving.<model>.shed_total counter) once this many requests are "
    "already queued ahead of them; the generation tier bounds its "
    "slot wait-queue the same way.  0 = unbounded queues (the pre-"
    "admission-control behavior: under overload, queue latency grows "
    "without bound and every request times out)")
FLAGS.define(
    "serving_max_inflight", int, 0,
    "server-level cap on concurrently admitted requests across ALL "
    "models of one InferenceServer (predict + generate); at the cap new "
    "requests shed with HTTP 429 + Retry-After.  0 = uncapped")
FLAGS.define(
    "serving_drain_timeout_s", float, 10.0,
    "graceful-drain budget: on SIGTERM the serving CLI flips /health to "
    "'draining' (503), rejects new requests with 503, lets in-flight "
    "and queued-admitted work complete up to this many seconds, dumps "
    "the flight recorder (trigger 'drain'), and exits 0")
FLAGS.define(
    "serving_breaker_threshold", int, 5,
    "per-model circuit breaker: this many CONSECUTIVE batch-execution "
    "failures open the breaker — submits fail fast with HTTP 503 "
    "(serving.<model>.breaker_state gauge: 0 closed / 1 open / 2 half-"
    "open) instead of queueing against a broken executor; after "
    "FLAGS_serving_breaker_cooldown_s ONE half-open probe is admitted "
    "and its outcome closes or re-opens the breaker.  0 disables "
    "(every request reaches the executor, the pre-breaker behavior)")
FLAGS.define(
    "serving_breaker_cooldown_s", float, 5.0,
    "how long an open circuit breaker rejects before admitting its "
    "half-open probe request")
FLAGS.define(
    "trace_requests", bool, False,
    "request-scoped distributed tracing for the serving tier "
    "(monitor/tracing.py): every serving request gets a trace id "
    "(accepting/emitting a W3C traceparent header) and a span tree "
    "decomposing its latency — queue wait, batch form, pad-to-bucket "
    "overhead, executor compile/run, de-batch, and per-token decode "
    "iterations for generation; traces land in the bounded trace store "
    "(/v1/traces endpoints), the flight ring, and the unified chrome "
    "timeline.  Off = zero cost: no trace objects, no registry entries, "
    "no flight events on the request path")
FLAGS.define(
    "trace_store", int, 256,
    "capacity of the in-memory finished-trace store behind /v1/traces "
    "(bounded memory; oldest traces evicted first)")
FLAGS.define(
    "serving_slo_ms", str, "",
    "per-model serving latency objective in milliseconds, e.g. '50' "
    "(every model) or 'demo=50,gendemo=500' (per model; a bare number "
    "entry is the default for unlisted models).  When set, every "
    "finished/shed request counts as a good or bad SLO event "
    "(serving.<model>.slo_good_total / slo_bad_total) and multi-window "
    "burn-rate gauges (slo_burn_rate_5m/30m/1h — observed bad fraction "
    "over the window divided by the 1-FLAGS_serving_slo_target error "
    "budget; 1.0 = burning exactly at budget) refresh on every /metrics "
    "scrape.  Empty disables the SLO engine")
FLAGS.define(
    "serving_slo_target", float, 0.999,
    "availability objective behind the burn-rate gauges: the error "
    "budget is 1 - this fraction of requests allowed to miss "
    "FLAGS_serving_slo_ms")
FLAGS.define(
    "router_port", int, 0,
    "TCP port for the serving router front-end (serving/router.py): "
    "proxies /v1/models/*:predict and :generate across the replica "
    "fleet; 0 = pick a free port")
FLAGS.define(
    "router_probe_interval_s", float, 0.5,
    "router health-probe period: every replica's /health is polled this "
    "often to drive the in-rotation / draining-out / evicted state "
    "machine (serving/router.py)")
FLAGS.define(
    "router_probe_timeout_s", float, 2.0,
    "per-probe HTTP timeout; a probe that times out counts as a failure "
    "toward FLAGS_router_evict_failures")
FLAGS.define(
    "router_evict_failures", int, 3,
    "consecutive failed health probes (connect error, timeout, or "
    "scheduler_dead status) before a replica is EVICTED from rotation; "
    "a single passing 'ready' probe re-admits it")
FLAGS.define(
    "router_retries", int, 2,
    "max failover attempts per proxied request AFTER the first (each on "
    "a different replica where possible), budgeted against the "
    "request's own timeout_s deadline — the router never sleeps or "
    "retries past it.  Predict retries on connect error/5xx/429; "
    "generation fails over only before the first upstream byte")
FLAGS.define(
    "router_hedge_ms", float, 0.0,
    "tail-latency hedging: if a proxied predict gets no response within "
    "this many ms, a second attempt is fired at a DIFFERENT replica and "
    "the first response wins (loser's connection is dropped; "
    "router.hedges_total / hedges_won_total).  0 disables; generation "
    "is never hedged")
FLAGS.define(
    "router_slo_weight", float, 0.0,
    "SLO-aware load balancing: a replica's effective load is "
    "inflight + this weight x its serving slo_burn_rate_5m gauge "
    "(scraped with each health probe), steering new requests away from "
    "replicas burning error budget; 0 = pure least-inflight")
FLAGS.define(
    "record_lowered_ops", bool, False,
    "test/debug flag: the executor trace records every lowered op type "
    "into the flight recorder (monitor/flight.py lowered_op_types) — the "
    "op-contract gate asserts registry coverage against this set")
FLAGS.define(
    "watchdog", bool, False,
    "arm the training anomaly watchdog (monitor/watchdog.py) in "
    "StepMonitor-instrumented loops: NaN/Inf loss, loss-spike z-score, "
    "throughput collapse, and a hang monitor on a daemon thread")
FLAGS.define(
    "watchdog_action", str, "dump",
    "what a watchdog trip does: 'log' (warn only), 'dump' (warn + write "
    "a flight record to FLAGS.flight_dir), or 'raise' (dump, then raise "
    "WatchdogError / interrupt the main thread — for tests)")
FLAGS.define(
    "checkpoint_async", bool, False,
    "CheckpointManager default save mode: snapshot device->host "
    "synchronously, then write/fsync/rename on a background thread so "
    "the step loop never blocks on disk (io.py checkpoint v2)")
FLAGS.define(
    "checkpoint_dir", str, "",
    "bench.py: arm interval checkpointing + emergency-save for every "
    "workload under this directory (one subdir per workload); empty "
    "disables")
FLAGS.define(
    "checkpoint_interval", int, 50,
    "bench.py checkpoint interval (in run_steps calls) when "
    "FLAGS.checkpoint_dir is set")
FLAGS.define(
    "chaos", bool, False,
    "master switch for deterministic fault injection "
    "(paddle_tpu/testing/chaos.py); off = every chaos hook is a no-op")
FLAGS.define(
    "chaos_seed", int, 0,
    "seed for any randomized chaos schedule (kept 0/deterministic by the "
    "built-in injections; reserved for custom harnesses)")
FLAGS.define(
    "chaos_kill_at_step", int, -1,
    "SIGKILL the process when a training loop reports this completed "
    "step (chaos.on_step); -1 disables")
FLAGS.define(
    "chaos_kill_at_run", int, -1,
    "SIGKILL the process on the Nth Executor.run call (1-based, "
    "chaos.on_executor_run); -1 disables")
FLAGS.define(
    "chaos_torn_write", int, -1,
    "truncate a tensor file of the Nth checkpoint save (0-based) after "
    "its manifest is computed — a disk-level torn write the integrity "
    "check must catch; -1 disables")
FLAGS.define(
    "chaos_io_errors", int, 0,
    "the first K chaos-guarded I/O calls (checkpoint rename/open, shard "
    "open, dataset download) raise a transient OSError; 0 disables")
FLAGS.define(
    "chaos_feed_stall_s", float, 0.0,
    "sleep injected per parsed batch in data-feed workers (feed "
    "starvation); 0 disables")
FLAGS.define(
    "chaos_nan_at_step", int, -1,
    "training loops report a NaN loss at this step (watchdog fodder); "
    "-1 disables")
FLAGS.define(
    "chaos_nan_var", str, "",
    "graph-level NaN injection: at trace time the named op-output var "
    "is poisoned with NaN (testing/chaos.poison_var, applied in "
    "core/executor.trace_block) — unlike chaos_nan_at_step's host-side "
    "fake loss, the NaN is real in the compiled graph, so the numerics "
    "locate replay must find the op that wrote it; '' disables")
FLAGS.define(
    "chaos_serve_latency_s", float, 0.0,
    "sleep injected into every serving batch execution / generation "
    "decode step (chaos.maybe_serve_latency — a slow-executor "
    "simulation that pins serving capacity so the CI overload gate is "
    "box-independent); 0 disables")
FLAGS.define(
    "chaos_serve_errors", int, 0,
    "the first K serving batch executions raise a transient "
    "RuntimeError (chaos.maybe_serve_error — circuit-breaker fodder; "
    "the budget is process-global and deterministic); 0 disables")
FLAGS.define(
    "chaos_serve_flood", int, 0,
    "request-flood burst: the FIRST admitted serving request after "
    "arming additionally fires this many synthetic duplicate requests "
    "at its own model (chaos.serve_flood — deterministic queue-pressure "
    "spike); 0 disables")
FLAGS.define(
    "chaos_kill_replica_after", int, -1,
    "replica-death injection: SIGKILL this serving process right after "
    "it finishes its Nth predict/generate request (1-based, "
    "chaos.on_request_done) — armed per replica via env override, the "
    "router/supervisor failover-and-restart fodder; -1 disables")
FLAGS.define(
    "chaos_probe_flap", int, 0,
    "health-probe flapping: every Nth /health readiness evaluation "
    "(1-based count of calls, process-global) reports not-ready "
    "(chaos.probe_flap) — exercises router eviction/re-admission "
    "hysteresis; 0 disables")
FLAGS.define(
    "chaos_replica_latency_s", float, 0.0,
    "slow-replica simulation: sleep injected once per proxied serving "
    "HTTP request at the handler level (chaos.maybe_replica_latency) — "
    "unlike chaos_serve_latency_s this delays the whole request path "
    "including admission, making one replica a hedging/eviction "
    "straggler; 0 disables")
