#!/usr/bin/env python
"""Benchmark driver entry: trains the flagship models on the available chip
and prints ONE JSON line {"metric", "value", "unit", "vs_baseline"} (plus
informational fields: mfu, loss, config).

Method: bf16 mixed-precision (pt.amp) training steps fused into one XLA call
per K steps via Executor.run_steps (lax.scan over device-resident batches),
so host dispatch latency amortizes and parameters never leave HBM.

vs_baseline:
  * resnet50 — ratio to the reference's best committed ResNet-50 training
    throughput (84.08 img/s, 2-socket Xeon 6148 + MKL-DNN,
    benchmark/IntelOptimizedPaddle.md:40-46; the reference repo has no
    committed GPU ResNet-50 number — see BASELINE.md).
  * transformer — the reference has NO committed transformer number, so
    vs_baseline is the ratio to the north-star target of BASELINE.json:
    50% MFU on this chip (vs_baseline = measured_mfu / 0.50).

MFU uses analytic model FLOPs (documented below) over the chip's bf16 peak.
"""

import argparse
import json
import sys
import time
import traceback

import numpy as np

def run_guarded(name, fn, *args):
    """Run one workload; print its JSON line the moment it is measured.

    A failure in one workload must not stop the others from being
    measured: the exception is reported on stderr with its traceback and
    the workload counts as failed — main() then exits non-zero.  Nothing
    is retried: a workload that fails is a finding, not noise.  Returns
    True iff the workload ran to its metric line."""
    try:
        fn(*args)
        return True
    except Warning:
        # only reachable under an explicit -W error::UserWarning run
        # (the CI warnings gate): a warning-turned-exception must FAIL
        # the bench, not be swallowed as a workload hiccup
        raise
    except Exception as e:  # noqa: BLE001 — the other workloads still run
        print(f"[bench] {name} failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        traceback.print_exc(file=sys.stderr)
        return False


def _step_monitor(name, examples_per_call=None, tokens_per_call=None,
                  flops_per_call=None):
    """A StepMonitor when FLAGS.monitor is on, else None.  One bench
    "step" is one run_steps call (scan_steps fused steps); JSONL goes to
    FLAGS.monitor_jsonl when set."""
    from paddle_tpu.flags import FLAGS

    if not FLAGS.monitor:
        return None
    from paddle_tpu.monitor import StepMonitor

    return StepMonitor(
        name=f"bench.{name}",
        examples_per_step=examples_per_call,
        tokens_per_step=tokens_per_call,
        flops_per_step=flops_per_call,
        jsonl_path=FLAGS.monitor_jsonl or None,
        watchdog=_bench_watchdog(),
    )


def _ckpt_manager(name, exe, prog, scope):
    """A CheckpointManager under FLAGS.checkpoint_dir/<name> (emergency
    save armed through the flight recorder), else None.  One bench "step"
    is one run_steps call."""
    from paddle_tpu.flags import FLAGS

    if not FLAGS.checkpoint_dir:
        return None
    import os

    import paddle_tpu as pt
    from paddle_tpu.monitor import flight

    mgr = pt.io.CheckpointManager(
        os.path.join(FLAGS.checkpoint_dir, name), exe,
        interval_steps=FLAGS.checkpoint_interval, main_program=prog,
        scope=scope)
    flight.install()
    mgr.install_emergency()
    return mgr


_WATCHDOG = None


def _bench_watchdog():
    """One process-wide watchdog shared by every workload's StepMonitor
    (armed by FLAGS_watchdog=1; hang monitor rides a daemon thread)."""
    global _WATCHDOG
    from paddle_tpu.flags import FLAGS

    if not (FLAGS.monitor and FLAGS.watchdog):
        return None
    if _WATCHDOG is None:
        from paddle_tpu.monitor import Watchdog

        _WATCHDOG = Watchdog()
        _WATCHDOG.arm()
    return _WATCHDOG


def timed_steps(exe, prog, feed, fetch, scope, warmup, calls, mon=None,
                ckpt=None, repeats=1):
    """Shared warmup + timing loop: returns (seconds, first_loss,
    last_loss).  first_loss is step 0 of the first (warmup) call, so
    last_loss < first_loss certifies the timed program actually LEARNS on
    its (fixed, memorizable) batches — the reference's book tests assert
    loss thresholds the same way (tests/book/test_recognize_digits.py).

    `repeats` repeats the `calls`-sized timed region that many times
    against the SAME compiled program (warmup runs once, before the
    first timed region).  The first return value is ALWAYS the list of
    per-repeat seconds (length `repeats`) — repeat before believing any
    single number.

    `mon`: optional StepMonitor (see _step_monitor) — records per-call
    loss/throughput/MFU telemetry for the timed calls.
    `ckpt`: optional CheckpointManager (see _ckpt_manager) — interval +
    emergency checkpoints; stepped IN the loop (use async_save /
    FLAGS_checkpoint_async to keep disk writes off the step path, and
    leave it off for measurement-grade numbers)."""
    from paddle_tpu.flags import FLAGS

    # Two stepping modes.  Measurement mode (default): inside the timed
    # region only a perf_counter stamp is taken per call; registry/JSONL
    # writes replay AFTER dt is measured so telemetry cost never lands in
    # the reported throughput.  Live mode (a watchdog is wired or a
    # flight dir is armed): mon.step() runs IN the loop — the watchdog
    # must see NaN/hang at the step it happens and a SIGTERM dump must
    # name the last completed step, which deferred replay cannot give.
    # Cost: ~tens of µs of writes per multi-ms call — the price of a
    # black box; leave watchdog/flight off for measurement-grade runs.
    live = mon is not None and (mon.watchdog is not None
                                or bool(FLAGS.flight_dir))
    first_loss = None
    for i in range(max(warmup, 1)):
        (losses,) = exe.run_steps(prog, feed=feed, fetch_list=fetch,
                                  scope=scope)
        if i == 0:
            first_loss = float(np.asarray(losses).reshape(-1)[0])
    try:
        dts = []
        stamps = []
        if mon is not None:
            mon.step(now=time.perf_counter())  # arm at region start
        for rep in range(max(repeats, 1)):
            t0 = time.perf_counter()
            for i in range(calls):
                step_no = rep * calls + i
                if ckpt is not None:
                    ckpt.step_started(step_no)
                (losses,) = exe.run_steps(prog, feed=feed, fetch_list=fetch,
                                          scope=scope)
                if live:
                    mon.step(loss=float(np.asarray(losses).reshape(-1)[-1]),
                             now=time.perf_counter())
                elif mon is not None:
                    stamps.append((time.perf_counter(), losses))
                if ckpt is not None:
                    ckpt.on_step(step_no)
            dts.append(time.perf_counter() - t0)
        if mon is not None:
            for now_i, lv in stamps:
                mon.step(loss=float(np.asarray(lv).reshape(-1)[-1]),
                         now=now_i)
    finally:
        # a failed workload must not leak the handles its StepMonitor
        # and checkpoint manager opened
        if mon is not None:
            mon.close()
        if ckpt is not None:
            ckpt.close()  # flush + detach the emergency callback
    return dts, first_loss, float(np.asarray(losses).reshape(-1)[-1])


def memory_probe(exe, prog, feed, fetch_list, scope, batch_size):
    """The ISSUE-15 memory fields for a dense-workload record:
    `activation_peak_bytes` (the static planner over the one-step
    program, paddle_tpu/memory) and `memory_analysis_peak_bytes` (XLA
    ground truth: the executed run_steps entry re-lowered AOT and its
    CompiledMemoryStats read — one extra compile per workload, after the
    timed region).  Telemetry must never fail a measured bench: each
    probe degrades to a stderr note."""
    fields = {}
    feed_names = sorted(feed)
    fetch_names = [getattr(v, "name", v) for v in fetch_list]
    try:
        from paddle_tpu import memory as M

        plan = M.plan_program(prog, feed_names, fetch_names,
                              batch_size=batch_size)
        fields["activation_peak_bytes"] = int(plan.activation_peak_bytes)
        fields["planner_peak_bytes"] = int(plan.peak_bytes)
        if plan.warnings:
            fields["planner_warnings"] = len(plan.warnings)
        M.publish_plan(plan, name="bench")
    except Exception as e:  # noqa: BLE001
        print(f"[bench] planner probe failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    try:
        import jax

        from paddle_tpu.core.executor import latest_jitted_entry
        from paddle_tpu.memory import xla_memory_stats

        entry = latest_jitted_entry(exe)
        feed_vals = [exe._to_device_array(prog, n, feed[n])
                     for n in feed_names]
        rw = [scope.find_var(n) for n in entry.rw_state]
        ro = [scope.find_var(n) for n in entry.ro_state]
        args = [feed_vals, rw, ro]
        if entry.needs_key:
            args.append(jax.random.key(0, impl="rbg"))
        stats = xla_memory_stats(entry.jitted.lower(*args).compile())
        fields["memory_analysis_peak_bytes"] = int(stats["peak_bytes"])
    except Exception as e:  # noqa: BLE001
        print(f"[bench] memory_analysis probe failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    return fields


def cost_probe(prog, batch_size, name):
    """Static roofline attribution for a record's one-step program
    (paddle_tpu/analysis/costmodel): predicted step time, launch count,
    and launch-bound fraction land in the record's config so
    tools/perf_report.py can compute predicted-vs-measured without
    rebuilding the program.  Degrades to a stderr note like
    memory_probe — attribution must never fail a measured bench."""
    try:
        from paddle_tpu.analysis.costmodel import cost_program, publish_cost

        cost = cost_program(prog, name=name, batch_size=batch_size)
        publish_cost(cost)
        return {
            "cost_device": cost.device.name,
            "cost_launches": cost.n_launches,
            "cost_launches_fused": cost.n_launches_fused,
            "cost_predicted_step_us": round(
                cost.predicted_seconds * 1e6, 2),
            "cost_predicted_step_us_fused": round(
                cost.predicted_seconds_fused * 1e6, 2),
            "cost_launch_bound_fraction": round(
                cost.launch_bound_fraction, 4),
            "cost_launch_bound_fraction_fused": round(
                cost.launch_bound_fraction_fused, 4),
        }
    except Exception as e:  # noqa: BLE001
        print(f"[bench] cost probe failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return {}


_PROVENANCE = None


def _provenance():
    """Computed once per process: git commit + dirty flag, jax/jaxlib
    versions, and the non-default flags — rides every record so a
    bench_diff comparison is attributable to a code/flag delta, not a
    mystery."""
    global _PROVENANCE
    if _PROVENANCE is not None:
        return _PROVENANCE
    import os
    import subprocess

    prov = {"git_commit": "unknown", "git_dirty": None}
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo, capture_output=True,
            text=True, timeout=10)
        if out.returncode == 0 and out.stdout.strip():
            prov["git_commit"] = out.stdout.strip()
            st = subprocess.run(
                ["git", "status", "--porcelain"], cwd=repo,
                capture_output=True, text=True, timeout=10)
            if st.returncode == 0:
                prov["git_dirty"] = bool(st.stdout.strip())
    except Exception:  # noqa: BLE001 — no git / not a checkout
        pass
    try:
        import jax
        import jaxlib

        prov["jax"] = jax.__version__
        prov["jaxlib"] = jaxlib.__version__
    except Exception:  # noqa: BLE001
        prov["jax"] = prov["jaxlib"] = "unknown"
    try:
        from paddle_tpu.flags import FLAGS

        defs = object.__getattribute__(FLAGS, "_defs")
        prov["flags"] = {
            n: getattr(FLAGS, n) for n in sorted(defs)
            if getattr(FLAGS, n) != defs[n].default}
    except Exception:  # noqa: BLE001
        prov["flags"] = {}
    _PROVENANCE = prov
    return prov


def emit_metric(metric, value, unit, vs_baseline, mfu, loss, config,
                loss_first=None):
    """One-json-line contract, extended with the self-validation fields:
    loss_first (pre-training) vs loss (final) and learned = decreased,
    plus the provenance block every bench_diff comparison requires."""
    rec = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 3) if vs_baseline is not None else 0.0,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "loss": round(loss, 4),
        "config": config,
        "provenance": _provenance(),
        "device": _device(),
    }
    if loss_first is not None:
        rec["loss_first"] = round(loss_first, 4)
        rec["learned"] = bool(loss < loss_first)
    print(json.dumps(rec), flush=True)
    return rec


def _repeats(args):
    """--runs N, defaulting to the PERF.md protocol: 3 timed repeats in a
    full bench, 1 in smoke."""
    return args.runs or (1 if args.smoke else 3)


def _mean_spread(runs):
    """(mean, spread, runs_list) of per-run throughputs.  The spread rides
    into the JSON record so run-to-run variance can't masquerade as a
    code-change regression or win."""
    runs = [float(r) for r in (runs if isinstance(runs, list) else [runs])]
    mean = float(np.mean(runs))
    spread = float(np.max(runs) - np.min(runs)) if len(runs) > 1 else 0.0
    return mean, spread, runs


REFERENCE_RESNET50_IMGS_PER_SEC = 84.08

# Committed per-chip throughput targets for the workloads with no
# reference number and no meaningful MFU (VERDICT r4 weak #5/#6: every
# line needs a baseline).  Values = the round-4 measured results on this
# chip, rounded down — vs_baseline >= 1.0 means "no regression vs r04".
MNIST_TARGET_IMGS_PER_SEC = 16000.0
DEEPFM_TARGET_EXAMPLES_PER_SEC = 40000.0

# ResNet-50 @224: 4.089 GMACs forward (standard torchvision/paper count,
# incl. final fc) -> 8.18 GFLOPs fwd; training fwd+bwd ~= 3x fwd.
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.089e9

def _device():
    """What every record names beside its provenance: the device jax
    reports (`jax.devices()[0].platform`, `.device_kind`, the count) — a
    number is never separated from the machine it was taken on."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "kind": devs[0].device_kind, "count": len(devs)}


def _peak_flops():
    """bf16 peak FLOP/s of device 0 from the committed per-chip table
    (analysis/costmodel.py DEVICE_MODELS, shared with StepMonitor).  On
    the CPU backend there is no peak and records carry mfu null; a TPU
    whose device_kind is not in the table is an error — an MFU against a
    guessed peak is worse than none.  The import is function-local so
    `--help`/bad-flag invocations exit in argparse without loading the
    framework."""
    from paddle_tpu.monitor.step import TPU_PEAK_FLOPS

    dev = _device()
    if dev["platform"] == "cpu":
        return None
    if dev["kind"] not in TPU_PEAK_FLOPS:
        raise LookupError(
            f"no peak FLOP/s for device_kind {dev['kind']!r} (known: "
            f"{sorted(TPU_PEAK_FLOPS)}); add it with its source to "
            f"analysis/costmodel.py DEVICE_MODELS")
    return TPU_PEAK_FLOPS[dev["kind"]]


def transformer_train_flops_per_token(n_layer, d_model, d_ff, n_head, d_key,
                                      seq_len, vocab):
    """Analytic matmul FLOPs per token, fwd, for the enc+dec transformer
    (matmuls only; 2 FLOPs per MAC).  Train = 3x fwd (bwd ~= 2x fwd).

    Per layer per token: qkv+out projections 4 * d_model * (n_head*d_key),
    attention scores+values 2 * seq_len * (n_head*d_key), ffn 2 * d_model *
    d_ff.  Decoder layers add cross-attention (same cost as self-attention).
    Final vocab projection d_model * vocab.
    """
    dh = n_head * d_key
    attn = 4 * d_model * dh + 2 * seq_len * dh
    ffn = 2 * d_model * d_ff
    enc = n_layer * (attn + ffn)
    dec = n_layer * (2 * attn + ffn)
    fwd_macs = enc + dec + d_model * vocab
    return 3 * 2 * fwd_macs


def bench_resnet50(batch_size=256, scan_steps=16, calls=2, warmup=1,
                   image_size=224, depth=50, amp=True, stream=False,
                   data_format="NHWC"):
    """stream=True feeds a fresh host batch per call through the
    double-buffer prefetcher (reader/decorator.py double_buffer), so the
    host->HBM copy overlaps the previous call's compute — the
    buffered_reader.cc capability; target is within ~5% of the
    cached-device-batch number."""
    import paddle_tpu as pt
    from paddle_tpu.models import resnet as R

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        img, label, avg_cost, acc, _ = R.build_train_net(
            class_dim=1000, image_shape=(3, image_size, image_size),
            depth=depth, lr=0.1, input_u8=stream, data_format=data_format,
        )
    if amp:
        pt.amp.enable(prog)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)

    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    x = rng.rand(scan_steps, batch_size, 3, image_size, image_size)
    y = rng.randint(0, 1000, (scan_steps, batch_size, 1))
    y64 = y.astype("int64")
    if stream:
        # uint8 wire format (what a decode pipeline hands over): 4x less
        # host->device traffic, normalized INSIDE the compiled program
        x_feed = (x * 255).astype("uint8")
    else:
        x_feed = x.astype("float32")
    feed = {"image": jnp.asarray(x_feed), "label": jnp.asarray(y64)}

    first_loss = None
    for i in range(max(warmup, 1)):
        (wl,) = exe.run_steps(prog, feed=feed, fetch_list=[avg_cost],
                              scope=scope)
        if i == 0:
            first_loss = float(np.asarray(wl).reshape(-1)[0])

    if stream:
        from paddle_tpu.reader.decorator import double_buffer

        # fresh host batch per call; the prefetch thread's only job is the
        # chunked host->HBM copy, overlapping the previous call's compute
        # (buffered_reader.cc pre-copies the raw batch the same way)
        def src(n):
            def reader():
                for i in range(n):
                    yield {"image": x_feed, "label": (y64 + i) % 1000}
            return reader

        # warm the streaming path (first transfer pipeline)
        for fd in double_buffer(src(1), capacity=2)():
            exe.run_steps(prog, feed=fd, fetch_list=[avg_cost], scope=scope)

        losses = None
        t0 = time.perf_counter()
        for fd in double_buffer(src(calls), capacity=2)():
            (losses,) = exe.run_steps(prog, feed=fd,
                                      fetch_list=[avg_cost], scope=scope)
        dt = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        for _ in range(calls):
            (losses,) = exe.run_steps(prog, feed=feed,
                                      fetch_list=[avg_cost], scope=scope)
        dt = time.perf_counter() - t0
    mem = memory_probe(exe, prog, feed, [avg_cost], scope, batch_size)
    ips = batch_size * scan_steps * calls / dt
    return ips, first_loss, float(np.asarray(losses)[-1]), mem


# transformer-base (Vaswani et al. 2017 "base": 6+6 layers, d_model 512,
# 8 heads x 64, d_ff 2048) and the CI smoke width — the ONE place the
# train workload's model geometry is written (chip_smoke.py's train leg
# builds through these same functions)
TRANSFORMER_BASE = dict(n_layer=6, n_head=8, d_key=64, d_value=64,
                        d_model=512, d_inner_hid=2048, vocab=32000)
TRANSFORMER_TINY = dict(n_layer=2, n_head=4, d_key=16, d_value=16,
                        d_model=64, d_inner_hid=128, vocab=256)


def build_transformer_train(cfg, seq_len, amp=True, use_flash=True):
    """(program, startup, avg_cost, feed_names) of the transformer train
    step: dropout 0.1, Adam 1e-4, bf16 mixed precision under `amp`."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as T

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        avg_cost, _, feed_names = T.transformer(
            src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
            max_length=seq_len, n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_key=cfg["d_key"], d_value=cfg["d_value"], d_model=cfg["d_model"],
            d_inner_hid=cfg["d_inner_hid"], dropout_rate=0.1,
            src_seq_len=seq_len, trg_seq_len=seq_len,
            use_flash=use_flash,
        )
        pt.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    if amp:
        pt.amp.enable(prog)
    return prog, startup, avg_cost, list(feed_names)


def transformer_feed(cfg, batch_size, seq_len, scan_steps):
    """[scan_steps, batch, ...] feed of fixed (memorizable) batches, one
    seed per step — what run_steps scans over."""
    from paddle_tpu.models import transformer as T

    batches = [
        T.make_batch(batch_size, seq_len, seq_len, cfg["n_head"],
                     cfg["vocab"], cfg["vocab"], rng=np.random.RandomState(s))
        for s in range(scan_steps)
    ]
    return {k: np.stack([b[k] for b in batches]) for k in batches[0]}


def bench_transformer(batch_size=32, seq_len=256, scan_steps=8, calls=4,
                      warmup=1, amp=True, tiny=False, use_flash=True,
                      repeats=1, recompute=False):
    import paddle_tpu as pt

    cfg = TRANSFORMER_TINY if tiny else TRANSFORMER_BASE
    prog, startup, avg_cost, feed_names = build_transformer_train(
        cfg, seq_len, amp=amp, use_flash=use_flash)
    # numerics observability A/B knob: FLAGS_check_numerics=summary adds
    # the fused per-param-group stats reductions + one [N,4] fetch per
    # step (the PERF.md overhead leg); off is a no-op by contract
    from paddle_tpu.analysis import numerics as AN

    AN.maybe_instrument(prog)
    rc_fields = {}
    if recompute:
        # the r12 A/B leg: activation-recompute pass applied to the
        # trained program (auto sqrt(N)-segment policy); the record
        # carries the planner's before/after peaks + est FLOPs factor
        from paddle_tpu import memory as M

        rep = M.apply_recompute(prog, feed_names,
                                fetch_names=[avg_cost.name],
                                batch_size=batch_size)
        rc_fields = {
            "recompute_segments": rep["n_segments"],
            "recompute_cloned_ops": rep["cloned_ops"],
            "recompute_activation_peak_before": rep[
                "activation_peak_before"],
            "recompute_activation_peak_after": rep[
                "activation_peak_after"],
            "recompute_flops_ratio": round(rep["flops_ratio"], 4),
        }
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)

    feed = transformer_feed(cfg, batch_size, seq_len, scan_steps)

    flops_tok = transformer_train_flops_per_token(
        cfg["n_layer"], cfg["d_model"], cfg["d_inner_hid"], cfg["n_head"],
        cfg["d_key"], seq_len, cfg["vocab"])
    toks_per_call = batch_size * seq_len * scan_steps
    mon = _step_monitor("transformer", tokens_per_call=toks_per_call,
                        flops_per_call=flops_tok * toks_per_call)
    ckpt = _ckpt_manager("transformer", exe, prog, scope)
    dt, first_loss, last_loss = timed_steps(exe, prog, feed, [avg_cost],
                                            scope, warmup, calls, mon=mon,
                                            ckpt=ckpt, repeats=repeats)
    mem = memory_probe(exe, prog, feed, [avg_cost], scope, batch_size)
    mem.update(rc_fields)
    mem.update(cost_probe(prog, batch_size, "bench.transformer"))
    # tokens counted on the decoded (trg) stream, the convention for MT
    toks = batch_size * seq_len * scan_steps * calls
    return [toks / d for d in dt], flops_tok, first_loss, last_loss, mem


# decode workload geometry (transformer-base, source 256, 64 new tokens,
# f32) and its CI smoke width — shared with chip_smoke.py's generate leg
DECODE_BASE = dict(TRANSFORMER_BASE, src_len=256, max_out=64)
DECODE_TINY = dict(n_layer=2, n_head=4, d_key=32, d_value=32, d_model=128,
                   d_inner_hid=256, vocab=1000, src_len=32, max_out=16)


def decode_model_kw(cfg, max_out=None, use_flash=True):
    """models/transformer.build_generation_programs keywords for a decode
    geometry (everything but batch_size and the sampling strategy)."""
    max_out = max_out or cfg["max_out"]
    return dict(
        src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
        max_length=max(cfg["src_len"], max_out) + 2,
        n_layer=cfg["n_layer"], n_head=cfg["n_head"], d_key=cfg["d_key"],
        d_value=cfg["d_value"], d_model=cfg["d_model"],
        d_inner_hid=cfg["d_inner_hid"], src_seq_len=cfg["src_len"],
        max_out_len=max_out,
        # eos outside the sampled range: every run generates exactly
        # max_tokens tokens (fixed work for the timed region)
        bos_id=0, eos_id=-1, use_flash=use_flash)


# fixed HBM budget the decode records' serving-capacity gauge is quoted
# against: concurrent_slots_at_budget = how many sequences of the
# benched shape fit this many KV bytes.  The ring layout charges every
# sequence its full ring rows; the paged layout charges only the blocks
# the sequence touches — tools/run_ci.sh gates the paged/ring ratio.
KV_CAPACITY_BUDGET_BYTES = 64 << 20


def _kv_capacity(progs, batch_size, src_len, max_tokens):
    """Serving-capacity fields for one decode record: bytes one
    sequence of this workload's shape holds resident, the slot count at
    the fixed budget, and the planner's kv_cache row (the same number
    hlo_diag --memory prints — keeps the bench and the planner honest
    against each other)."""
    from paddle_tpu import memory as M

    self_c, cross_c = progs.self_cache, progs.cross_cache
    if getattr(progs, "paged", False):
        per_seq = (self_c.blocks_for(max_tokens) * self_c.block_bytes
                   + cross_c.blocks_for(src_len) * cross_c.block_bytes)
    else:
        per_seq = (self_c.hbm_bytes + cross_c.hbm_bytes) // batch_size
    kv_row = M.plan_program(progs.decode, [], []).class_peaks.get(
        "kv_cache", 0)
    budget = KV_CAPACITY_BUDGET_BYTES
    return {
        "paged": bool(getattr(progs, "paged", False)),
        "kv_bytes_per_seq": int(per_seq),
        "kv_budget_bytes": int(budget),
        "concurrent_slots_at_budget": int(budget // max(per_seq, 1)),
        "planner_kv_cache_bytes": int(kv_row),
        "kv_resident_gb": (self_c.hbm_bytes + cross_c.hbm_bytes) / 1e9,
    }


def bench_decode(batch_size=1, max_tokens=64, tiny=False, repeats=1,
                 use_flash=True):
    """Autoregressive decode tokens/sec (ROADMAP item 2's named metric:
    decode at batch 1 and 64).  One compiled prefill + ONE compiled
    per-token decode program stepped by the host — the serving-shaped
    loop (token fetched to host every step).  Route follows
    FLAGS.kv_cache (the A/B knob: cached O(T) vs full-prefix-recompute
    O(T²)); FLAGS.flash_decode picks the Pallas decode kernel on TPU.

    Returns (tokens/sec per repeat, prefill_seconds, compile_flat,
    compile_count): compile_flat asserts the executor compile cache did
    NOT grow between the end of warmup and the last generated token —
    the length-independent-compile-key acceptance criterion."""
    import paddle_tpu as pt
    from paddle_tpu.generation import GenerationSession
    from paddle_tpu.models import transformer as T

    cfg = DECODE_TINY if tiny else DECODE_BASE
    progs = T.build_generation_programs(
        **decode_model_kw(cfg, max_out=max(max_tokens, cfg["max_out"]),
                          use_flash=use_flash),
        batch_size=batch_size, strategy="greedy")
    sess = GenerationSession(progs)
    sess.init_params()
    rng = np.random.RandomState(0)
    src = rng.randint(2, cfg["vocab"],
                      (batch_size, cfg["src_len"], 1)).astype(np.int64)

    from paddle_tpu.testing import chaos

    def one_pass(n_tokens):
        t0 = time.perf_counter()
        sess.prefill(src)
        t_prefill = time.perf_counter() - t0
        tokens = np.full((batch_size,), progs.bos_id, np.int64)
        prefix = np.full((batch_size, progs.t_buf), progs.bos_id,
                         np.int64)
        t1 = time.perf_counter()
        for t in range(n_tokens):
            # per-decode-step chaos latency hook (one flag read when
            # off): FLAGS_chaos + FLAGS_chaos_serve_latency_s inject a
            # deterministic synthetic slowdown — the bench_diff red
            # gate's regression source (tools/run_ci.sh)
            chaos.maybe_serve_latency()
            if progs.kv_cache:
                tokens = sess.decode_step(tokens)
            else:
                tokens = sess.decode_step(None, prefix=prefix, t=t)
                if t + 1 < progs.t_buf:
                    prefix[:, t + 1] = tokens
        return t_prefill, time.perf_counter() - t1

    one_pass(2)  # warmup: compiles prefill + decode
    compiles = sess.compile_count
    runs, prefill_s = [], None
    for _ in range(max(repeats, 1)):
        prefill_s, dt = one_pass(max_tokens)
        runs.append(batch_size * max_tokens / dt)
    compile_flat = sess.compile_count == compiles
    # static roofline attribution of the per-token decode program — the
    # launch-bound-fraction input ROADMAP item 1 reads off this record
    cost = cost_probe(progs.decode, batch_size, "bench.decode")
    if progs.kv_cache:
        cost = dict(cost)
        cost.update(_kv_capacity(progs, batch_size, cfg["src_len"],
                                 max_tokens))
    return runs, prefill_s, compile_flat, sess.compile_count, cost


def run_decode(args, peak):
    """Emit decode_tokens_per_sec at the ROADMAP batch pair (1 and 64;
    tiny shapes under --smoke).  config records the kv_cache /
    flash_decode / fused_decode_step flags — tools/run_ci.sh pairs a
    FLAGS_kv_cache=0 recompute record next to the cached one for the
    A/B — and compile_flat, which run_ci asserts True.

    When FLAGS_fused_decode_step is on (the default) each batch emits a
    PAIR: the fused record under the baseline-continuous metric name,
    then a `_unfused` record rebuilt with the flag off — the megastep
    speedup ratio run_ci's decode smoke gate reads (fused b1 tokens/sec
    must not lose to unfused)."""
    from paddle_tpu.flags import FLAGS

    repeats = _repeats(args)
    max_tokens = 16 if args.smoke else 64
    batches = ([1, 8] if args.smoke else [1, 64])
    if args.batch_size:
        batches = [args.batch_size]
    # the pair only means something on the cached route (the recompute
    # oracle never runs cached_decoder_step)
    variants = ([(True, ""), (False, "_unfused")]
                if FLAGS.fused_decode_step and FLAGS.kv_cache
                else [(bool(FLAGS.fused_decode_step), "")])
    for bs in batches:
        for fused, suffix in variants:
            try:
                if not fused:
                    FLAGS.set("fused_decode_step", False)
                runs, prefill_s, flat, n_compiles, cost = bench_decode(
                    batch_size=bs, max_tokens=max_tokens, tiny=args.smoke,
                    repeats=repeats)
            finally:
                FLAGS.reset("fused_decode_step")
            tps, spread, run_list = _mean_spread(runs)
            config = {"batch": bs, "max_tokens": max_tokens,
                      "tiny": args.smoke,
                      "kv_cache": bool(FLAGS.kv_cache),
                      "flash_decode": bool(FLAGS.flash_decode),
                      "fused_decode_step": fused,
                      "prefill_ms": round(prefill_s * 1e3, 2),
                      "compile_flat": bool(flat),
                      "compiled_signatures": n_compiles,
                      "runs": [round(r, 1) for r in run_list],
                      "spread": round(spread, 1)}
            config.update(cost)
            if config.get("kv_resident_gb"):
                # ROADMAP item 2's capacity-efficiency metric, bench-side
                config["tokens_per_sec_per_hbm_gb"] = round(
                    tps / config["kv_resident_gb"], 1)
            emit_metric(
                f"decode_tokens_per_sec_b{bs}{suffix}", tps, "tokens/sec",
                None, None, 0.0, config)
        if FLAGS.kv_cache and not FLAGS.paged_kv_cache:
            # paired paged record next to the ring one: same shape, the
            # block-pool cache layout — run_ci's capacity gate reads the
            # concurrent_slots_at_budget ratio off this pair
            try:
                FLAGS.set("paged_kv_cache", True)
                runs, prefill_s, flat, n_compiles, cost = bench_decode(
                    batch_size=bs, max_tokens=max_tokens, tiny=args.smoke,
                    repeats=repeats)
            finally:
                FLAGS.reset("paged_kv_cache")
            tps, spread, run_list = _mean_spread(runs)
            config = {"batch": bs, "max_tokens": max_tokens,
                      "tiny": args.smoke,
                      "kv_cache": bool(FLAGS.kv_cache),
                      "flash_decode": bool(FLAGS.flash_decode),
                      "fused_decode_step": bool(FLAGS.fused_decode_step),
                      "prefill_ms": round(prefill_s * 1e3, 2),
                      "compile_flat": bool(flat),
                      "compiled_signatures": n_compiles,
                      "runs": [round(r, 1) for r in run_list],
                      "spread": round(spread, 1)}
            config.update(cost)
            if config.get("kv_resident_gb"):
                config["tokens_per_sec_per_hbm_gb"] = round(
                    tps / config["kv_resident_gb"], 1)
            emit_metric(
                f"decode_tokens_per_sec_b{bs}_paged", tps, "tokens/sec",
                None, None, 0.0, config)


def bench_dispatch(calls=300, warmup=30, repeats=3):
    """Per-launch dispatch overhead microbench: time N cache-hit
    Executor.run calls of a trivially small program (one mean over 32
    floats — nanoseconds of arithmetic), so the per-call wall time IS
    the host-side launch cost the cost model charges each op: Python
    bookkeeping, cache lookup, device enqueue, and the blocking fetch.
    CPU-measurable today; re-run on chip to re-arm DEVICE_MODELS /
    FLAGS_launch_overhead_us.  Returns per-repeat seconds/call."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        out = layers.mean(x)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    feed = {"x": np.zeros((4, 8), np.float32)}
    for _ in range(max(warmup, 1)):
        exe.run(prog, feed=feed, fetch_list=[out], scope=scope)
    per_call = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        for _ in range(calls):
            exe.run(prog, feed=feed, fetch_list=[out], scope=scope)
        per_call.append((time.perf_counter() - t0) / calls)
    return per_call


def run_dispatch(args, peak):
    """Explicit-only (--model dispatch): emits dispatch_overhead_us, the
    measured per-launch constant behind DEVICE_MODELS' launch term.  The
    config carries the device kind and the table constant currently in
    force so the report shows measured-vs-declared drift."""
    from paddle_tpu.analysis.costmodel import resolve_device_model

    repeats = _repeats(args)
    calls = args.calls or (50 if args.smoke else 300)
    per_call = bench_dispatch(calls=calls, repeats=repeats)
    mean_us, spread, run_list = _mean_spread([p * 1e6 for p in per_call])
    dm = resolve_device_model()
    emit_metric(
        "dispatch_overhead_us", mean_us, "us/launch", None, None, 0.0,
        {"calls": calls, "device_model": dm.name,
         "table_launch_overhead_us": round(dm.launch_overhead_s * 1e6, 1),
         "table_source": dm.source,
         "runs": [round(r, 2) for r in run_list],
         "spread": round(spread, 2)})


def bench_ringattn(seq_len=8192, n_head=8, d_head=64, iters=8, warmup=2):
    """Long-context attention kernel line (VERDICT r4 item 3): fwd+bwd
    tokens/sec of the Pallas flash path vs the unfused reference at 8k+
    sequence on one chip.  vs_baseline = flash/reference speedup — the
    single-device leg of the long-context capability (the multi-device leg,
    ring CP over a mesh, is exercised by tests/test_ring_attention.py and
    dryrun_multichip's sp axis; one chip can't run a real ring)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.attention import (
        flash_attention,
        reference_attention,
    )

    rng = np.random.RandomState(0)
    shape = (1, n_head, seq_len, d_head)
    q = jnp.asarray(rng.randn(*shape).astype("float32")).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(*shape).astype("float32")).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape).astype("float32")).astype(jnp.bfloat16)
    scale = 1.0 / np.sqrt(d_head)

    def make(fn):
        def loss(q, k, v):
            o = fn(q, k, v, None, scale=scale, causal=True)
            return jnp.sum(o.astype(jnp.float32) * 1e-3)
        return jax.jit(jax.grad(loss, (0, 1, 2)))

    def time_one(g):
        r = g(q, k, v)
        np.asarray(jax.tree_util.tree_leaves(r)[0][0, 0, 0])  # sync
        for _ in range(warmup):
            r = g(q, k, v)
        np.asarray(jax.tree_util.tree_leaves(r)[0][0, 0, 0])
        t0 = time.perf_counter()
        for _ in range(iters):
            r = g(q, k, v)
        np.asarray(jax.tree_util.tree_leaves(r)[0][0, 0, 0])
        return (time.perf_counter() - t0) / iters

    t_flash = time_one(make(flash_attention))
    t_ref = time_one(make(reference_attention))
    tps = seq_len / t_flash
    return tps, t_ref / t_flash, t_flash, t_ref


def run_ringattn(args, peak):
    seq = 1024 if args.smoke else 8192
    tps, speedup, t_flash, t_ref = bench_ringattn(seq_len=seq)
    emit_metric("flash_attention_longseq_fwd_bwd_tokens_per_sec", tps,
                "tokens/sec", speedup, None, 0.0,
                {"seq_len": seq, "n_head": 8, "d_head": 64, "causal": True,
                 "bf16": True, "flash_ms": round(t_flash * 1e3, 2),
                 "reference_ms": round(t_ref * 1e3, 2)})


# The five distinct ResNet-50 bottleneck conv+BN shapes (stage 1-4 members;
# one 3x3 so both fused routes — dot+stats epilogue and conv+stats-kernel —
# are measured).  (label, batch, hw, c_in, c_out, ksize, stride, residual);
# residual=True also folds the block's add+relu epilogue, the conv3 site.
CONVBN_SHAPES = [
    ("s1_1x1_256_64_hw56", 16, 56, 256, 64, 1, 1, False),
    ("s1_1x1_64_256_hw56", 16, 56, 64, 256, 1, 1, True),
    ("s2_3x3_128_128_hw28", 16, 28, 128, 128, 3, 1, False),
    ("s3_1x1_1024_256_hw14", 16, 14, 1024, 256, 1, 1, False),
    ("s4_1x1_512_2048_hw7", 16, 7, 512, 2048, 1, 1, True),
]
CONVBN_SHAPES_SMOKE = [
    ("smoke_1x1_128_128_hw8", 2, 8, 128, 128, 1, 1, True),
    ("smoke_3x3_64_64_hw8", 2, 8, 64, 64, 3, 1, False),
]


def bench_convbn_shape(n, hw, cin, cout, ksize, stride, residual,
                       iters=20, repeats=3, warmup=1):
    """One conv+BN(+residual+relu) fwd+bwd A/B at a fixed shape: the XLA
    reference composition vs the fused kernels (kernels/conv_bn.py).

    In-loop protocol (per-call host dispatch would dominate a
    micro-benchmark of one kernel pair): `iters` chained
    fwd+bwd steps run INSIDE one jit via lax.scan — each step feeds its
    gradients back into the carried operands, so nothing is DCE'd and one
    host sync covers the whole loop.  Returns (fused_ms, ref_ms) lists of
    per-repeat ms/iter."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import conv_bn as CB

    rng = np.random.RandomState(0)
    pad = ksize // 2
    ohw = (hw + 2 * pad - ksize) // stride + 1
    dt = jnp.bfloat16
    x = jnp.asarray(rng.randn(n, hw, hw, cin).astype("float32")).astype(dt)
    w = jnp.asarray(
        (rng.randn(cout, cin, ksize, ksize)
         * np.sqrt(2.0 / (cin * ksize * ksize))).astype("float32")).astype(dt)
    gamma = jnp.asarray(rng.rand(cout).astype("float32") + 0.5)
    beta = jnp.asarray(rng.randn(cout).astype("float32"))
    res = (jnp.asarray(rng.randn(n, ohw, ohw, cout).astype("float32"))
           .astype(dt) if residual else None)
    eps = 1e-5

    def fused_loss(x, w, gamma, beta):
        y, s1, s2 = CB.conv_bn_stats(x, w, (stride, stride), (pad, pad))
        m = y.size // y.shape[-1]
        mean = s1 / m
        var = s2 / m - jnp.square(mean)
        out = CB.bn_apply(y, gamma, beta, mean, var, residual=res,
                          eps=eps, act="relu")
        return jnp.sum(out.astype(jnp.float32)) * 1e-6

    def ref_loss(x, w, gamma, beta):
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "OIHW", "NHWC"))
        ys = y.astype(jnp.float32)
        mean = ys.mean((0, 1, 2))
        var = (ys * ys).mean((0, 1, 2)) - jnp.square(mean)
        wv = gamma * jax.lax.rsqrt(var + eps)
        bv = beta - mean * wv
        out = y * wv.astype(y.dtype) + bv.astype(y.dtype)
        if res is not None:
            out = out + res
        return jnp.sum(jax.nn.relu(out).astype(jnp.float32)) * 1e-6

    def make_timed(loss):
        g = jax.grad(loss, (0, 1, 2, 3))

        @jax.jit
        def run(x, w, gamma, beta):
            def body(carry, _):
                x, w, gamma, beta = carry
                dx, dw, dg, db = g(x, w, gamma, beta)
                # feed the grads back so the chain is sequential on device
                return (x + dx * jnp.asarray(1e-3, x.dtype),
                        w + dw * jnp.asarray(1e-3, w.dtype),
                        gamma + dg * 1e-3, beta + db * 1e-3), None
            (x, w, gamma, beta), _ = jax.lax.scan(
                body, (x, w, gamma, beta), None, length=iters)
            return x, gamma

        def timed():
            xs = []
            for _ in range(max(warmup, 1)):
                out = run(x, w, gamma, beta)
            np.asarray(out[1])  # host readback: waits for the device
            for _ in range(repeats):
                t0 = time.perf_counter()
                out = run(x, w, gamma, beta)
                np.asarray(out[1])
                xs.append((time.perf_counter() - t0) * 1e3 / iters)
            return xs

        return timed

    fused_ms = make_timed(fused_loss)()
    ref_ms = make_timed(ref_loss)()
    return fused_ms, ref_ms


def run_convbn(args, peak):
    """--model convbn: per-shape fused-vs-XLA A/B records (BENCH_r07.json
    `convbn_*` slots).  vs_baseline = XLA-composition time / fused time —
    > 1.0 means the fused kernels win that shape; the per-lever protocol
    in PERF.md round 7 reads these before trusting the end-to-end number."""
    shapes = CONVBN_SHAPES_SMOKE if args.smoke else CONVBN_SHAPES
    iters = 2 if args.smoke else 20
    repeats = args.runs or (1 if args.smoke else 3)
    for (label, n, hw, cin, cout, k, stride, residual) in shapes:
        fused_ms, ref_ms = bench_convbn_shape(
            n, hw, cin, cout, k, stride, residual, iters=iters,
            repeats=repeats)
        fmean, fspread, fruns = _mean_spread(fused_ms)
        rmean, rspread, rruns = _mean_spread(ref_ms)
        emit_metric(
            f"convbn_fused_step_ms_{label}", fmean, "ms/iter",
            rmean / fmean if fmean else None, None, 0.0,
            {"batch": n, "hw": hw, "c_in": cin, "c_out": cout,
             "ksize": k, "stride": stride, "residual": residual,
             "iters": iters, "bf16": True,
             "runs": [round(r, 3) for r in fruns],
             "spread": round(fspread, 3),
             "ref_ms": round(rmean, 3),
             "ref_runs": [round(r, 3) for r in rruns],
             "ref_spread": round(rspread, 3)})


def bert_train_flops_per_token(n_layer, d_model, d_ff, seq_len, vocab):
    """Analytic matmul FLOPs per token, encoder-only + MLM head (2 FLOPs
    per MAC, train = 3x fwd)."""
    attn = 4 * d_model * d_model + 2 * seq_len * d_model
    fwd_macs = n_layer * (attn + 2 * d_model * d_ff) + d_model * vocab
    return 3 * 2 * fwd_macs


def bench_bert(batch_size=32, seq_len=128, scan_steps=8, calls=4, warmup=1,
               amp=True, tiny=False, use_flash=True, repeats=1):
    """BERT-base MLM pretraining step (BASELINE.md workload 4: the
    layer_norm/gelu/fused-attention path)."""
    import paddle_tpu as pt
    from paddle_tpu.models import bert as B

    cfg = dict(n_layer=2, n_head=4, d_model=128, d_ff=512,
               vocab=1000) if tiny else dict(
        n_layer=12, n_head=12, d_model=768, d_ff=3072, vocab=30522)
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        avg_loss, _ = B.build_pretrain_net(
            vocab_size=cfg["vocab"], seq_len=seq_len, n_layer=cfg["n_layer"],
            n_head=cfg["n_head"], d_model=cfg["d_model"], d_ff=cfg["d_ff"],
            dropout_rate=0.1, use_flash=use_flash)
    if amp:
        pt.amp.enable(prog)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)

    batches = [B.make_batch(batch_size, seq_len, cfg["vocab"],
                            rng=np.random.RandomState(s))
               for s in range(scan_steps)]
    feed = {k: np.stack([b[k] for b in batches]) for k in batches[0]}

    flops_tok = bert_train_flops_per_token(
        cfg["n_layer"], cfg["d_model"], cfg["d_ff"], seq_len, cfg["vocab"])
    toks_per_call = batch_size * seq_len * scan_steps
    mon = _step_monitor("bert", tokens_per_call=toks_per_call,
                        flops_per_call=flops_tok * toks_per_call)
    ckpt = _ckpt_manager("bert", exe, prog, scope)
    dt, first_loss, last_loss = timed_steps(exe, prog, feed, [avg_loss],
                                            scope, warmup, calls, mon=mon,
                                            ckpt=ckpt, repeats=repeats)
    mem = memory_probe(exe, prog, feed, [avg_loss], scope, batch_size)
    toks = batch_size * seq_len * scan_steps * calls
    return [toks / d for d in dt], flops_tok, first_loss, last_loss, mem


def bench_deepfm(batch_size=4096, scan_steps=8, calls=4, warmup=1,
                 hash_dim=1000001, amp=False):
    """DeepFM CTR step (BASELINE.md workload 5: sparse lookup_table).
    hash_dim defaults to the reference dist_ctr_reader.py scale (1e6+1).
    MFU is not meaningful for a sparse-dominated workload; reports
    examples/sec."""
    import paddle_tpu as pt
    from paddle_tpu.models import deepfm as D

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        avg_cost, _, _, _ = D.build_train_net(hash_dim=hash_dim)
    if amp:
        pt.amp.enable(prog)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)

    batches = [D.make_batch(batch_size, hash_dim=hash_dim,
                            rng=np.random.RandomState(s))
               for s in range(scan_steps)]
    feed = {k: np.stack([b[k] for b in batches]) for k in batches[0]}

    mon = _step_monitor("deepfm",
                        examples_per_call=batch_size * scan_steps)
    ckpt = _ckpt_manager("deepfm", exe, prog, scope)
    dts, first_loss, last_loss = timed_steps(exe, prog, feed, [avg_cost],
                                             scope, warmup, calls, mon=mon,
                                             ckpt=ckpt)
    eps = batch_size * scan_steps * calls / dts[0]
    return eps, first_loss, last_loss


def bench_mnist(batch_size=512, scan_steps=16, calls=2, warmup=1, amp=True):
    """LeNet-5 MNIST train step (BASELINE.md workload 1) — smoke-scale."""
    import paddle_tpu as pt
    from paddle_tpu.models import mnist as M

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        img, label, avg_cost, acc, _ = M.build_train_net()
        pt.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    if amp:
        pt.amp.enable(prog)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)

    # learnable synthetic digits (class k = bright k x k corner patch) so
    # the loss demonstrably decreases — mirrors tests/test_mnist.py
    rng = np.random.RandomState(0)
    x = rng.rand(scan_steps, batch_size, 1, 28, 28).astype("float32") * 0.1
    y = rng.randint(0, 10, (scan_steps, batch_size, 1)).astype("int64")
    for s in range(scan_steps):
        for b in range(batch_size):
            k = int(y[s, b, 0])
            x[s, b, 0, k:k + 3, k:k + 3] += 1.0
    feed = {"pixel": x, "label": y}
    mon = _step_monitor("mnist", examples_per_call=batch_size * scan_steps)
    ckpt = _ckpt_manager("mnist", exe, prog, scope)
    dts, first_loss, last_loss = timed_steps(exe, prog, feed, [avg_cost],
                                             scope, warmup, calls, mon=mon,
                                             ckpt=ckpt)
    mem = memory_probe(exe, prog, feed, [avg_cost], scope, batch_size)
    mem.update(cost_probe(prog, batch_size, "bench.mnist"))
    ips = batch_size * scan_steps * calls / dts[0]
    return ips, first_loss, last_loss, mem


def run_bert(args, peak):
    # bs 128 measured best on v5e (35.5% MFU vs 28.9% at bs 32; 256
    # regresses under scan memory pressure) — PERF.md r04
    bs = args.batch_size or (4 if args.smoke else 128)
    seq = 64 if args.smoke else 128
    repeats = _repeats(args)
    tps_runs, flops_tok, loss0, loss, mem = bench_bert(
        batch_size=bs, seq_len=seq,
        scan_steps=args.scan_steps or (2 if args.smoke else 16),
        calls=args.calls or (1 if args.smoke else 2),
        amp=args.amp, tiny=args.smoke, repeats=repeats)
    tps, spread, runs = _mean_spread(tps_runs)
    mfu = (tps * flops_tok / peak) if peak else None
    # no committed reference BERT number: vs_baseline is the ratio to the
    # BASELINE.json north star (50% MFU on this chip)
    config = {"bf16": args.amp, "batch": bs, "seq_len": seq,
              "tiny": args.smoke,
              "runs": [round(r, 1) for r in runs],
              "spread": round(spread, 1)}
    config.update(mem)
    emit_metric("bert_base_train_tokens_per_sec_per_chip", tps, "tokens/sec",
                mfu / 0.50 if mfu is not None else None, mfu, loss,
                config, loss_first=loss0)


def run_deepfm(args, peak):
    bs = args.batch_size or (64 if args.smoke else 4096)
    hash_dim = 10001 if args.smoke else 1000001
    # r04 recorded 49.8k (BENCH_r04) vs 39.4k (PERF.md) from single runs —
    # repeat and report mean+-spread so the number is trustworthy
    repeats = _repeats(args)
    runs = []
    loss0 = loss = None
    for _ in range(repeats):
        eps_i, loss0, loss = bench_deepfm(
            batch_size=bs,
            scan_steps=args.scan_steps or (2 if args.smoke else 8),
            calls=args.calls or (1 if args.smoke else 2),
            hash_dim=hash_dim)
        runs.append(eps_i)
    eps, spread, runs = _mean_spread(runs)
    # gather-bound workload: MFU is meaningless; report the analytic HBM
    # traffic of the sparse path (embedding gathers fwd + row-sparse
    # scatter bwd + lazy-adam moment updates on touched rows) vs the v5e
    # roofline (~800 GB/s), plus throughput vs the committed target
    from paddle_tpu.models import deepfm as D

    emb_bytes = D.SPARSE_SLOTS * (10 + 1) * 4  # per-example rows (k=10 + w1)
    bytes_per_ex = emb_bytes * (1 + 2 + 4)  # fwd + grad r/w + m,v r/w
    hbm_gbps = eps * bytes_per_ex / 1e9
    from paddle_tpu.flags import FLAGS as _FLAGS

    emit_metric("deepfm_ctr_train_examples_per_sec_per_chip", eps,
                "examples/sec", eps / DEEPFM_TARGET_EXAMPLES_PER_SEC,
                None, loss,
                {"batch": bs, "hash_dim": hash_dim, "sparse": True,
                 # the r08 A/B knob: run once with FLAGS_fused_embedding=0
                 # for the per-slot baseline record (tools/run_ci.sh does)
                 "fused_embedding": bool(_FLAGS.fused_embedding),
                 "runs": [round(r, 1) for r in runs],
                 "spread": round(spread, 1),
                 "hbm_gbps_analytic": round(hbm_gbps, 2),
                 "hbm_roofline_frac": round(hbm_gbps / 800.0, 4),
                 "bound": "dispatch/gather-latency (not HBM, not MXU)"},
                loss_first=loss0)


def run_mnist(args, peak):
    bs = args.batch_size or (64 if args.smoke else 512)
    ips, loss0, loss, mem = bench_mnist(
        batch_size=bs,
        scan_steps=args.scan_steps or (2 if args.smoke else 16),
        calls=args.calls or (1 if args.smoke else 2),
        amp=args.amp)
    # no reference MNIST throughput number exists: vs_baseline is the
    # ratio to the committed round-4 target (no-regression contract)
    config = {"bf16": args.amp, "batch": bs}
    config.update(mem)
    emit_metric("mnist_lenet5_train_images_per_sec_per_chip", ips,
                "images/sec", ips / MNIST_TARGET_IMGS_PER_SEC, None, loss,
                config, loss_first=loss0)


def run_resnet50(args, peak):
        if args.smoke:
            bs = args.batch_size or 8
            ips, loss0, loss, mem = bench_resnet50(
                batch_size=bs, scan_steps=2, calls=1, warmup=1,
                image_size=64, depth=18, amp=args.amp, stream=args.stream,
                data_format=args.data_format)
            mfu = None  # smoke runs ResNet-18@64: the R50@224 FLOPs no longer apply
            config = {"bf16": args.amp, "batch": bs, "image": 64,
                      "depth": 18, "data_format": args.data_format}
        else:
            bs = args.batch_size or 256
            ips, loss0, loss, mem = bench_resnet50(
                batch_size=bs, scan_steps=args.scan_steps or 16,
                calls=args.calls or 2, amp=args.amp, stream=args.stream,
                data_format=args.data_format)
            mfu = (ips * RESNET50_TRAIN_FLOPS_PER_IMG / peak) if peak else None
            config = {"bf16": args.amp, "batch": bs, "image": 224,
                      "depth": 50, "stream": args.stream,
                      "data_format": args.data_format}
        config.update(mem)
        emit_metric("resnet50_train_images_per_sec_per_chip", ips,
                    "images/sec", ips / REFERENCE_RESNET50_IMGS_PER_SEC,
                    mfu, loss, config, loss_first=loss0)


def run_transformer(args, peak):
        bs = args.batch_size or (2 if args.smoke else 64)
        seq = 64 if args.smoke else 256
        repeats = _repeats(args)
        tps_runs, flops_tok, loss0, loss, mem = bench_transformer(
            batch_size=bs, seq_len=seq,
            scan_steps=args.scan_steps or (2 if args.smoke else 32),
            calls=args.calls or (1 if args.smoke else 2),
            amp=args.amp, tiny=args.smoke, repeats=repeats,
            recompute=args.recompute)
        tps, spread, runs = _mean_spread(tps_runs)
        # flops_tok matches the model actually run (tiny config in smoke)
        mfu = (tps * flops_tok / peak) if peak else None
        # no committed reference transformer number exists: vs_baseline is
        # the ratio to the BASELINE.json north star (50% MFU on this chip)
        config = {"bf16": args.amp, "batch": bs, "seq_len": seq,
                  "tiny": args.smoke,
                  # the r12 A/B knob: --recompute pairs a rewritten
                  # record next to this one (tools/run_ci.sh does)
                  "recompute": bool(args.recompute),
                  "runs": [round(r, 1) for r in runs],
                  "spread": round(spread, 1)}
        config.update(mem)
        emit_metric("transformer_base_train_tokens_per_sec_per_chip", tps,
                    "tokens/sec", mfu / 0.50 if mfu is not None else None,
                    mfu, loss, config, loss_first=loss0)


def run_pipeline(args, peak):
    """`--model transformer --pp N`: the pipeline-parallel training leg
    (parallel/pipeline).  Runs pp-stage GPipe AND 1F1B micro-batch
    schedules against single-program run_accumulated from identical
    init, asserts the LOSS TRAJECTORIES ARE BIT-IDENTICAL (dropout on —
    the subsystem's core numeric contract), and reports tokens/sec for
    each variant; config carries pp/schedule/micro_batches/bit_parity +
    the schedule's analytic bubble fraction.  run_ci.sh archives the
    three paired records as ci_artifacts/bench_pipeline_smoke.json."""
    import paddle_tpu as pt
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models import transformer as T
    from paddle_tpu.parallel.pipeline import (
        PipelineProgram, bubble_fraction, split_program)

    pp = args.pp
    tiny = args.smoke
    cfg = dict(n_layer=max(2, pp), n_head=4, d_key=16, d_value=16,
               d_model=64, d_inner_hid=128, vocab=256,
               seq=32) if tiny else dict(
        n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
        d_inner_hid=2048, vocab=2048, seq=32)
    k = args.scan_steps or 4                       # micro-batches
    mbs = args.batch_size or 2                     # micro-batch size
    steps = args.calls or 2

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup), fw.guard_unique_name():
        avg_cost, _, feeds = T.transformer(
            src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
            max_length=cfg["seq"], n_layer=cfg["n_layer"],
            n_head=cfg["n_head"], d_key=cfg["d_key"],
            d_value=cfg["d_value"], d_model=cfg["d_model"],
            d_inner_hid=cfg["d_inner_hid"], dropout_rate=0.1,
            src_seq_len=cfg["seq"], trg_seq_len=cfg["seq"],
            use_flash=False)
        pt.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    loss = avg_cost.name
    stages = split_program(prog, feeds, n_stages=pp)
    pnames = [p.name for p in prog.all_parameters()]

    batches = [T.make_batch(mbs, cfg["seq"], cfg["seq"], cfg["n_head"],
                            cfg["vocab"], cfg["vocab"],
                            rng=np.random.RandomState(s))
               for s in range(k)]
    feed = {n: np.stack([b[n] for b in batches]) for n in batches[0]}
    toks_per_step = k * mbs * cfg["seq"]

    def run_variant(runner_for):
        """Fresh scope from the shared init; returns (traj, tokens/sec,
        final param snapshot)."""
        scope = pt.Scope()
        exe = pt.Executor()
        with pt.scope_guard(scope):
            exe.run(startup, scope=scope)
            for n, v in run_variant.init.items():
                scope.set_var(n, v)
            step = runner_for(exe, scope)
            traj = [np.asarray(step())]          # warmup incl. compile
            t0 = time.perf_counter()
            for _ in range(steps):
                traj.append(np.asarray(step()))
            dt = time.perf_counter() - t0
            params = {n: np.asarray(scope.find_var(n)) for n in pnames}
        return traj, steps * toks_per_step / dt, params

    scope0 = pt.Scope()
    exe0 = pt.Executor()
    with pt.scope_guard(scope0):
        exe0.run(startup, scope=scope0)
        run_variant.init = {n: np.asarray(scope0.find_var(n)).copy()
                            for n in pnames}

    traj_single, tps_single, params_single = run_variant(
        lambda exe, scope: lambda: exe.run_accumulated(
            prog, feed=feed, fetch_list=[loss], scope=scope)[0])
    variants = {"single": (traj_single, tps_single, None, 0.0)}
    # ONE PipelineProgram: compiled stage entries are schedule-
    # independent, so GPipe and 1F1B share them
    pipe = PipelineProgram(prog, feeds, schedule="gpipe", stages=stages)
    for sched in ("gpipe", "1f1b"):
        pipe.schedule = sched
        traj, tps, params = run_variant(
            lambda exe, scope: lambda: exe.run(
                pipe, feed=feed, fetch_list=[loss], scope=scope)[0])
        # the pipeline parity CONTRACT (PERF.md r11): training STATE
        # bit-identical; fetched loss to the ulp (a reduce feeding only
        # a fetched scalar may round differently across separately
        # compiled modules — params never drift)
        state_parity = all(
            np.array_equal(params_single[n], params[n]) for n in pnames)
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = max(
                float(np.nanmax(np.abs(a - b) / np.maximum(
                    np.abs(a), 1e-30)))
                for a, b in zip(traj_single, traj))
        variants[sched] = (traj, tps, state_parity, rel)

    for name, (traj, tps, parity, rel) in variants.items():
        emit_metric(
            f"transformer_pp{pp}_{name}_tokens_per_sec", tps,
            "tokens/sec", None, None, float(np.asarray(traj[-1]).mean()),
            {"pp": pp, "schedule": name, "micro_batches": k,
             "micro_batch_size": mbs, "seq_len": cfg["seq"],
             "tiny": tiny, "dropout": 0.1,
             "state_bit_parity": parity,
             "loss_max_rel_diff": rel,
             "bubble_fraction": (round(bubble_fraction(pp, k, name), 4)
                                 if name != "single" else 0.0)})
    bad = [n for n, (_, _, p, rel) in variants.items()
           if p is False or (rel is not None and rel > 3e-7)]
    if bad:
        raise AssertionError(
            f"pipeline schedules {bad} lost parity vs single-program "
            f"run_accumulated (state must be bit-identical, losses "
            f"within 1 ulp)")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="all",
                   choices=["all", "resnet50", "transformer", "bert",
                            "deepfm", "mnist", "ringattn", "convbn",
                            "decode", "dispatch"])
    p.add_argument("--pp", type=int, default=0,
                   help="with --model transformer: run the pp-stage "
                        "pipeline-parallel leg (GPipe + 1F1B vs single-"
                        "program run_accumulated, loss bit-parity "
                        "asserted) instead of the dense bench")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes for a fast correctness pass")
    p.add_argument("--recompute", action="store_true",
                   help="with --model transformer: apply the activation-"
                        "recompute pass (paddle_tpu/memory, auto sqrt(N) "
                        "segments) to the trained program before timing — "
                        "the r12 A/B leg; the record carries the planner's "
                        "before/after activation peaks + est FLOPs factor")
    p.add_argument("--no-amp", dest="amp", action="store_false")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--scan-steps", type=int, default=None)
    p.add_argument("--calls", type=int, default=None)
    p.add_argument("--runs", type=int, default=None,
                   help="repeat the timed region N times and report "
                        "mean + runs[] + spread (transformer/bert/deepfm/"
                        "convbn; "
                        "default 3 full, 1 smoke)")
    p.add_argument("--data-format", default="NHWC",
                   choices=["NHWC", "NCHW"],
                   help="resnet50 conv layout (NHWC is ~18%% faster on "
                        "v5e; NCHW for reference-parity comparison)")
    p.add_argument("--stream", action="store_true",
                   help="resnet50: stream fresh host batches through the "
                        "double-buffer prefetcher instead of a cached "
                        "device batch")
    p.add_argument("--monitor-snapshot", default=None, metavar="PATH",
                   help="with FLAGS_monitor=1: write a Prometheus-text "
                        "metrics snapshot to PATH after all workloads "
                        "(plus PATH.jsonl with the JSONL exposition)")
    args = p.parse_args()

    from paddle_tpu.flags import FLAGS
    from paddle_tpu.inference import enable_compile_cache

    enable_compile_cache()
    if FLAGS.monitor:
        # black box + scrape endpoint for the whole bench run: a SIGTERM'd
        # or crashed bench leaves flight-*.jsonl under FLAGS_flight_dir,
        # and FLAGS_monitor_port serves /metrics /health /flight live
        from paddle_tpu.monitor import flight, serve

        flight.install()
        try:
            serve.start()
        except OSError as e:  # port taken: telemetry must not fail the run
            print(f"[bench] monitor endpoint disabled: {e}",
                  file=sys.stderr)

    peak = _peak_flops()
    # Default run prints one metric line per workload, each emitted the
    # moment it is measured (a crash in one workload cannot zero the rest,
    # but it does fail the run: main() returns non-zero).
    # The driver parses the LAST line, so resnet50 (the metric tracked
    # since round 1) stays last.
    ran = []
    if args.model in ("all", "mnist"):
        ran.append(run_guarded("mnist", run_mnist, args, peak))
    if args.model in ("all", "deepfm"):
        ran.append(run_guarded("deepfm", run_deepfm, args, peak))
    if args.model == "convbn":
        # per-lever A/B microbench (PERF.md r07); not part of "all" so the
        # full-bench content and the resnet50-last line stay unchanged —
        # the driver runs it explicitly: python bench.py --model convbn
        ran.append(run_guarded("convbn", run_convbn, args, peak))
    if args.model == "decode":
        # generation workload (PERF.md r10): tokens/sec decode at batch
        # 1 and 64 with the kv_cache/flash_decode flags in the record;
        # explicit-only for the same resnet50-last reason —
        # python bench.py --model decode (run_ci.sh pairs the
        # FLAGS_kv_cache=0 recompute baseline next to it)
        ran.append(run_guarded("decode", run_decode, args, peak))
    if args.model == "dispatch":
        # per-launch overhead microbench (the cost model's launch-term
        # constant); explicit-only like convbn/decode —
        # python bench.py --model dispatch
        ran.append(run_guarded("dispatch", run_dispatch, args, peak))
    if args.model in ("all", "ringattn"):
        ran.append(run_guarded("ringattn", run_ringattn, args, peak))
    if args.model in ("all", "bert"):
        ran.append(run_guarded("bert", run_bert, args, peak))
    if args.model == "transformer" and args.pp:
        # pipeline-parallel leg (PERF.md r11): explicit-only, like
        # convbn/decode — python bench.py --model transformer --pp 2
        ran.append(run_guarded("pipeline", run_pipeline, args, peak))
    elif args.model in ("all", "transformer"):
        ran.append(run_guarded("transformer", run_transformer, args, peak))
    if args.model in ("all", "resnet50"):
        ok = run_guarded("resnet50", run_resnet50, args, peak)
        if not ok:
            # the driver records the LAST line as the round-tracked
            # resnet50 metric: on failure emit an explicit null line so a
            # different workload's number is never mis-attributed to it
            print(json.dumps({
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": None, "unit": "images/sec", "vs_baseline": 0.0,
                "error": "workload failed (see stderr)",
            }), flush=True)
        ran.append(ok)

    if args.monitor_snapshot:
        from paddle_tpu.flags import FLAGS
        from paddle_tpu.monitor import default_registry

        if FLAGS.monitor:
            # a bad path must not turn a measured bench run into a
            # failure — the metric lines already printed are the product
            try:
                import os

                d = os.path.dirname(args.monitor_snapshot)
                if d:
                    os.makedirs(d, exist_ok=True)
                reg = default_registry()
                reg.write_prometheus(args.monitor_snapshot)
                reg.write_jsonl(args.monitor_snapshot + ".jsonl")
                print(f"[bench] metrics snapshot: {args.monitor_snapshot} "
                      f"(+ .jsonl)", file=sys.stderr)
            except OSError as e:
                print(f"[bench] metrics snapshot failed: {e}",
                      file=sys.stderr)
        else:
            print("[bench] --monitor-snapshot ignored: FLAGS_monitor is "
                  "off", file=sys.stderr)
    # every requested workload must have run to its metric line
    return 0 if ran and all(ran) else 1


if __name__ == "__main__":
    sys.exit(main())
