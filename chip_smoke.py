#!/usr/bin/env python
"""chip_smoke.py — does the system still start on the chip?

One process, one TPU chip, the normal entry points, transformer-base at
its full width (the one model this repo trains, decodes and serves):

  preflight   jax's first device is a TPU whose device_kind has peaks in
              analysis/costmodel.py; versions printed.
  train       bench.py's full transformer configuration (6+6 layers,
              d_model 512, 8 x 64 heads, d_ff 2048, vocab 32000, batch 64
              x seq 256, dropout 0.1, Adam, bf16 amp) through
              Executor.run_steps: loss finite and falling, parameters
              resident on the TPU, Mosaic kernels in the compiled step.
  moe         a small latent-attention (d_qk 192, d_v 128) + expert-layer
              + MTP stack (models.mla_moe_decoder, 4 of 16 experts held)
              through Executor.run_steps in bf16 amp: loss finite and
              falling, the flash and grouped-matmul kernels all Mosaic
              calls of the compiled step.
  generate    bench.py's decode geometry (source 256, 64 new tokens, f32)
              as a GenerationServingModel behind an in-process
              InferenceServer: HTTP :generate requests of several prompt
              lengths, two concurrent; compile count flat after warmup;
              Mosaic kernels in the decode program; first-step logits
              against the XLA reference route at highest matmul precision.
  kernels     every must_accept row of analysis/kernel_lint.py's ten
              canonical matrices, built at the row's shape, compiled with
              interpret=False and compared with its family's in-repo
              reference: compiled / refused (Mosaic's words) / mismatch /
              xla_by_design.

`--chips 4` (run by hand on a four-chip host; the default stays one chip)
adds the sharded leg: the train configuration as a ShardedProgram over a
data 2 x model 2 mesh, against the one-chip trajectory from the same seed.

It exits non-zero unless every leg passed and has no argument or variable
that lets it pass without a TPU.  On success the last line of stdout is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Timings it prints are smoke timings (compile seconds, cold vs warm cache),
never a rate under a benchmark metric name.

The leg functions are importable: tests/test_chip_smoke.py runs them on
the CPU at a tiny width with interpret=True, which only relaxes what a
CPU cannot show (TPU residency, Mosaic custom calls).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
import urllib.request
import zlib

import numpy as np

import bench

# ---------------------------------------------------------------------------
# tolerances, each with its reason.  Errors are max|got - ref| over
# max|ref| of the compared array ("of range").
# ---------------------------------------------------------------------------

#: f32 kernel vs its reference run at jax.default_matmul_precision
#: ("highest"): Mosaic's f32 dots may take fewer bf16 passes than XLA's
#: six, and the blocked online softmax sums in another order.
TOL_F32 = 2e-2
#: bf16 operands: one rounding of every stored tile (2^-8 relative) on top
#: of the f32 case; the repo's own Test*TPU classes use the same bound.
TOL_BF16 = 5e-2
#: directional finite difference of a dropout kernel (mask fixed by the
#: seed, dots at highest precision) against its analytic gradient: the
#: difference quotient's own truncation + f32 cancellation error at eps
#: 1e-2.  A backward pass that regenerated a DIFFERENT mask than the
#: forward misses by O(rate) = 0.1+.
TOL_FD = 5e-2
#: served decode logits (default TPU matmul precision: one bf16 pass per
#: f32 dot, through 6 decoder layers and the vocab projection) against the
#: XLA reference route at highest precision.
TOL_LOGITS_SERVED = 5e-2
#: the same programs compiled at highest precision against that reference:
#: only the flash-decode kernel's f32 summation order differs (3.4e-7 on
#: the chip at PR 21).
TOL_LOGITS_STRICT = 1e-4
#: four-chip loss against the one-chip loss, same seed, batches and
#: dropout masks, bf16 amp: row-parallel matmuls reduce over the model
#: axis (psum) and the batch mean over the data axis, so every reduction
#: re-associates; the one-chip route runs the flash kernels where the
#: sharded one runs the XLA references; Adam carries the difference
#: forward a few steps (2.3e-5 over 4 steps on the chip at PR 21).
TOL_SHARDED_LOSS = 2e-3

TRAIN_FULL = dict(cfg=bench.TRANSFORMER_BASE, batch=64, seq=256,
                  scan_steps=4, calls=3)
GENERATE_FULL = dict(cfg=bench.DECODE_BASE, slots=4)


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-6))


def _mosaic_calls(hlo_text: str) -> int:
    return hlo_text.count("tpu_custom_call")


def _entry_hlo(exe, program, feed, scope) -> str:
    """Optimized HLO of the executor's most recent compile, re-lowered on
    the live arguments (tools/hlo_diag.py lower_entry; with the compile
    cache on this is a cache read, not a second compile)."""
    from paddle_tpu.core.executor import latest_jitted_entry, prng_key

    entry = latest_jitted_entry(exe)
    args = [[exe._to_device_array(program, n, feed[n])
             for n in sorted(feed)],
            [scope.find_var(n) for n in entry.rw_state],
            [scope.find_var(n) for n in entry.ro_state]]
    if entry.needs_key:
        args.append(prng_key(0))
    return entry.jitted.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# preflight
# ---------------------------------------------------------------------------


def preflight() -> dict:
    """The device line; exits the process (no result printed) unless jax's
    first device is a TPU the peaks table knows."""
    import jax
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — the version is only printed
        libtpu = "unknown"
    dev = bench._device()
    _say(f"device platform={dev['platform']} kind={dev['kind']!r} "
         f"count={dev['count']} jax={jax.__version__} "
         f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax.devices()[0].platform is "
                 f"{dev['platform']!r}; this script only passes on the "
                 f"chip (run it through the chip tool)")
    from paddle_tpu.analysis.costmodel import DEVICE_MODELS

    if dev["kind"] not in DEVICE_MODELS:
        sys.exit(f"chip_smoke: device_kind {dev['kind']!r} has no entry in "
                 f"analysis/costmodel.py DEVICE_MODELS "
                 f"(known: {sorted(DEVICE_MODELS)})")
    dm = DEVICE_MODELS[dev["kind"]]
    _say(f"peaks {dm.peak_flops / 1e12:.0f} TFLOP/s bf16, "
         f"{dm.hbm_bytes_per_s / 1e9:.0f} GB/s ({dm.source})")
    return dev


# ---------------------------------------------------------------------------
# train leg
# ---------------------------------------------------------------------------


def train_leg(cfg=None, batch=64, seq=256, scan_steps=4, calls=3,
              interpret=False) -> dict:
    """bench.py's transformer train step through Executor.run_steps."""
    import jax

    import paddle_tpu as pt

    cfg = cfg or bench.TRANSFORMER_BASE
    fails = []
    prog, startup, avg_cost, _ = bench.build_transformer_train(cfg, seq)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    feed = bench.transformer_feed(cfg, batch, seq, scan_steps)

    t0 = time.perf_counter()
    (losses,) = exe.run_steps(prog, feed=feed, fetch_list=[avg_cost],
                              scope=scope)
    first = np.asarray(losses, np.float64).reshape(-1)
    compile_s = time.perf_counter() - t0  # first call: trace + compile + run
    t0 = time.perf_counter()
    for _ in range(calls):
        (losses,) = exe.run_steps(prog, feed=feed, fetch_list=[avg_cost],
                                  scope=scope)
    last = np.asarray(losses, np.float64).reshape(-1)
    steady_s = (time.perf_counter() - t0) / calls

    if not (np.all(np.isfinite(first)) and np.all(np.isfinite(last))):
        fails.append(f"non-finite loss: {first} .. {last}")
    elif not last[-1] < first[0]:
        fails.append(f"loss did not fall: {first[0]:.4f} -> {last[-1]:.4f}")

    params = [p.name for p in prog.all_parameters()]
    dev0 = jax.devices()[0]
    off = [n for n in params
           if not (isinstance(scope.find_var(n), jax.Array)
                   and scope.find_var(n).devices() == {dev0})]
    if off:
        fails.append(f"{len(off)} parameters are not jax.Arrays on "
                     f"{dev0}: {off[:3]}")
    mosaic = _mosaic_calls(_entry_hlo(exe, prog, feed, scope))
    if not interpret:
        if dev0.platform != "tpu":
            fails.append(f"parameters live on {dev0.platform}, not the TPU")
        if mosaic == 0:
            fails.append("no Mosaic custom call (tpu_custom_call) in the "
                         "compiled train step: every kernel fell back")
    _say(f"train: loss {first[0]:.4f} -> {last[-1]:.4f} over "
         f"{(calls + 1) * scan_steps} steps, {len(params)} params on "
         f"{dev0}, mosaic_calls={mosaic}, smoke compile+first call "
         f"{compile_s:.1f}s, smoke steady call {steady_s:.2f}s")
    return dict(leg="train", ok=not fails, failures=fails,
                loss_first=float(first[0]), loss_last=float(last[-1]),
                losses=[float(x) for x in np.concatenate([first, last])],
                params=len(params), mosaic_calls=mosaic,
                compile_s=round(compile_s, 2))


# ---------------------------------------------------------------------------
# expert-layer leg
# ---------------------------------------------------------------------------

#: a small MLA + MoE + MTP stack at shapes Mosaic tiles (d_qk 192, d_v 128)
MOE_SMALL = dict(
    vocab_size=1024, seq_len=256, batch=2, d_model=256, n_head=4,
    q_lora_rank=128, kv_lora_rank=128, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128, n_dense=1, n_moe=1, d_ff_dense=512, d_ff_expert=256,
    n_experts=16, n_held=4, expert_offset=4, top_k=4, n_mtp=1,
    bias_std=0.01, lr=1e-3)
MOE_KERNELS = ("flash_bhtd_fwd", "flash_bhtd_bwd_dq", "flash_bhtd_bwd_dkv",
               "moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw")


def _moe_run(sizes, scan_steps, calls):
    """(losses of calls + 1 run_steps calls, the entry's HLO, the last
    call's device counters) of the expert-layer stack at `sizes`."""
    import paddle_tpu as pt
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.models import mla_moe_decoder as M
    from paddle_tpu.monitor import flight

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        loss, _ = M.build_train_net(**sizes)
    pt.amp.enable(prog)
    scope, exe = pt.Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.default_rng(7)
    b, t = sizes["batch"], sizes["seq_len"]
    feed = {"ids": rng.integers(0, sizes["vocab_size"],
                                (scan_steps, b, t + 2, 1), dtype=np.int32),
            "loss_weight": np.ones((scan_steps, b, t, 1), np.float32)}
    losses = []
    for i in range(calls + 1):
        FLAGS.monitor = i == calls  # the last call reads the counters back
        try:
            (out,) = exe.run_steps(prog, feed=feed, fetch_list=[loss],
                                   scope=scope)
        finally:
            FLAGS.reset("monitor")
        losses.append(np.asarray(out, np.float64).reshape(-1))
    event = flight.default_recorder().events(kind="executor.run_steps")[-1]
    return losses, _entry_hlo(exe, prog, feed, scope), event["counters"]


def moe_leg(sizes=None, scan_steps=2, calls=3, interpret=False) -> dict:
    """A small latent-attention + expert-layer + MTP train step (models.
    mla_moe_decoder, bf16 amp) through Executor.run_steps, twice: one
    chip's share of the experts (a trip or two of the expert layer's walk)
    and every expert held (four trips a layer: the grouped matmuls inside
    a loop whose bound is traced, at more than one trip).  Loss finite and
    falling, every kernel of MOE_KERNELS a Mosaic call of the compiled
    step (no silent fallback to the XLA routes), and the rows walked what
    the held pairs need."""
    from paddle_tpu.ops.llm_ops import chunk_rows

    sizes = dict(sizes or MOE_SMALL)
    layers_ = sizes["n_moe"] + sizes["n_mtp"]
    pairs = sizes["batch"] * sizes["seq_len"] * sizes["top_k"]
    fails, rep = [], {}
    for tag, over in (("share", {}), ("all_held", dict(
            n_held=sizes["n_experts"], expert_offset=0))):
        run = dict(sizes, **over)
        chunk = chunk_rows(pairs, run["n_held"], run["n_experts"])
        losses, hlo, counters = _moe_run(run, scan_steps, calls)
        if not all(np.all(np.isfinite(x)) for x in losses):
            fails.append(f"{tag}: non-finite loss: {losses}")
        elif not losses[-1][-1] < losses[0][0]:
            fails.append(f"{tag}: loss did not fall: {losses[0][0]:.4f} -> "
                         f"{losses[-1][-1]:.4f}")
        absent = [] if interpret else [k for k in MOE_KERNELS
                                       if k not in hlo]
        if absent:
            fails.append(f"{tag}: kernels that fell back to XLA (no Mosaic "
                         f"call of that name in the compiled step): {absent}")
        walked, held = (counters["moe_rows_walked"],
                        counters["moe_local_pairs"])
        if not held <= walked < held + layers_ * chunk:
            fails.append(f"{tag}: {walked} rows walked for {held} pairs")
        if over and walked != layers_ * pairs:
            fails.append(f"{tag}: {walked} rows walked, not every chunk of "
                         f"{layers_} layers x {pairs} pairs")
        _say(f"moe {tag}: loss {losses[0][0]:.4f} -> {losses[-1][-1]:.4f} "
             f"over {(calls + 1) * scan_steps} steps, rows walked "
             f"{walked:.0f} for {held:.0f} pairs (chunk {chunk}), "
             f"mosaic_calls={_mosaic_calls(hlo)}, "
             f"absent={absent}")
        rep[tag] = dict(loss_first=float(losses[0][0]),
                        loss_last=float(losses[-1][-1]),
                        rows_walked=float(walked), pairs=float(held),
                        mosaic_calls=_mosaic_calls(hlo))
    return dict(leg="moe", ok=not fails, failures=fails,
                loss_first=rep["share"]["loss_first"],
                loss_last=rep["share"]["loss_last"],
                mosaic_calls=rep["share"]["mosaic_calls"],
                all_held=rep["all_held"])


# ---------------------------------------------------------------------------
# generate leg
# ---------------------------------------------------------------------------


def _post_generate(port, name, prompt, max_tokens):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}:generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_tokens": max_tokens,
                         "timeout_s": 300.0}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=330.0) as r:
        return r.status, json.loads(r.read())


def _decode_feed(p, active: float) -> dict:
    """The decode program's feed with every slot (in)active; programs
    that self-feed their token take no gen_token."""
    feed = {"gen_active": np.full((p.lanes, 1), active, np.float32)}
    if not p.self_feed_token:
        feed["gen_token"] = np.full((p.lanes, 1), p.bos_id, np.int64)
    return feed


def _first_step_logits(sess, prompts):
    """Logits of the first decode step after a prefill of `prompts`
    (every slot active), straight from the session's own programs."""
    p = sess.p
    sess.prefill(prompts)
    (logits,) = sess.exe.run(p.decode, feed=_decode_feed(p, 1.0),
                             fetch_list=[p.logits_name], scope=sess.scope)
    return np.asarray(logits, np.float64).reshape(p.lanes, -1)


def generate_leg(cfg=None, slots=4, interpret=False) -> dict:
    """bench.py's decode geometry served over HTTP in this process."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.generation import GenerationSession
    from paddle_tpu.models.transformer import build_generation_programs
    from paddle_tpu.serving import InferenceServer
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationServingModel)

    cfg = cfg or bench.DECODE_BASE
    fails = []
    name = "smoke"
    model_kw = bench.decode_model_kw(cfg)
    model = GenerationServingModel(GenerationConfig(
        name, slots=slots, max_tokens=cfg["max_out"], **model_kw))
    p = model.session.p
    for prog in (p.prefill, p.decode, p.startup):
        prog.random_seed = 11
    model.init_params()

    server = InferenceServer(host="127.0.0.1", port=0)
    server.add_generation_model(model)
    t0 = time.perf_counter()
    port = server.start(warmup=True)
    compile_s = time.perf_counter() - t0
    try:
        warm = model.compile_count
        mosaic = _mosaic_calls(_entry_hlo(
            model.session.exe, p.decode, _decode_feed(p, 0.0),
            model.session.scope))

        rng = np.random.RandomState(0)
        n_src = cfg["src_len"]
        # prompt lengths across the range, one full; token budgets vary
        lens = sorted({1, max(2, n_src // 8), max(3, n_src // 2), n_src})
        jobs = [(rng.randint(2, cfg["vocab"], (n,)), want)
                for n, want in zip(
                    lens + lens[:2],
                    [cfg["max_out"], 7, cfg["max_out"] // 2, 3, 5,
                     cfg["max_out"]])]
        replies = [None] * len(jobs)

        def ask(i):
            try:
                replies[i] = _post_generate(port, name, *jobs[i])
            except Exception as e:  # noqa: BLE001 — reported per request
                replies[i] = (None, {"error": f"{type(e).__name__}: {e}"})

        for i in range(len(jobs) - 2):          # one at a time ...
            ask(i)
        pair = [threading.Thread(target=ask, args=(i,))
                for i in (len(jobs) - 2, len(jobs) - 1)]
        for t in pair:                          # ... then two at once
            t.start()
        for t in pair:
            t.join(timeout=400.0)
        for i, ((prompt, want), rep) in enumerate(zip(jobs, replies)):
            status, body = rep if rep is not None else (None, {})
            got = len(body.get("tokens", ()))
            if status != 200 or got != want:
                fails.append(f"request {i} (prompt {len(prompt)}, "
                             f"max_tokens {want}): status {status}, "
                             f"{got} tokens, {body.get('error', '')}")
        all_answered = not fails
        flat = model.compile_count == warm
        if not flat:
            fails.append(f"compile count grew after warmup: {warm} -> "
                         f"{model.compile_count}")
    finally:
        server.stop()

    if not interpret and mosaic == 0:
        fails.append("no Mosaic custom call (tpu_custom_call) in the "
                     "compiled decode program: every kernel fell back")

    # first-step logits.  Reference = the same model with both decode
    # kernel routes off (FLAGS read at build AND at trace time), XLA
    # attention in the encoder, compiled at highest matmul precision —
    # the op chain kernels/decode_step.reference_decode_step spells out.
    scope = model.session.scope
    prompts = rng.randint(2, cfg["vocab"],
                          (slots, n_src, 1)).astype(np.int64)
    prompts[1:, n_src // 2:] = 0  # ragged: pad ids end a prompt
    served = _first_step_logits(model.session, prompts)
    with jax.default_matmul_precision("highest"):
        strict_sess = GenerationSession(p, scope=scope,
                                        executor=pt.Executor())
        strict = _first_step_logits(strict_sess, prompts)
        saved = FLAGS.fused_decode_step, FLAGS.flash_decode
        FLAGS.fused_decode_step = FLAGS.flash_decode = False
        try:
            ref_sess = GenerationSession(
                build_generation_programs(
                    batch_size=slots, strategy="greedy", cache_prefix="ref",
                    **dict(model_kw, use_flash=False)),
                scope=scope, executor=pt.Executor())
            ref = _first_step_logits(ref_sess, prompts)
        finally:
            FLAGS.fused_decode_step, FLAGS.flash_decode = saved
    err_served, err_strict = _rel_err(served, ref), _rel_err(strict, ref)
    if not err_served <= TOL_LOGITS_SERVED:
        fails.append(f"served logits off the reference: {err_served:.3g} of "
                     f"range > {TOL_LOGITS_SERVED}")
    if not err_strict <= TOL_LOGITS_STRICT:
        fails.append(f"highest-precision logits off the reference: "
                     f"{err_strict:.3g} of range > {TOL_LOGITS_STRICT}")
    answered = ("all 200 with the requested token counts" if all_answered
                else "FAILED")
    _say(f"generate: {len(jobs)} requests "
         f"(prompts {[len(j[0]) for j in jobs]}, last two concurrent) "
         f"{answered}, compile_count {warm} flat={flat}, decode "
         f"mosaic_calls={mosaic}, "
         f"first-step logits vs highest-precision XLA reference: served "
         f"{err_served:.2e} (tol {TOL_LOGITS_SERVED}), at highest "
         f"{err_strict:.2e} (tol {TOL_LOGITS_STRICT}) of range, smoke "
         f"start+warmup {compile_s:.1f}s")
    return dict(leg="generate", ok=not fails, failures=fails,
                requests=len(jobs), compile_count=warm, compile_flat=flat,
                mosaic_calls=mosaic, logits_err_served=err_served,
                logits_err_strict=err_strict, compile_s=round(compile_s, 2))


# ---------------------------------------------------------------------------
# kernel leg: one builder per family.  A builder returns (kernel_fn,
# reference_fn, args, tol[, fd]) — both functions take `args` and return a
# pytree of arrays; `fd` asks for the dropout finite-difference check.
# ---------------------------------------------------------------------------


def _randn(rng, shape, dtype, scale=1.0):
    import jax.numpy as jnp

    return jnp.asarray((rng.randn(*shape) * scale).astype("float32")
                       ).astype(dtype)


def _tol(dtype) -> float:
    return TOL_F32 if np.dtype(dtype).itemsize >= 4 else TOL_BF16


def _attention_row(cfg, interpret, rng):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as att

    fmt, d = cfg["fmt"], cfg["d"]
    shape = ((cfg["b"], cfg["t"], cfg["h"], d) if fmt == "bthd"
             else (cfg["b"], cfg["h"], cfg["t"], d))
    # a grouped-query row (bhtd): k and v at their own head count
    kv_shape = (cfg["b"], cfg.get("h_kv", shape[1])) + shape[2:]
    q = _randn(rng, shape, cfg["dtype"], 0.5)
    k, v = (_randn(rng, kv_shape, cfg["dtype"], 0.5) for _ in range(2))
    seed = jnp.asarray([1234], jnp.uint32)
    ref_attn = (att._reference_bthd if fmt == "bthd"
                else att.reference_attention)
    mask = cfg.get("mask")  # a block-diffusion row: masked, not causal

    def fwd_bwd(attn):
        def run(q, k, v):
            def loss(q, k, v):
                o = attn(q, k, v)
                return jnp.sum(o.astype(jnp.float32) * 1e-2), o
            (_, o), g = jax.value_and_grad(loss, (0, 1, 2),
                                           has_aux=True)(q, k, v)
            return o, g
        return run

    kernel = fwd_bwd(lambda q, k, v: att.flash_attention(
        q, k, v, None, scale=d ** -0.5, causal=mask is None, fmt=fmt,
        interpret=interpret, mask=mask))
    ref = fwd_bwd(lambda q, k, v: ref_attn(
        q, k, v, None, d ** -0.5, mask is None, mask=mask))
    if mask is not None:
        return kernel, ref, (q, k, v), _tol(cfg["dtype"])

    def dropped(q, k, v):
        return att.flash_attention(
            q, k, v, None, scale=d ** -0.5, causal=True, fmt=fmt,
            interpret=interpret, dropout_rate=0.1, dropout_seed=seed)

    return kernel, ref, (q, k, v), _tol(cfg["dtype"]), dropped


def _conv_bn_row(cfg, interpret, rng):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import conv_bn as cbn

    rows, c, dt = cfg["rows"], cfg["c"], cfg["dtype"]
    if cfg.get("kind") == "dot":
        x = _randn(rng, (rows, 256), dt, 0.5)
        w = _randn(rng, (c, 256), dt, 0.06)

        def fwd_bwd(dot):
            def run(x, w):
                def loss(x, w):
                    y, s1, s2 = dot(x, w)
                    return (jnp.sum(y.astype(jnp.float32)) * 1e-3
                            + jnp.sum(s1) * 1e-4 + jnp.sum(s2) * 1e-5,
                            (y, s1, s2))
                (_, out), g = jax.value_and_grad(loss, (0, 1),
                                                 has_aux=True)(x, w)
                return out, g
            return run

        def ref_dot(x, w):
            y = jax.lax.dot_general(
                x, w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(x.dtype)
            ys = y.astype(jnp.float32)
            return y, ys.sum(0), (ys * ys).sum(0)

        return (fwd_bwd(lambda x, w: cbn.dot_col_stats(
            x, w, interpret=interpret)), fwd_bwd(ref_dot), (x, w), _tol(dt))

    x = _randn(rng, (rows, c), dt)
    res = _randn(rng, (rows, c), dt)
    wv = jnp.asarray(rng.rand(c).astype("float32") + 0.5)
    bv = jnp.asarray(rng.randn(c).astype("float32"))

    def fwd_bwd(stats, ssa):
        def run(x, wv, bv, res):
            def loss(x, wv, bv, res):
                s1, s2 = stats(x)
                o = ssa(x, wv, bv, res)
                return (jnp.sum(o.astype(jnp.float32)) * 1e-3
                        + jnp.sum(s1) * 1e-4 + jnp.sum(s2) * 1e-5,
                        (s1, s2, o))
            (_, out), g = jax.value_and_grad(loss, (0, 1, 2, 3),
                                             has_aux=True)(x, wv, bv, res)
            return out, g
        return run

    def ref_stats(x):
        xs = x.astype(jnp.float32)
        return xs.sum(0), (xs * xs).sum(0)

    def ssa(x, wv, bv, res):
        return cbn.scale_shift_act(x, wv, bv, residual=res, relu=True,
                                   interpret=interpret)

    def ref_ssa(x, wv, bv, res):
        out = x * wv.astype(x.dtype) + bv.astype(x.dtype) + res
        # The ReLU mask is the KERNEL's (out_kernel > 0, its backward's
        # rule): XLA fuses this chain in f32 and rounds once, the kernel
        # rounds per bf16 op, so a few of the 6M pre-activations land on
        # opposite sides of zero and a mask recomputed here would differ
        # there by a whole gradient (seen on the chip: dx off by 1.0 of
        # range at a handful of elements, its channel sums exact).
        kept = jax.lax.stop_gradient(ssa(x, wv, bv, res)) > 0
        return jnp.where(kept, out, jnp.zeros((), x.dtype))

    kernel = fwd_bwd(
        lambda x: cbn.channel_stats(x, interpret=interpret), ssa)
    return kernel, fwd_bwd(ref_stats, ref_ssa), (x, wv, bv, res), _tol(dt)


def _dropout_row(cfg, interpret, rng):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import dropout_epilogue as de

    rate, dt = 0.1, cfg["dtype"]
    x = _randn(rng, cfg["shape"], dt)
    res = _randn(rng, cfg["shape"], dt)
    seed = jnp.asarray([99], jnp.uint32)

    def kernel(x, res):
        # the hardware-PRNG mask has no reference bits: reduce the kernel
        # to what must hold for ANY mask — kept elements are x/(1-rate) +
        # residual, dropped ones the residual, the backward regenerates
        # the forward's mask, and the keep rate is 1 - rate.  Each check
        # is returned as 1 + its error, so the reference is all ones and
        # the leg's relative error IS the error.
        f32 = jnp.float32
        inv_keep = 1 / (1 - rate)
        out = de.dropout_add(x, res, rate, seed, interpret=interpret)
        gx = jax.grad(lambda x: jnp.sum(de.dropout_add(
            x, res, rate, seed, interpret=interpret).astype(f32)))(x)
        gx = gx.astype(f32)
        kept = gx != 0
        want = (jnp.where(kept, x * jnp.asarray(inv_keep, x.dtype),
                          jnp.zeros((), x.dtype)) + res).astype(f32)
        return (1 + jnp.max(jnp.abs(out.astype(f32) - want))
                / jnp.max(jnp.abs(want)),
                1 + jnp.max(jnp.abs(jnp.where(kept, gx, inv_keep)
                                    - inv_keep)) / inv_keep,
                # a keep rate off by a point or more reads as 1 + 1
                1 + (jnp.abs(jnp.mean(kept.astype(f32)) - (1 - rate))
                     >= 0.01).astype(f32))

    def ref(x, res):
        return (jnp.ones((), jnp.float32),) * 3

    return kernel, ref, (x, res), _tol(dt)


def _decode_row(cfg, interpret, rng):
    import jax.numpy as jnp

    from paddle_tpu.kernels import decode_attention as kda

    b, h, dh, max_t, dt = (cfg["b"], cfg["h"], cfg["dh"], cfg["max_t"],
                           cfg["dtype"])
    q = _randn(rng, (b, h, dh), dt)
    k = _randn(rng, (b, max_t, h, dh), dt)
    v = _randn(rng, (b, max_t, h, dh), dt)
    lens = jnp.asarray(rng.randint(1, max_t + 1, (b,)).astype("int32"))
    return (lambda *a: kda.flash_decode(*a, scale=dh ** -0.5,
                                        interpret=interpret),
            lambda *a: kda.reference_decode(*a, scale=dh ** -0.5),
            (q, k, v, lens), _tol(dt))


def _paged_row(cfg, interpret, rng):
    import jax.numpy as jnp

    from paddle_tpu.kernels import decode_attention as kda

    b, h, dh, bt, mb, dt = (cfg["b"], cfg["h"], cfg["dh"], cfg["block_t"],
                            cfg["max_blocks"], cfg["dtype"])
    pool_n = b * mb + 3  # holes: the table is not the identity
    q = _randn(rng, (b, h, dh), dt)
    kp = _randn(rng, (pool_n, bt, h, dh), dt)
    vp = _randn(rng, (pool_n, bt, h, dh), dt)
    table = jnp.asarray(rng.permutation(pool_n)[:b * mb]
                        .reshape(b, mb).astype("int32"))
    lens = jnp.asarray(rng.randint(1, bt * mb + 1, (b,)).astype("int32"))
    return (lambda *a: kda.flash_decode_paged(*a, scale=dh ** -0.5,
                                              interpret=interpret),
            lambda *a: kda.reference_decode_paged(*a, scale=dh ** -0.5),
            (q, kp, vp, table, lens), _tol(dt))


def _megastep_operands(cfg, rng, batch):
    import jax.numpy as jnp

    dm, h, dh, di, dt = (cfg["dm"], cfg["h"], cfg["dh"], cfg["di"],
                         cfg["dtype"])
    hd = h * dh
    x = _randn(rng, (batch, 1, dm), dt)

    def ln():
        return (jnp.asarray(rng.rand(dm).astype("float32") + 0.5),
                _randn(rng, (dm,), "float32", 0.1))

    weights = [_randn(rng, (dm, 3 * hd), dt, 0.05),
               _randn(rng, (hd, dm), dt, 0.05), *ln(),
               _randn(rng, (dm, hd), dt, 0.05),
               _randn(rng, (hd, dm), dt, 0.05), *ln(),
               _randn(rng, (dm, di), dt, 0.05),
               _randn(rng, (di,), dt, 0.05),
               _randn(rng, (di, dm), dt, 0.05),
               _randn(rng, (dm,), dt, 0.05), *ln()]
    return x, weights


def _megastep_row(cfg, interpret, rng):
    import jax.numpy as jnp

    from paddle_tpu.kernels import decode_step as kds

    b, n_layer, layer = 4, 2, 1
    h, dh, dt = cfg["h"], cfg["dh"], cfg["dtype"]
    max_t, cross_t = cfg["max_t"], cfg["cross_t"]
    x, weights = _megastep_operands(cfg, rng, b)
    ck, cv = (_randn(rng, (n_layer, b, max_t, h, dh), dt)
              for _ in range(2))
    xk, xv = (_randn(rng, (n_layer, b, cross_t, h, dh), dt)
              for _ in range(2))
    pos = jnp.asarray(rng.randint(0, max_t - 1, (b,)).astype("int32"))
    act = jnp.asarray([1, 1, 0, 1], jnp.int32)  # one lane sits out
    lens = pos + act
    clens = jnp.asarray(rng.randint(1, cross_t + 1, (b,)).astype("int32"))
    kw = dict(layer=layer, n_head=h, scale=dh ** -0.5)
    args = (x, *weights, ck, cv, xk, xv, pos, lens, clens, act)
    return (lambda *a: kds.fused_decode_step(*a, interpret=interpret, **kw),
            lambda *a: kds.reference_decode_step(*a, **kw), args, _tol(dt))


def _paged_megastep_row(cfg, interpret, rng):
    import jax.numpy as jnp

    from paddle_tpu.kernels import decode_step as kds

    b, n_layer, layer = cfg["b"], 2, 1
    h, dh, dt = cfg["h"], cfg["dh"], cfg["dtype"]
    bt, cbt = cfg["block_t"], cfg["cross_block_t"]
    mb, cmb = cfg["max_blocks"], cfg["cross_max_blocks"]
    x, weights = _megastep_operands(cfg, rng, b)
    pool_n, xpool_n = b * mb + 3, b * cmb + 3
    ck, cv = (_randn(rng, (n_layer, pool_n, bt, h, dh), dt)
              for _ in range(2))
    xk, xv = (_randn(rng, (n_layer, xpool_n, cbt, h, dh), dt)
              for _ in range(2))
    stab = jnp.asarray(rng.permutation(pool_n)[:b * mb]
                       .reshape(b, mb).astype("int32"))
    ctab = jnp.asarray(rng.permutation(xpool_n)[:b * cmb]
                       .reshape(b, cmb).astype("int32"))
    pos = jnp.asarray(rng.randint(0, bt * mb - 1, (b,)).astype("int32"))
    act = jnp.asarray((np.arange(b) % 4 != 2).astype("int32"))
    lens = pos + act
    clens = jnp.asarray(rng.randint(1, cbt * cmb + 1, (b,)).astype("int32"))
    kw = dict(layer=layer, n_head=h, scale=dh ** -0.5)
    args = (x, *weights, ck, cv, xk, xv, pos, lens, clens, stab, ctab, act)
    return (lambda *a: kds.fused_decode_step_paged(
                *a, interpret=interpret, **kw),
            lambda *a: kds.reference_decode_step_paged(*a, **kw), args,
            _tol(dt))


def _embedding_row(cfg, interpret, rng):
    import jax.numpy as jnp

    from paddle_tpu.kernels import embedding as emb

    (v, d), dt = cfg["tables"][0]
    s_n, batch = len(cfg["tables"]), cfg["batch"]
    tables = [_randn(rng, (v, d), dt) for _ in range(s_n)]
    ids = jnp.asarray(rng.randint(0, v, (s_n, batch)).astype("int32"))
    rows = _randn(rng, (s_n, batch, d), dt, 0.1)
    if cfg["tiers"] == 1:
        def kernel(tables, ids, rows):
            uids, mrows = emb.merge_slot_rows(ids, rows, v)
            return (emb.multi_table_gather(tables, ids,
                                           interpret=interpret),
                    emb.multi_table_scatter_add(
                        tables, uids, mrows, jnp.float32(0.5),
                        interpret=interpret))

        def ref(tables, ids, rows):
            uids, mrows = emb.merge_slot_rows(ids, rows, v)
            return (emb.multi_table_gather_xla(tables, ids),
                    emb.multi_table_scatter_add_xla(
                        tables, uids, mrows, jnp.float32(0.5)))

        return kernel, ref, (tables, ids, rows), _tol(dt)

    m1s = [_randn(rng, (v, d), dt, 0.1) for _ in range(s_n)]
    m2s = [jnp.abs(_randn(rng, (v, d), dt, 0.1)) for _ in range(s_n)]
    hyper = (jnp.float32(1e-2), 0.9, 0.999, 1e-8)

    def kernel(tables, m1s, m2s, ids, rows):
        uids, mrows = emb.merge_slot_rows(ids, rows, v)
        return emb.multi_table_sparse_adam(tables, m1s, m2s, uids, mrows,
                                           *hyper, interpret=interpret)

    def ref(tables, m1s, m2s, ids, rows):
        uids, mrows = emb.merge_slot_rows(ids, rows, v)
        return emb.multi_table_sparse_adam_xla(tables, m1s, m2s, uids,
                                               mrows, *hyper)

    return kernel, ref, (tables, m1s, m2s, ids, rows), _tol(dt)


def _short_conv_row(cfg, interpret, rng):
    import jax.numpy as jnp

    from paddle_tpu.kernels import short_conv as sc

    dt = cfg["dtype"]
    x = _randn(rng, (cfg["b"], cfg["t"], 3 * cfg["d"]), dt)
    g = _randn(rng, (cfg["b"], cfg["t"], cfg["d"]), dt)
    w = _randn(rng, (cfg["d"], cfg["taps"]), "float32", 0.5)

    def both(fwd, bwd):
        def run(x, w, g):
            dx, dw = bwd(x, w, g)
            # dW sums 8192 rows: compared on its own scale
            return fwd(x, w), dx, dw / jnp.max(jnp.abs(dw))
        return run

    kernel = both(lambda x, w: sc.short_conv(x, w, interpret=interpret),
                  lambda x, w, g: sc.short_conv_bwd(x, w, g,
                                                    interpret=interpret))
    ref = both(sc.reference_short_conv, sc.reference_short_conv_bwd)
    return kernel, ref, (x, w, g), _tol(dt)


def kernel_matrix():
    """(family, lint matrix, row builder) for the ten canonical matrices
    of analysis/kernel_lint.py."""
    from paddle_tpu.analysis import kernel_lint as kl

    return [
        ("attention", kl._ATTENTION_MATRIX, _attention_row),
        ("conv_bn", kl._CONV_BN_MATRIX, _conv_bn_row),
        ("ring_attention", kl._RING_MATRIX, _attention_row),
        ("dropout_epilogue", kl._DROPOUT_MATRIX, _dropout_row),
        ("decode_attention", kl._DECODE_MATRIX, _decode_row),
        ("decode_step", kl._MEGASTEP_MATRIX, _megastep_row),
        ("paged_decode_attention", kl._PAGED_MATRIX, _paged_row),
        ("paged_decode_step", kl._PAGED_MEGASTEP_MATRIX,
         _paged_megastep_row),
        ("embedding", kl._EMBEDDING_MATRIX, _embedding_row),
        ("short_conv", kl._SHORT_CONV_MATRIX, _short_conv_row),
    ]


def _leaf_errs(got, ref):
    import jax

    got_l, ref_l = jax.tree.leaves(got), jax.tree.leaves(ref)
    if len(got_l) != len(ref_l):
        return [float("inf")]
    return [_rel_err(g, r) for g, r in zip(got_l, ref_l)]


def _dropout_fd_err(dropped, args, rng):
    """A dropout kernel against itself: same seed, same bits — and, for
    f32 operands, a directional finite difference against its own
    gradient.  The keep-mask is a pure function of the seed, so
    sum(f(x + eps d) - f(x - eps d)) / 2 eps must equal <grad, d> — unless
    the backward kernels regenerate a different mask than the forward.
    (bf16 operands cannot resolve the difference quotient; they get the
    determinism and finiteness checks only.)"""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    run = jax.jit(dropped)
    out = run(*args)
    if not (bool(jnp.array_equal(out, run(*args)))
            and bool(jnp.all(jnp.isfinite(out.astype(f32))))):
        return float("inf")

    wide = args[0].dtype.itemsize >= 4

    def total(*a):
        if not wide:
            return jnp.sum(dropped(*a).astype(f32) * 1e-2)
        # highest precision INSIDE the kernels too (jnp.dot reads the
        # context at trace time): at the TPU's default one-bf16-pass dots
        # the rounding noise of f, not its slope, fills the difference
        # quotient (measured on the chip: errors of 0.1-16)
        with jax.default_matmul_precision("highest"):
            return jnp.sum(dropped(*a).astype(f32) * 1e-2)

    grads = jax.jit(jax.grad(total, tuple(range(len(args)))))(*args)
    if not all(bool(jnp.all(jnp.isfinite(g.astype(f32)))) for g in grads):
        return float("inf")
    if not wide:
        return 0.0
    # one direction per operand, at the operand's own scale
    dirs = [jnp.asarray(rng.randn(*a.shape).astype("float32"))
            * jnp.std(a) for a in args]
    eps = 1e-2
    fwd = jax.jit(total)
    plus = fwd(*[a + eps * d for a, d in zip(args, dirs)])
    minus = fwd(*[a - eps * d for a, d in zip(args, dirs)])
    fd = (float(plus) - float(minus)) / (2 * eps)
    analytic = sum(float(jnp.sum(g * d)) for g, d in zip(grads, dirs))
    if not np.isfinite(fd):
        return float("inf")
    return abs(fd - analytic) / (abs(analytic) + 1e-6)


def kernel_leg(interpret=False, families=None) -> dict:
    """Every must_accept lint row, compiled and compared."""
    import jax

    fails, counts, rows_out = [], {}, []
    t_leg = time.perf_counter()
    for family, matrix, build in kernel_matrix():
        if families is not None and family not in families:
            continue
        for cfg in matrix:
            label = f"{family}:{cfg['label']}"
            if not cfg.get("must_accept", True):
                why = cfg.get("mosaic_refusal")
                status, note = "xla_by_design", (
                    f"gate rejects: Mosaic refused ({why})" if why
                    else "gate rejects this geometry")
            else:
                status, note = _run_row(cfg, build, interpret, jax)
            counts[status] = counts.get(status, 0) + 1
            rows_out.append((label, status))
            _say(f"kernel {label}: {status}"
                 + (f" — {note}" if note else ""))
            if status in ("refused", "mismatch"):
                fails.append(f"{label}: {status} — {note}")
    _say(f"kernels: {counts}, {time.perf_counter() - t_leg:.1f}s smoke")
    return dict(leg="kernels", ok=not fails, failures=fails, counts=counts,
                rows=rows_out)


def _run_row(cfg, build, interpret, jax):
    # a stable seed per row: rows are independent of their order
    rng = np.random.RandomState(zlib.crc32(cfg["label"].encode()))
    kernel, ref, args, tol, *rest = build(cfg, interpret, rng)
    try:
        return _compare_row(kernel, ref, args, tol, rest, interpret, rng, jax)
    except Exception as e:  # noqa: BLE001 — the compiler's words ARE it
        msg = " ".join(str(e).split())
        return "refused", f"{type(e).__name__}: {msg[:400]}"


def _compare_row(kernel, ref, args, tol, rest, interpret, rng, jax):
    compiled = jax.jit(kernel).lower(*args).compile()
    mosaic = _mosaic_calls(compiled.as_text())
    if not interpret and mosaic == 0:
        return "mismatch", ("no Mosaic custom call in the compiled row: the "
                            "gate took the XLA fallback on a must_accept "
                            "shape")
    got = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*args)
    errs = _leaf_errs(got, want)
    note = f"mosaic_calls={mosaic} max err {max(errs):.2e} of range"
    if not max(errs) <= tol:
        return "mismatch", note + f" > tol {tol}"
    if rest:
        fd = _dropout_fd_err(rest[0], args, rng)
        note += f", dropout grad-vs-finite-difference {fd:.2e}"
        if not fd <= TOL_FD:
            return "mismatch", note + f" > tol {TOL_FD}"
    return "compiled", note


# ---------------------------------------------------------------------------
# four chips: the train configuration as a ShardedProgram
# ---------------------------------------------------------------------------


def sharded_leg(cfg=None, batch=64, seq=256, steps=4, mesh_axes=None,
                interpret=False) -> dict:
    """One process, data x model mesh over the first prod(mesh) devices,
    against the one-chip trajectory from the same seed and batches."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.parallel.sharding import (ShardedProgram, ShardingPlan,
                                              transformer_tp_rules)

    cfg = cfg or bench.TRANSFORMER_BASE
    mesh_axes = mesh_axes or {"data": 2, "model": 2}
    n_dev = int(np.prod(list(mesh_axes.values())))
    fails = []
    devices = jax.devices()[:n_dev]
    prog, startup, avg_cost, _ = bench.build_transformer_train(cfg, seq)
    feed = bench.transformer_feed(cfg, batch, seq, steps)

    def trajectory(make_runner):
        # a fresh executor per route: both fold the same run ids (start-up
        # draws 1, the train steps 2..), so the dropout streams line up
        exe, scope, target = pt.Executor(), pt.Scope(), make_runner()
        exe.run(startup, scope=scope)
        out = []
        for s in range(steps):
            (lv,) = exe.run(target, feed={k: v[s] for k, v in feed.items()},
                            fetch_list=[avg_cost], scope=scope)
            out.append(float(np.asarray(lv).reshape(-1)[0]))
        return out, scope, target, exe

    # The sharded step cannot hold Mosaic kernels (GSPMD does not
    # partition them, kernels/placement.py): it runs the XLA references,
    # whose dropout masks are the counter hash.  Only the hash mask is
    # reproducible across the two routes, so the one-chip trajectory is
    # taken with the hardware-PRNG masks off (read at trace time).
    hw_prng, FLAGS.tpu_prng_dropout = FLAGS.tpu_prng_dropout, False
    try:
        one, _, _, _ = trajectory(lambda: prog)
    finally:
        FLAGS.tpu_prng_dropout = hw_prng
    plan = ShardingPlan(mesh_axes=mesh_axes,
                        param_rules=transformer_tp_rules("model"),
                        zero_stage=1, devices=list(devices))
    t0 = time.perf_counter()
    many, scope, sharded, exe = trajectory(
        lambda: ShardedProgram(prog, plan, loss_name=avg_cost.name))
    sharded_s = time.perf_counter() - t0

    mesh_devs = [d for d in sharded.mesh.devices.flat]
    _say(f"sharded: mesh {dict(mesh_axes)} over {mesh_devs} "
         f"(jax.devices() list order: {mesh_devs == list(devices)})")
    if not np.all(np.isfinite(many)):
        fails.append(f"non-finite sharded loss {many}")
    errs = [abs(a - b) / abs(b) for a, b in zip(many, one)]
    if not max(errs) <= TOL_SHARDED_LOSS:
        fails.append(f"sharded loss leaves the one-chip trajectory: "
                     f"{many} vs {one} (max rel {max(errs):.3g} > "
                     f"{TOL_SHARDED_LOSS})")

    holders = set()
    split = 0
    for name in (p.name for p in prog.all_parameters()):
        arr = scope.find_var(name)
        holders |= set(arr.sharding.device_set)
        split += not arr.sharding.is_fully_replicated
    if holders != set(devices):
        fails.append(f"shards live on {sorted(map(str, holders))}, not on "
                     f"all of {sorted(map(str, devices))}")
    if split == 0:
        fails.append("every parameter is fully replicated: the tensor-"
                     "parallel rules matched nothing")
    live = [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in devices]
    if not interpret and min(live) < (1 << 20):
        fails.append(f"bytes_in_use per device {live}: some device holds "
                     f"under 1 MiB")

    hlo = exe.lower(sharded, {n: v[0] for n, v in feed.items()}, [avg_cost],
                    scope).compile().as_text()
    coll = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")}
    if sum(coll.values()) == 0:
        fails.append("no collective in the sharded step's HLO")
    _say(f"sharded: loss one-chip {['%.4f' % x for x in one]} vs "
         f"{n_dev}-chip {['%.4f' % x for x in many]} (max rel "
         f"{max(errs):.2e}, tol {TOL_SHARDED_LOSS}); {split} of "
         f"{len(prog.all_parameters())} params split, shards on "
         f"{len(holders)} devices, bytes_in_use {live}, collectives {coll}, "
         f"mosaic_calls={_mosaic_calls(hlo)}, smoke {sharded_s:.1f}s")
    return dict(leg="sharded", ok=not fails, failures=fails, loss_one=one,
                loss_sharded=many, devices=len(holders),
                bytes_in_use=live, collectives=coll)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 adds the sharded leg on a four-chip host "
                         "(default: one chip)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    dev = preflight()
    if args.chips > dev["count"]:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax reports "
                 f"{dev['count']} device(s)")
    from paddle_tpu.inference import enable_compile_cache

    cache_dir = enable_compile_cache()

    def entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    before = entries()
    _say(f"compile cache {cache_dir}: {before} entries at start "
         f"({'warm' if before else 'cold'})")

    legs = [("train", lambda: train_leg(**TRAIN_FULL)),
            ("moe", moe_leg),
            ("generate", lambda: generate_leg(**GENERATE_FULL)),
            ("kernels", kernel_leg)]
    if args.chips == 4:
        legs.append(("sharded", lambda: sharded_leg(
            cfg=TRAIN_FULL["cfg"], batch=TRAIN_FULL["batch"],
            seq=TRAIN_FULL["seq"])))
    reports = []
    for name, run in legs:
        t0 = time.perf_counter()
        try:
            rep = run()
        except Exception as e:  # noqa: BLE001 — a crashed leg fails the run
            traceback.print_exc()
            rep = dict(leg=name, ok=False,
                       failures=[f"crashed: {type(e).__name__}: {e}"])
        rep["seconds"] = round(time.perf_counter() - t0, 1)
        reports.append(rep)
        _say(f"leg {name}: {'PASS' if rep['ok'] else 'FAIL'} in "
             f"{rep['seconds']}s (smoke, {'warm' if before else 'cold'} "
             f"cache)")
        for f in rep["failures"]:
            _say(f"  FAIL {name}: {f}")

    after = entries()
    _say(f"compile cache {cache_dir}: {after} entries at end "
         f"(+{after - before}); total {time.perf_counter() - t_start:.0f}s")
    ok = all(r["ok"] for r in reports)
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
