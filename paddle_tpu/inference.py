"""Inference serving: Predictor with an AOT executable cache + the
BN-fold inference optimization pass.

Reference parity:
  * PaddlePredictor / NativeConfig — inference/api/paddle_api.h:153,200,
    api/api_impl.h:34 (NativePaddlePredictor): load a saved model once,
    then serve many Run() calls with no per-call graph work.
  * AnalysisPredictor pass pipeline — api/analysis_predictor.h:45,
    analysis/analyzer.cc: IR optimization before serving; the first pass
    delivered here is conv/fc + batch_norm folding, the reference's
    inference_transpiler.py:1 / conv_bn_fuse_pass.cc.

TPU-first: the "executable cache" is the Executor's fingerprint-keyed XLA
compile cache — Run() re-traces nothing after the first call per feed
signature; parameters stay resident in the Predictor's private Scope (HBM)
across calls, mirroring ir_params_sync_among_devices_pass.cc's
params-frozen-to-device behavior.
"""

from __future__ import annotations

import contextlib
import os
from typing import Dict, List, Optional

import numpy as np

from . import io
from .core import framework as fw
from .core.executor import CPUPlace, Executor, Scope


def _consumers(block: fw.Block, name: str) -> List[fw.Operator]:
    return [op for op in block.ops if name in op.input_arg_names()]


def _fold_bn_into(block, scope, idx, bn_op, prod_op) -> bool:
    """Fold `bn_op` (at op index `idx`) into its producer conv2d/mul.
    Returns True on success; mutates program + scope."""
    if prod_op.type == "conv2d":
        # the BN must normalize the conv's channel axis: its data_layout
        # has to agree with the conv's data_format
        if (bn_op.attr("data_layout", "NCHW")
                != prod_op.attr("data_format", "NCHW")):
            return False
        w_name = prod_op.input("Filter")[0]
        out_axis = 0  # filter is OIHW for either data_format
    elif prod_op.type == "mul":
        w_name = prod_op.input("Y")[0]
        out_axis = 1  # [in, out]
    else:
        return False

    w_var = scope.find_var(w_name)
    if w_var is None:
        return False
    gamma = np.asarray(scope.find_var(bn_op.input("Scale")[0]))
    beta = np.asarray(scope.find_var(bn_op.input("Bias")[0]))
    mean = np.asarray(scope.find_var(bn_op.input("Mean")[0]))
    var = np.asarray(scope.find_var(bn_op.input("Variance")[0]))
    eps = bn_op.attr("epsilon", 1e-5)

    w = np.asarray(w_var)
    orig_dtype = w.dtype
    factor = (gamma / np.sqrt(var.astype("float64") + eps)).astype("float64")
    bshape = [1] * w.ndim
    bshape[out_axis] = -1
    scope.set_var(
        w_name,
        (w.astype("float64") * factor.reshape(bshape)).astype(orig_dtype),
    )
    fold_bias = (
        beta.astype("float64") - mean.astype("float64") * factor
    ).astype(orig_dtype)

    bias_name = fw.unique_name(f"{w_name}.bn_fold_bias")
    block.create_var(
        name=bias_name, shape=list(fold_bias.shape),
        dtype=str(fold_bias.dtype), persistable=True,
    )
    scope.set_var(bias_name, fold_bias)

    y_name = bn_op.output("Y")[0]
    x_name = bn_op.input("X")[0]
    block.remove_op(idx)
    # channel axis of the producer's output: conv2d NCHW -> 1, NHWC -> -1;
    # mul output [.., C] -> -1
    if prod_op.type == "conv2d":
        axis = -1 if prod_op.attr("data_format", "NCHW") == "NHWC" else 1
    else:
        axis = -1
    block.insert_op(
        idx,
        "elementwise_add",
        inputs={"X": [x_name], "Y": [bias_name]},
        outputs={"Out": [y_name]},
        attrs={"axis": axis},
    )
    return True


def inference_transpile(program: fw.Program, scope: Scope) -> int:
    """Fold batch_norm (inference mode) into the preceding conv2d/mul
    weights: W' = W * gamma/sqrt(var+eps); +bias' = beta - mean*that
    (reference: transpiler/inference_transpiler.py:1, ir/conv_bn_fuse_pass.cc).

    Mutates `program` and the parameter values in `scope`; returns the
    number of batch_norm ops folded.  Only valid for inference programs
    (clone(for_test=True) / load_inference_model output)."""
    block = program.global_block()
    folded = 0
    changed = True
    while changed:
        changed = False
        producers: Dict[str, tuple] = {}
        for i, op in enumerate(block.ops):
            for n in op.output_arg_names():
                producers[n] = (i, op)
        for i, op in enumerate(block.ops):
            if op.type != "batch_norm":
                continue
            x_name = op.input("X")[0]
            prod = producers.get(x_name)
            if prod is None:
                continue
            _, prod_op = prod
            # the conv output must feed only this BN (otherwise other
            # consumers would see the refolded weights)
            if len(_consumers(block, x_name)) != 1:
                continue
            if _fold_bn_into(block, scope, i, op, prod_op):
                folded += 1
                changed = True
                break
    return folded


AOT_DIRNAME = "__aot__"
# v2: executables are serialized WITHOUT buffer donation.  v1 bundles
# baked the executor's donate_argnums aliasing into the payload, and
# jax's deserialized-Compiled path lacks the donation bookkeeping that
# marks consumed arrays deleted — running one returns state arrays
# aliasing freed buffers (use-after-free; nondeterministic corruption
# under serving load).  Loaders REJECT v1 bundles (JIT fallback).
AOT_VERSION = 2


def _feed_signature(feed_names, feed):
    return tuple(
        (n, tuple(np.asarray(feed[n]).shape), str(np.asarray(feed[n]).dtype))
        for n in feed_names
    )


def _aot_trees(n_feed, n_rw, n_ro, needs_key, n_fetch, n_state):
    """Reconstruct the executable's in/out pytree structures from the
    executor calling convention — fn((feed_list, rw_list, ro_list[, key]),
    {}) -> (fetch_list, state_list).  Rebuilding them from counts keeps the
    MANIFEST pickle-free (JSON + raw XLA payload).  SECURITY: the payload
    itself is NOT safe — jax's deserialize_and_load runs an unrestricted
    unpickler over it, so loading a bundle from an untrusted model
    directory can execute arbitrary code.  That is why Predictor defaults
    use_aot=False (explicit opt-in for trusted artifacts)."""
    import jax

    args = ([0] * n_feed, [0] * n_rw, [0] * n_ro)
    if needs_key:
        args = args + (0,)
    in_tree = jax.tree_util.tree_structure((args, {}))
    out_tree = jax.tree_util.tree_structure(([0] * n_fetch, [0] * n_state))
    return in_tree, out_tree


def export_aot_bundle(dirname, feed_examples, place=None) -> int:
    """Serialize AOT-compiled executables for the saved model at `dirname`
    (reference gap: the C++ predictor serves without the framework in the
    loop, api/paddle_api.h:153 — the TPU-native analogue is an XLA
    executable serialized NEXT TO the save_inference_model artifact, so a
    serving process loads and runs it with NO program re-trace).

    feed_examples: list of feed dicts (one per signature to pre-compile).
    Writes `<dirname>/__aot__/sig_<i>.json` manifests + `sig_<i>.xla`
    payloads; returns how many were exported.  Loading falls back to the
    normal retrace path when a bundle does not match the runtime
    (jax/platform change) — see Predictor.

    SECURITY: the sig_<i>.xla payload is deserialized via jax's
    serialize_executable, which uses pickle under the hood — a bundle is a
    TRUSTED artifact (like a pickle checkpoint), and Predictor only loads
    one when constructed with use_aot=True."""
    import json

    import jax
    from jax.experimental import serialize_executable as se

    with _persistent_cache_disabled():
        return _export_aot_bundle(dirname, feed_examples, place, jax, se,
                                  json)


def reset_compilation_cache_singleton():
    """Reset jax's persistent-compilation-cache singleton: jax memoizes
    cache-enablement at first compile, so flipping
    jax_compilation_cache_dir without this leaves the old cache live.
    Private API (the one `jax._src` import in the tree), shared by
    export (cache OFF around bundle serialization) and
    enable_compile_cache — keep the jax-upgrade fix in this one place."""
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache for this process and
    return its directory.  For ENTRY POINTS only (chip_smoke.py, bench.py
    main(), python -m paddle_tpu.serving) — never at import, never from
    the test suite.

    The directory can be placed from outside: where
    JAX_COMPILATION_CACHE_DIR is set jax already reads it and no
    directory is set in code (a fleet's replicas inherit the variable
    from their supervisor); otherwise the cache lives at
    `<checkout>/.jax_cache` — a fixed path, because the path is part of
    what a later process must reproduce to hit."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # every compile is worth keeping, however fast or small: a chip call
    # starts with no compiled code, a server restart replays its ladder
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # a process that compiled anything before this call has memoized
    # "cache disabled"
    reset_compilation_cache_singleton()
    return cache_dir


@contextlib.contextmanager
def _persistent_cache_disabled():
    """Disable jax's persistent compilation cache for the duration and
    restore exactly the directory that was configured.

    An executable LOADED from the persistent cache re-serializes as a
    thin reference to in-process jit symbols (XLA:CPU deserialize then
    fails with "Symbols not found" in any other process), so
    export_aot_bundle must compile its payloads fresh — a bundle's whole
    point is surviving the process that wrote it."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    # a live singleton can outlast config=None
    reset_compilation_cache_singleton()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        reset_compilation_cache_singleton()


def _export_aot_bundle(dirname, feed_examples, place, jax, se, json) -> int:
    pred = Predictor(dirname, place=place, optimize=False, use_aot=False)
    exe, scope, program = pred._exe, pred._scope, pred._program
    out_dir = os.path.join(dirname, AOT_DIRNAME)
    os.makedirs(out_dir, exist_ok=True)
    n_ok = 0
    for i, feed in enumerate(feed_examples):
        # this signature's entry and the arguments a call of it passes
        # (the names beside the executable go into the manifest, so the
        # Lowered of `Executor.lower` alone would not do)
        entry, args = exe._lowerable(program, feed, pred._fetch_names, scope)
        feed_names = sorted(feed)
        feed_vals = args[0]
        # The executor's entry is jitted with donate_argnums=(1,) (rw
        # buffers update in place), and that input/output aliasing gets
        # baked into the serialized executable.  jax's deserialized
        # Compiled call path has none of the donation bookkeeping that
        # marks consumed arrays deleted, so a donating bundle returns
        # state arrays aliasing freed buffers — serving reads then race
        # the allocator (nondeterministic corruption under load).
        # Bundles therefore serialize a donation-FREE recompile; rw
        # state on inference programs is tiny (quant scalars, BN stats),
        # so the per-call copy is noise.
        entry_src = getattr(entry.jitted, "__wrapped__", None)
        if entry_src is None:
            raise RuntimeError(
                "export_aot_bundle: executor entry is not a jitted "
                "function; cannot build a donation-free executable")
        payload, in_tree, out_tree = se.serialize(
            jax.jit(entry_src).lower(*args).compile())
        # the bundle stores only counts; verify the rebuilt trees match
        # the real ones so a convention drift fails at EXPORT, not serve
        want_in, want_out = _aot_trees(
            len(feed_vals), len(entry.rw_state), len(entry.ro_state),
            entry.needs_key, len(pred._fetch_names),
            len(entry.state_writes))
        if want_in != in_tree or want_out != out_tree:
            raise RuntimeError(
                "export_aot_bundle: executable pytree structure diverged "
                "from the executor calling convention — update _aot_trees")
        manifest = {
            "aot_version": AOT_VERSION,
            "signature": _feed_signature(feed_names, feed),
            "feed_names": feed_names,
            "rw_state": entry.rw_state,
            "ro_state": entry.ro_state,
            "state_writes": entry.state_writes,
            "needs_key": bool(entry.needs_key),
            "fetch_names": pred._fetch_names,
            "platform": jax.default_backend(),
            "n_devices": 1,  # Predictor executables are single-device
            "jax_version": jax.__version__,
        }
        with open(os.path.join(out_dir, f"sig_{i}.json"), "w") as f:
            json.dump(manifest, f)
        with open(os.path.join(out_dir, f"sig_{i}.xla"), "wb") as f:
            f.write(payload)
        n_ok += 1
    return n_ok


class Predictor:
    """Load-once, serve-many inference API (reference: PaddlePredictor
    api/paddle_api.h:153 + NativePaddlePredictor api_impl.h:34).

        pred = Predictor(dirname)            # load + optimize once
        outs = pred.run({"x": batch})        # AOT-cached; no retracing

    Each distinct feed signature (shapes/dtypes) compiles exactly once;
    `pred.compile_count` exposes the executable-cache size for tests.

    If the artifact carries an AOT bundle (save_inference_model
    aot_feed_examples / export_aot_bundle) AND the Predictor is built with
    `use_aot=True`, matching-signature calls serve straight from the
    DESERIALIZED XLA EXECUTABLE — the program is never re-traced, the
    reference's no-framework-in-the-loop serving property.  A bundle that
    fails to load (different platform / incompatible jax) falls back to
    the retrace path; `pred.aot_signatures` lists live bundles.

    use_aot defaults to FALSE: bundle deserialization runs jax's
    serialize_executable unpickler over the payload, so a bundle must be
    treated like a pickle file — opt in only for model directories you
    trust (ones your own pipeline exported).

    run() is THREAD-SAFE: the per-signature compile cache is guarded by
    per-key locks in the Executor (N concurrent callers x M signatures
    compile exactly M executables), stateless executables run fully
    concurrently, and stateful ones (scope write-backs, e.g. unfolded BN
    pass-through) serialize on the executor's ONE stateful-run lock —
    every feed signature (and every AOT bundle) donates the same scope
    arrays, so per-entry locking would race a use-after-donate.
    Required by the serving tier's dynamic batcher, whose scheduler
    threads drain into this cache."""

    def __init__(
        self,
        dirname: str,
        place=None,
        optimize: bool = True,
        model_filename: Optional[str] = None,
        params_filename: Optional[str] = None,
        use_aot: bool = False,
    ):
        self._scope = Scope()
        self._exe = Executor(place or CPUPlace())
        self._program, self._feed_names, self._fetch_vars = (
            io.load_inference_model(
                dirname, self._exe, scope=self._scope,
                model_filename=model_filename,
                params_filename=params_filename,
            )
        )
        self._fetch_names = [v.name for v in self._fetch_vars]
        self._aot: Dict[tuple, dict] = {}
        if use_aot:
            self._load_aot_bundles(dirname)
        self.folded_ops = 0
        # BN-folding mutates the SAME scope params the AOT executables were
        # compiled against (they bake the unfolded program in) — folding
        # under live bundles would silently corrupt AOT results.  XLA fuses
        # inference BN anyway, so the fold is skipped when bundles loaded.
        if optimize and not self._aot:
            self.folded_ops = inference_transpile(self._program, self._scope)

    def _load_aot_bundles(self, dirname):
        """Load serialized executables (use_aot=True opt-in ONLY).  The
        manifest is plain JSON, but deserialize_and_load runs an
        unrestricted unpickler over the sig_*.xla payload — loading a
        bundle from an untrusted model directory can execute arbitrary
        code, which is exactly why this path is off by default."""
        import glob
        import json

        import jax
        from jax.experimental import serialize_executable as se

        for path in sorted(
                glob.glob(os.path.join(dirname, AOT_DIRNAME,
                                       "sig_*.json"))):
            try:
                with open(path) as f:
                    bundle = json.load(f)
                if bundle["platform"] != jax.default_backend():
                    raise RuntimeError(
                        f"bundle platform {bundle['platform']} != runtime "
                        f"{jax.default_backend()}")
                if bundle.get("aot_version", 1) != AOT_VERSION:
                    raise RuntimeError(
                        f"bundle version {bundle.get('aot_version', 1)} != "
                        f"{AOT_VERSION} (v1 bundles donate buffers, which "
                        "corrupts state through jax's deserialized call "
                        "path — re-export with export_aot_bundle)")
                with open(path[:-5] + ".xla", "rb") as f:
                    payload = f.read()
                in_tree, out_tree = _aot_trees(
                    len(bundle["feed_names"]), len(bundle["rw_state"]),
                    len(bundle["ro_state"]), bundle["needs_key"],
                    len(bundle["fetch_names"]),
                    len(bundle["state_writes"]))
                loaded = se.deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=jax.devices()[
                        :bundle.get("n_devices", 1)])
                bundle["loaded"] = loaded
                # stateful bundles (scope write-backs) serialize on the
                # EXECUTOR's one stateful-run lock — the same scope
                # state backs every bundle signature AND the JIT
                # entries, so a per-bundle lock would let two
                # signatures interleave their write-backs; stateless
                # bundles run concurrently from serving threads
                bundle["run_lock"] = (self._exe._stateful_lock
                                      if bundle["state_writes"] else None)
                sig = tuple((n, tuple(shape), dt)
                            for n, shape, dt in bundle["signature"])
                self._aot[sig] = bundle
            except Exception as e:  # noqa: BLE001 — any mismatch: retrace
                from . import monitor
                from .log import vlog

                # degrade, never fail the model load: the JIT path serves
                # every signature the bundle would have; the NAMED counter
                # + flight event make the silent-retrace cause visible on
                # /metrics and /flight (serving satellite: a corrupted
                # sig_*.xla must not take the model down)
                if monitor.enabled():
                    monitor.counter("inference.aot_bundle_errors").inc()
                    from .monitor import flight as _mflight

                    _mflight.record(
                        "inference.aot_bundle_error", path=path,
                        error=f"{type(e).__name__}: {str(e)[:200]}")
                vlog(1, f"Predictor: AOT bundle {path} unusable "
                        f"({type(e).__name__}: {e}); falling back to "
                        "retrace")

    @property
    def feed_names(self) -> List[str]:
        return list(self._feed_names)

    @property
    def fetch_names(self) -> List[str]:
        return list(self._fetch_names)

    def feed_var_specs(self) -> Dict[str, tuple]:
        """{feed name: (declared shape tuple, dtype str)} from the loaded
        program — the leading batch dim is -1 for data-layer feeds.  The
        serving tier derives warmup shapes for its bucket ladder from
        this (serving/model.py)."""
        block = self._program.global_block()
        specs = {}
        for n in self._feed_names:
            v = block._find_var_recursive(n)
            specs[n] = (tuple(v.shape) if v is not None else None,
                        str(v.dtype) if v is not None else "float32")
        return specs

    def fetch_var_specs(self) -> List[tuple]:
        """[(fetch name, declared shape tuple or None, dtype str)] in
        fetch order — a leading -1 marks a batch-dependent output.  The
        serving batcher uses this to decide which outputs to slice back
        per coalesced request (serving/batcher.py)."""
        specs = []
        for v in self._fetch_vars:
            try:
                shape = tuple(v.shape)
            except (AttributeError, TypeError):
                shape = None
            specs.append((v.name, shape, str(getattr(v, "dtype", "float32"))))
        return specs

    @property
    def program(self) -> fw.Program:
        return self._program

    @property
    def compile_count(self) -> int:
        return len(self._exe._cache)

    @property
    def aot_signatures(self):
        return list(self._aot)

    def _run_aot(self, bundle, feed, return_numpy):
        import contextlib

        import jax

        feed_names = bundle["feed_names"]
        feed_vals = [self._exe._to_device_array(self._program, n, feed[n])
                     for n in feed_names]
        lock = bundle.get("run_lock")
        with lock if lock is not None else contextlib.nullcontext():
            rw_vals = [self._scope.find_var(n) for n in bundle["rw_state"]]
            ro_vals = [self._scope.find_var(n)
                       for n in bundle["ro_state"]]
            args = (feed_vals, rw_vals, ro_vals)
            if bundle["needs_key"]:
                from .core.executor import prng_key

                args = args + (jax.random.fold_in(
                    prng_key(self._program.random_seed or 0),
                    self._exe._next_run_id()),)
            fetches, new_state = bundle["loaded"](*args)
            for n, v in zip(bundle["state_writes"], new_state):
                self._scope.set_var(n, v)
        if return_numpy:
            return [np.asarray(v) for v in fetches]
        return list(fetches)

    def run(self, feed: Dict[str, np.ndarray], return_numpy: bool = True):
        """Serve one batch; a matching AOT bundle serves without any trace,
        otherwise compiles on first call per feed signature.

        With FLAGS.monitor on, each call lands in the
        `inference.request_seconds` latency histogram and the
        `inference.requests` counter (QPS = rate over scrapes)."""
        from . import monitor

        if not monitor.enabled():
            return self._run_impl(feed, return_numpy)

        import time as _time

        t0 = _time.perf_counter()
        try:
            outs = self._run_impl(feed, return_numpy)
        except Exception:
            monitor.counter("inference.request_errors").inc()
            raise
        dt = _time.perf_counter() - t0
        monitor.counter("inference.requests").inc()
        monitor.histogram("inference.request_seconds").observe(dt)
        # batch size comes from the FEED (fetches may be scalars/reduced)
        shape = getattr(feed.get(self._feed_names[0])
                        if self._feed_names else None, "shape", None)
        monitor.counter("inference.examples").inc(
            int(shape[0]) if shape else 1)
        return outs

    def _run_impl(self, feed, return_numpy):
        missing = [n for n in self._feed_names if n not in feed]
        if missing:
            raise KeyError(f"Predictor.run: missing feeds {missing}")
        if self._aot:
            feed = {n: feed[n] for n in self._feed_names}
            sig = _feed_signature(sorted(feed), feed)
            bundle = self._aot.get(sig)
            if bundle is not None:
                return self._run_aot(bundle, feed, return_numpy)
        return self._exe.run(
            self._program,
            feed={n: feed[n] for n in self._feed_names},
            fetch_list=self._fetch_names,
            scope=self._scope,
            return_numpy=return_numpy,
        )
