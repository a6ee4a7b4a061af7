"""Executor: lowers a whole Program block to ONE jitted JAX function.

Capability parity with the reference Executor/Scope (reference:
paddle/fluid/framework/executor.cc:203-457, scope.h:48,
python/paddle/fluid/executor.py:260-589), redesigned TPU-first:

  * The reference interprets ops one-by-one (hot loop executor.cc:448) with
    per-op kernel dispatch and eager GC.  Here the entire block is traced into
    a single function and compiled by XLA: fusion, scheduling, memory planning,
    rematerialization and collective insertion all happen in the compiler.
  * `Scope` holds parameter/state arrays between runs (device-resident).  A
    run is functional: (feeds, state) -> (fetches, new state); persistable
    writes (optimizer updates) come back as donated outputs, so parameters
    stay in HBM and update in place.
  * Compiled executables are cached per (call mode, program mutation-stamp,
    feed signature, fetch list, layout, ..: `_KEY_PARTS`) — parity with
    executor.py:445 program cache, but the cached object is an XLA
    executable, not a prepared op list.
  * There is ONE way to execute a program (`Executor._call`): `run`,
    `run_steps` and `run_accumulated` name their step computation (one
    step, a `lax.scan` over steps, accumulate-then-optimize) and share the
    host path round it (feed, key, [compile], gather, dispatch, writeback,
    fetch), the cache, the locks and the instruments.  Sharding is an
    argument of that path: a CompiledProgram / ShardedProgram hands over
    its Program and a `Layout`.
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import framework as fw
from . import registry

# ---------------------------------------------------------------------------
# Places (reference: platform/place.h:79).  TPU-native: places name JAX
# backends; XLA/PJRT owns real device handles.
# ---------------------------------------------------------------------------


class Place:
    backend: str = "cpu"
    device_id: int = 0

    def __eq__(self, other):
        return (
            type(self) is type(other)
            and self.backend == other.backend
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((type(self).__name__, self.backend, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    backend = "cpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id


class TPUPlace(Place):
    backend = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id


def default_place() -> Place:
    """The one place that reads the backend: a process whose JAX default
    backend is the TPU computes there, any other on the CPU."""
    import jax

    if jax.default_backend() == "tpu":
        return TPUPlace(0)
    return CPUPlace(0)


# ---------------------------------------------------------------------------
# Scope (reference: scope.h:48; hierarchical name->Variable store)
# ---------------------------------------------------------------------------


class Scope:
    def __init__(self, parent: Optional["Scope"] = None):
        self.parent = parent
        self._vars: Dict[str, Any] = {}

    def var(self, name: str):
        if name not in self._vars:
            self._vars[name] = None
        return self._vars[name]

    def set_var(self, name: str, value):
        self._vars[name] = value

    def find_var(self, name: str):
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        s = self
        while s is not None:
            if name in s._vars:
                return True
            s = s.parent
        return False

    def new_scope(self) -> "Scope":
        return Scope(self)

    def local_var_names(self) -> List[str]:
        return list(self._vars)

    def drop_kids(self):
        pass  # child scopes are plain objects; GC handles them


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


import contextlib as _contextlib


@_contextlib.contextmanager
def scope_guard(scope: Scope):
    """Swap the global scope within a `with` block (reference:
    python/paddle/fluid/executor.py scope_guard) — lets user code isolate
    parameter state, e.g. train vs. loaded-inference scopes."""
    global _global_scope
    prev, _global_scope = _global_scope, scope
    try:
        yield scope
    finally:
        _global_scope = prev


# ---------------------------------------------------------------------------
# PRNG keys
# ---------------------------------------------------------------------------


def prng_key(seed: int):
    """Framework-created PRNG keys use the `rbg` implementation: threefry key
    derivation is VPU-heavy on TPU (measured ~30ms/step of pure dropout-mask
    cost on transformer-base) while rbg generates at near-memory speed and
    still supports fold_in.  Scoped here rather than flipping the global
    jax_default_prng_impl, so user jax code in the same process keeps stock
    threefry semantics."""
    import jax

    # typed key: carries its impl through fold_in/bernoulli/etc (a raw
    # uint32[4] key would be misread as threefry downstream)
    return jax.random.key(seed, impl="rbg")


# ---------------------------------------------------------------------------
# Trace context
# ---------------------------------------------------------------------------


class TraceContext:
    """Per-trace state handed to lowerings via LowerContext."""

    def __init__(self, program: fw.Program, base_key, is_test: bool = False,
                 mesh=None, check_nan_inf: bool = False):
        self.program = program
        self.base_key = base_key  # traced jax PRNG key (runtime arg)
        self.is_test = is_test
        self.mesh = mesh
        self._rng_counter = 0
        self.has_random = False
        self.amp_bf16 = bool(getattr(program, "_amp_bf16", False))
        # debug mode (reference FLAGS_check_nan_inf, operator.cc:943): record
        # one all-finite flag per op; the executor checks them on the host
        # after the step and names the first offending op
        self.check_nan_inf = check_nan_inf
        self.nan_checks: List[Tuple[str, Any]] = []
        # sparse-tier trace census (FLAGS_monitor only): the embedding
        # lowerings accumulate gather-launch / rows-touched counts here
        # (ops/nn_ops.py _note_embed_stats); trace_block publishes them as
        # per-step `embedding.*` gauges — a traced block IS one step
        self.embed_stats = {"gather_launches": 0, "sparse_rows_touched": 0}

    def next_rng_key(self, op=None):
        import jax

        self.has_random = True
        self._rng_counter += 1
        return jax.random.fold_in(self.base_key, self._rng_counter)


def trace_block(block: fw.Block, env: Dict[str, Any], tctx: TraceContext,
                ops: Optional[Sequence] = None):
    """Run every op's lowering over `env` (name -> traced value), in order.

    This is the TPU replacement for the interpreter hot loop
    (executor.cc:448): it executes at *trace time only*; the result is a
    single XLA computation.  `ops` restricts tracing to a subset (used by
    gradient accumulation to split the fwd/bwd prefix from the Optimize
    suffix).
    """
    import jax

    from .. import amp as _amp
    from ..flags import FLAGS
    from ..kernels import placement as _placement

    op_list = block.ops if ops is None else ops
    if FLAGS.record_lowered_ops:
        # executed-op recording (test flag): the op-contract gate asserts
        # every registered op reaches a trace — trace-time only, so the
        # run hot path never sees this
        from ..monitor import flight as _flight

        _flight.note_lowered_ops([op.type for op in op_list])

    for op in op_list:
        lower = registry.get_grad_lowering(op.type) if op.type.endswith("_grad") else None
        if lower is None:
            lower = registry.get(op.type).lower
        ins = {}
        for slot, names in op.inputs.items():
            ins[slot] = [env.get(n) if n else None for n in names]
        ctx = registry.LowerContext(op, op.attrs, tctx)
        ctx.env = env  # control-flow ops need sub-block access
        ctx.block = block
        try:
            # a trace that carries a mesh is GSPMD-partitioned: Mosaic
            # kernels cannot be placed in it (kernels/placement.py)
            # and every op lowers, amp casts of its inputs included,
            # under its own type's scope, so a profile puts each fusion
            # down to the op it came from (HLO metadata only: `op_name`,
            # the trace's `tf_op` stat)
            with _placement.gspmd_trace(tctx.mesh is not None), \
                    jax.named_scope(op.type):
                if tctx.amp_bf16:
                    ins = _amp.apply_cast_policy(op.type, ins)
                outs = lower(ctx, ins)
        except Exception as e:
            raise RuntimeError(
                f"Error lowering op {op.type!r} "
                f"(inputs={ {s: n for s, n in op.inputs.items() if n} }): {e}"
            ) from e
        for slot, vals in (outs or {}).items():
            names = op.output(slot)
            for name, val in zip(names, vals):
                if name and val is not None:
                    env[name] = val
        if FLAGS.chaos:
            # graph-level NaN injection (FLAGS_chaos_nan_var): poison the
            # named op output IN the compiled graph, so the numerics
            # tier's locate replay has a real in-graph origin to find —
            # unlike chaos_nan_at_step's host-side fake loss.  One flag
            # read per op at trace time only when chaos is armed.
            from ..testing import chaos as _chaos

            _chaos.poison_outputs(op, env)
        bvars = op.attrs.get("pipeline_boundary_vars")
        if bvars and getattr(tctx, "boundary_barriers", True):
            # Pipeline-annotated programs (parallel/pipeline/partition.py
            # split_program): values that cross a stage cut get an
            # optimization barrier at their producer, so XLA associates
            # the reductions CONSUMING them identically whether the value
            # is in-program (single-program run_accumulated) or a stage
            # boundary input (the pipeline schedules) — the
            # association-normalization behind the bit-parity contract.
            # Unannotated programs pay one dict miss per op, trace-time
            # only.
            import jax as _jax

            for n in bvars:
                if env.get(n) is not None:
                    env[n] = _jax.lax.optimization_barrier(env[n])
        if tctx.check_nan_inf and outs:
            flag = _all_finite_flag(outs, registry.unfilled_slots(op.type))
            if flag is not None:
                tctx.nan_checks.append((repr(op), flag))
    if any(tctx.embed_stats.values()):
        # per-step sparse-tier gauges (trace-time writes only; the outer
        # block's publish runs last, so sub-block traces never leave a
        # partial count behind).  Guarded inside _note_embed_stats: the
        # accumulators stay zero unless FLAGS.monitor was on at trace
        # time.  The same census rides the flight ring so
        # tools/trace_report.py can surface it from a postmortem dump.
        from .. import monitor
        from ..monitor import flight as _flight

        for k, v in tctx.embed_stats.items():
            monitor.gauge(f"embedding.{k}").set(v)
        _flight.record("embedding.census", **tctx.embed_stats)
    return env


def _all_finite_flag(outs, unfilled=()):
    """Scalar bool: every inexact-float leaf in an op's outputs is finite;
    the slots the op registers as `unfilled` are not looked at."""
    import jax
    import jax.numpy as jnp

    leaves = [
        leaf
        for slot, vals in outs.items()
        if slot not in unfilled
        for v in vals
        if v is not None
        for leaf in jax.tree_util.tree_leaves(v)
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.inexact)
    ]
    if not leaves:
        return None
    flag = jnp.bool_(True)
    for leaf in leaves:
        flag = jnp.logical_and(flag, jnp.isfinite(leaf).all())
    return flag


# ---------------------------------------------------------------------------
# Program analysis: feed/state/write sets
# ---------------------------------------------------------------------------


_RANDOM_OPS = frozenset(
    {
        "dropout",
        "dropout_add",  # fused epilogue: mask seed derives from the step key
        "uniform_random",
        "gaussian_random",
        "truncated_gaussian_random",
        "sampling_id",
        "random_crop",
        "shuffle_batch",
        "nce",  # draws negative samples from the trace key
    }
)


# Ops whose randomness is attr-gated: op type -> predicate over the op.
# fused attention draws from the step key only when its in-kernel weights
# dropout is armed; sample_token only for the stochastic strategies
# (greedy decode programs stay key-free and bit-deterministic).  Each
# predicate mirrors the op's registry derives_rng declaration — the
# static verifier cross-checks the two sides per op instance.
def _dropout_armed(op) -> bool:
    return bool(op.attrs.get("dropout_rate", 0.0))


_COND_RANDOM_OPS = {
    "fused_attention": _dropout_armed,
    "fused_qkv_attention": _dropout_armed,
    "sample_token":
        lambda op: op.attrs.get("strategy", "greedy") != "greedy",
}

# Extension point for ops registered OUTSIDE the core tree: a downstream
# registry.register(..., derives_rng=True) op must also call this so the
# executor threads the step key for it — the static verifier's
# rng-unthreaded check enforces the pairing.  (In-tree ops use the
# hand-maintained sets above: keeping them independent of the registry
# metadata is deliberate defense-in-depth — the verifier cross-checks the
# two, so a random op missing from EITHER side is a named pre-compile
# error instead of a frozen-mask bug.)
_EXTRA_RANDOM_OPS: set = set()


def register_random_op(op_type: str) -> None:
    """Declare that `op_type`'s lowering draws PRNG bits from the step
    key.  Pairs with registry.register(..., derives_rng=...); the
    verifier (paddle_tpu/analysis) rejects programs whose derives_rng
    ops are not known here."""
    _EXTRA_RANDOM_OPS.add(op_type)


# ONE process-wide mutex for program verification: the verifier's shape
# re-inference temporarily mutates Variable.shape on the Program being
# verified (snapshot/restored), and a Program can be shared across
# Executor instances (train + eval executors, per-thread executors over
# default_main_program) — a per-executor lock would let two executors'
# verifies interleave on the same IR.
import threading as _threading

_VERIFY_MUTEX = _threading.Lock()


def _iter_ops_recursive(block: fw.Block):
    """Yield the block's ops, descending into sub_block attrs (while /
    conditional_block bodies)."""
    for op in block.ops:
        yield op
        sub = op.attrs.get("sub_block")
        if sub is not None:
            yield from _iter_ops_recursive(sub)


def op_threads_rng(op) -> bool:
    """Whether the executor threads the step key on account of THIS op.

    The single source of truth for step-key threading: program_uses_random
    folds it over the block, and the static verifier
    (paddle_tpu/analysis/verifier.py) cross-checks it against the
    registry's derives_rng contract metadata — an op whose lowering draws
    PRNG bits but is invisible here would reuse the trace-constant base
    key on every plain run (the PR-4 dropout_add bug class), so the
    verifier turns that mismatch into a pre-compile error."""
    cond = _COND_RANDOM_OPS.get(op.type)
    return bool(
        op.type in _RANDOM_OPS
        or op.type in _EXTRA_RANDOM_OPS
        or op.type.endswith("_grad")
        or (cond is not None and cond(op))
    )


def program_uses_random(block: fw.Block) -> bool:
    """Whether lowering may draw PRNG bits (then the compiled fn takes a key
    argument).  Grad ops count: the generic vjp re-traces forward lowerings.
    fused_attention / fused_qkv_attention count only when their in-kernel
    weights dropout is on (the mask seed derives from the step key)."""
    return any(op_threads_rng(op) for op in _iter_ops_recursive(block))


def analyze_block_io(
    block: fw.Block, feed_names: Sequence[str], scope: Scope
) -> Tuple[List[str], List[str]]:
    """Return (state_reads, state_writes): scope-resident vars the block reads
    before writing, and persistable/scope vars it writes."""
    defined = set(feed_names)
    reads: List[str] = []
    writes: List[str] = []
    seen_r, seen_w = set(), set()
    for op in block.ops:
        in_names = list(op.input_arg_names())
        sub = op.attrs.get("sub_block")
        if sub is not None:
            # while/conditional bodies read outer state (params!) from inside
            # the sub-block; those are reads of the outer op.  Names only
            # live inside the sub-block are filtered by the scope check.
            in_names += [
                n
                for inner in _iter_ops_recursive(sub)
                for n in inner.input_arg_names()
            ]
        for n in in_names:
            if n and n not in defined and n not in seen_r:
                if scope.has_var(n) and scope.find_var(n) is not None:
                    reads.append(n)
                    seen_r.add(n)
                    defined.add(n)
        for n in op.output_arg_names():
            if not n:
                continue
            defined.add(n)
            v = block._find_var_recursive(n)
            persistable = (v is not None and v.persistable) or scope.has_var(n)
            if persistable and n not in seen_w:
                writes.append(n)
                seen_w.add(n)
    return reads, writes


# ---------------------------------------------------------------------------
# Telemetry (paddle_tpu.monitor, gated on FLAGS.monitor)
# ---------------------------------------------------------------------------


# Named components of THE cache key (Executor._prepare builds the tuple in
# this order, for every call mode).  The recompile detector diffs
# consecutive keys against these names so a silent retrace storm logs
# WHICH component keeps changing (feed-signature churn from ragged batch
# shapes is the classic one).  `count`: None for a single step, the steps
# of run_steps, the micro-batches of run_accumulated; `layout`: the
# wrapped program's Layout, None for a plain Program.
_KEY_PARTS = (
    "call-mode", "program-stamp", "amp-mode", "is-test-mode",
    "check-nan-inf", "scope-signature", "count", "feed-names",
    "feed-signature", "fetch-list", "layout",
)

# compile times are seconds-scale (XLA), run times sub-second: separate
# bucket ladders keep both histograms informative
_COMPILE_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                    10.0, 30.0, 60.0, 120.0, 300.0)


class _CallSpans:
    """The host phases of ONE executor call, told twice from the same
    boundaries.

    To the profiler: `executor.<mode>` and, tiling it in call order,
    `executor.feed|key|compile|gather|dispatch|writeback|fetch`, each a
    `jax.profiler.TraceAnnotation` carrying `call` (the run id).  They
    are entered unconditionally (well under a microsecond each with no
    session), so ANY profiler session has the host's side of the call
    on the device planes' clock.

    To the flight ring, when `monitor.spans_on()` held at entry: the
    same boundaries as `phases`, [name, start offset, seconds] off one
    `perf_counter_ns` sequence that starts at entry (a boundary is ONE
    stamp: the end of a phase is the start of the next), in ONE event
    written at exit, whose `dur` runs to that exit: what lies between
    the end of `fetch` and it is the call's frame coming down (some
    hundreds of donated arrays are freed there) and this bookkeeping.
    With tracing off no stamp is taken and nothing is written."""

    __slots__ = ("mode", "call", "mon", "on", "phases", "compile0",
                 "outcome", "counters", "entry_ns", "_annotation", "_parent",
                 "_child", "_in_call", "_open")

    def __init__(self, mode: str, call: int):
        self.mode, self.call = mode, call
        self.phases: List[list] = []
        self.compile0 = self.outcome = self.counters = None
        self._child = self._open = None

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        from ..monitor import enabled, spans_on
        from ..monitor import flight as _flight

        self.on = spans_on()
        self.mon = self.on and enabled()
        self._in_call = _flight.executor_call()
        self._in_call.__enter__()
        self._annotation = TraceAnnotation
        self._parent = TraceAnnotation(f"executor.{self.mode}",
                                       call=self.call)
        self._parent.__enter__()
        if self.on:
            import time as _time

            self.entry_ns = _time.perf_counter_ns()
        return self

    def phase(self, name: Optional[str]):
        """The boundary between the open phase and `name` (None: the
        last phase ends here)."""
        if self.on:
            import time as _time

            now = _time.perf_counter_ns()
            if self._open is not None:
                opened, t_open = self._open
                self.phases.append([opened, (t_open - self.entry_ns) / 1e9,
                                    (now - t_open) / 1e9])
            self._open = None if name is None else (name, now)
        if self._child is not None:
            self._child.__exit__(None, None, None)
            self._child = None
        if name is not None:
            self._child = self._annotation(f"executor.{name}",
                                           call=self.call)
            self._child.__enter__()

    def compiling(self):
        """A miss: open the `compile` phase, and note where jax's own
        compile-phase totals stand (its work comes with the first
        dispatch), so that the call's flight event carries its share."""
        self.phase("compile")
        if self.on:
            from ..monitor import flight as _flight

            self.compile0 = _flight.compile_phases()

    def finished(self, compiled_now, steps, waited, feed_vals, np_outs):
        """The call went through: what its record will say.  `waited`:
        the fetch blocked for the device (return_numpy)."""
        nbytes = None
        if self.mon:
            nbytes = tuple(
                sum(int(getattr(v, "nbytes", 0) or 0) for v in vals or ())
                for vals in (feed_vals, np_outs))
        self.outcome = (compiled_now, steps, waited, nbytes)

    def __exit__(self, *exc):
        self.phase(None)
        if self.on and self.outcome is not None and exc[0] is None:
            self._record()
        self._parent.__exit__(None, None, None)
        self._in_call.__exit__(None, None, None)
        return False

    def _record(self):
        """One finished executor call, tracing on: ONE flight event with
        the call's phases (`executor.compile` when this call traced and
        compiled — jax.jit compiles lazily, so the miss call's duration
        IS the compile cost, and jax's own phases of it ride along —
        else `executor.<mode>`), and under FLAGS.monitor the registry's
        wall times, the dispatch / device-wait split, feed and fetch
        bytes.  Every time comes off the one clock sequence."""
        import time as _time

        from .. import monitor
        from ..monitor import flight as _flight
        from ..monitor import tracing as _tracing

        mode, mon = self.mode, self.mon
        compiled_now, steps, waited, nbytes = self.outcome
        dt = (_time.perf_counter_ns() - self.entry_ns) / 1e9
        # span start bridged to the epoch clock the unified timeline and
        # request traces ride (perf_counter + the import-time offset —
        # `time.time() - dt` would drift off the other spans' stamps
        # under NTP slew)
        t0_epoch = _tracing.pc_to_epoch(self.entry_ns / 1e9)
        fields = {"t0": t0_epoch, "dur": round(dt, 6), "call": self.call,
                  "phases": [[n, round(a, 6), round(d, 6)]
                             for n, a, d in self.phases]}
        meta = {"compiled": int(compiled_now)}
        if steps is not None:
            fields["steps"] = meta["steps"] = steps
        if self.counters:
            # the program's device counters (monitor.device_counter), each
            # its mean over the call's steps
            fields["counters"] = self.counters
        # what the parent annotation learns only during the call
        self._parent.set_metadata(**meta)
        if mon:
            monitor.counter(f"executor.{mode}.calls").inc()
        if compiled_now:
            # keep the miss call OUT of run_seconds so run-latency
            # percentiles are not dominated by seconds-scale compiles
            if mon:
                monitor.counter("executor.compiles").inc()
                monitor.histogram(
                    "executor.compile_seconds",
                    buckets=_COMPILE_BUCKETS).observe(dt)
            if self.compile0 is not None:
                now = _flight.compile_phases()
                fields.update({k: round(now[k] - v, 6)
                               for k, v in self.compile0.items()})
            _flight.default_recorder().record(
                "executor.compile", mode=mode, **fields)
        else:
            if waited:
                # dispatch = everything but the blocking fetch (Python
                # bookkeeping + XLA enqueue); device_wait = the blocking
                # np.asarray conversion.  Async dispatch means compute
                # overlaps the dispatch window, so device_wait is a LOWER
                # bound on device time and dispatch an upper bound on
                # launch overhead (tools/perf_report.py).
                device_wait_s = sum(p[2] for p in self.phases
                                    if p[0] == "fetch")
                dispatch_s = max(dt - device_wait_s, 0.0)
                fields.update(dispatch_s=round(dispatch_s, 6),
                              device_wait_s=round(device_wait_s, 6))
                if mon:
                    monitor.histogram(
                        "executor.dispatch_seconds").observe(dispatch_s)
                    monitor.histogram(
                        "executor.device_wait_seconds").observe(
                            device_wait_s)
            if mon:
                monitor.histogram("executor.run_seconds").observe(dt)
            _flight.default_recorder().record(f"executor.{mode}", **fields)
        if not mon:
            return
        for name, n in zip(("executor.feed_bytes", "executor.fetch_bytes"),
                           nbytes):
            if n:
                monitor.counter(name).inc(n)
        # request-tracing hook: when a serving batcher armed this thread's
        # executor context (monitor/tracing.py), the call's compile-vs-run
        # wall time lands as a sub-span in every participating request
        # trace; one thread-local read otherwise
        _tracing.note_executor(mode, t0_epoch, dt, compiled_now)


# ---------------------------------------------------------------------------
# Layout: where a wrapped program's arrays live
# ---------------------------------------------------------------------------


class Layout:
    """What a wrapped program (CompiledProgram in data-parallel mode,
    parallel/sharding.py ShardedProgram) hands the executor beside its
    inner Program: the mesh, a PartitionSpec per feed and one per state
    name.  The one call path then traces with the mesh, gives the one
    `jax.jit` its in/out shardings, places the feeds and re-places state
    that lies elsewhere.  Compared by identity: a Layout is the `layout`
    part of the cache key (which also keeps it alive, so that its id is
    never another's)."""

    def __init__(self, mesh, feed_spec, state_spec):
        self.mesh = mesh
        self.feed_spec = feed_spec    # feed name -> PartitionSpec
        self.state_spec = state_spec  # (state name, shape | None) -> spec

    def feed_sharding(self, name, stacked: bool):
        from jax.sharding import NamedSharding, PartitionSpec

        spec = self.feed_spec(name)
        if stacked:  # the steps axis comes first and is held whole
            spec = PartitionSpec(None, *spec)
        return NamedSharding(self.mesh, spec)

    def state_sharding(self, name, value):
        from jax.sharding import NamedSharding

        return NamedSharding(
            self.mesh, self.state_spec(name, getattr(value, "shape", None)))


def _unwrap(program):
    """(the Program to run, its Layout or None) of what a caller handed
    to run / run_steps / run_accumulated / lower."""
    if program is None:
        return fw.default_main_program(), None
    unwrap = getattr(program, "_unwrap", None)
    return unwrap() if unwrap is not None else (program, None)


# ---------------------------------------------------------------------------
# Step programs: the three computations, as closures over one trace's pieces
# ---------------------------------------------------------------------------


class _StepTrace:
    """What the three step computations share while jax traces them: the
    block and its state split, a TraceContext per traced step, the env a
    step starts from, the fetch look-up and the nan-flag stacking."""

    def __init__(self, program, feed_names, fetch_names, rw_state, ro_state,
                 state_writes, check, mesh):
        self.program = program
        self.block = program.global_block()
        self.feed_names, self.fetch_names = feed_names, fetch_names
        self.rw_state, self.ro_state = rw_state, ro_state
        self.state_writes = state_writes
        # write-only names (created by the program): surfaced from the
        # last step's outputs rather than carried through a scan
        self.wo_state = [n for n in state_writes if n not in set(rw_state)]
        self.check, self.mesh = check, mesh
        # op descriptions for check_nan_inf mode, parallel to the flags the
        # closure returns; filled in while it is traced
        self.nan_check_ops: List[str] = []

    def context(self, key) -> TraceContext:
        return TraceContext(
            self.program, key,
            is_test=getattr(self.program, "_is_test", False),
            mesh=self.mesh, check_nan_inf=self.check)

    def env(self, feed_vals, rw_vals, ro_vals) -> Dict[str, Any]:
        env: Dict[str, Any] = {}
        env.update(zip(self.feed_names, feed_vals))
        env.update(zip(self.rw_state, rw_vals))
        env.update(zip(self.ro_state, ro_vals))
        return env

    def fetch(self, env):
        for n in self.fetch_names:
            if n not in env:
                raise KeyError(
                    f"fetch target {n!r} was not produced by the program")
        return [env[n] for n in self.fetch_names]

    def flags(self, tctx):
        """One all-finite flag per checked op of `tctx`'s trace, stacked."""
        import jax.numpy as jnp

        if self.check and tctx.nan_checks:
            return jnp.stack([f for _, f in tctx.nan_checks])
        return jnp.ones((0,), bool)

    def note_ops(self, *tctxs):
        self.nan_check_ops[:] = [d for t in tctxs for d, _ in t.nan_checks]


# A builder returns (the closure to jit, whether it takes the step key as a
# fourth argument, whether it returns nan flags as a third result).


def _one_step(t: _StepTrace, count):
    """`run`: the block, once.  The key is an argument only where the
    program draws from it; a key-free program stays a function of
    (feeds, state) alone."""

    def run_fn(feed_vals, rw_vals, ro_vals, key=None):
        if key is None:
            key = prng_key(t.program.random_seed or 0)
        tctx = t.context(key)
        env = t.env(feed_vals, rw_vals, ro_vals)
        trace_block(t.block, env, tctx)
        fetches = t.fetch(env)
        new_state = [env.get(n) for n in t.state_writes]
        if t.check:
            t.note_ops(tctx)
            return fetches, new_state, t.flags(tctx)
        return fetches, new_state

    if program_uses_random(t.block):
        return run_fn, True, t.check
    return (lambda f, rw, ro: run_fn(f, rw, ro)), False, t.check


def _scan_steps(t: _StepTrace, steps):
    """`run_steps`: a `lax.scan` of the block over the feeds' leading
    axis, the rw state as its carry."""
    import jax
    import jax.numpy as jnp

    def scan_fn(feed_vals, rw_vals, ro_vals, base_key):
        def body(carry, xs):
            rw, i = carry, xs[0]
            per_step = xs[1]
            tctx = t.context(jax.random.fold_in(base_key, i))
            env = t.env(per_step, rw, ro_vals)
            trace_block(t.block, env, tctx)
            new_rw = [env.get(n, v) for n, v in zip(t.rw_state, rw)]
            fetches = t.fetch(env)
            wo = [env.get(n) for n in t.wo_state]
            if t.check:
                t.note_ops(tctx)
                return new_rw, (fetches, wo, t.flags(tctx))
            return new_rw, (fetches, wo)

        xs = (jnp.arange(steps), feed_vals)
        final_rw, step_outs = jax.lax.scan(body, list(rw_vals), xs)
        stacked, wo_stacked = step_outs[:2]
        # state ordering matches state_writes: rw carries final values,
        # write-only vars take their last-step value
        by_name = dict(zip(t.rw_state, final_rw))
        by_name.update(
            {n: (v[-1] if v is not None else None)
             for n, v in zip(t.wo_state, wo_stacked)}
        )
        new_state = [by_name.get(n) for n in t.state_writes]
        if t.check:
            return stacked, new_state, step_outs[2]
        return stacked, new_state

    return scan_fn, True, t.check


def _accumulate(t: _StepTrace, k, unroll=False):
    """`run_accumulated`: the fwd/bwd prefix over k micro-batches summing
    the gradients the optimizer reads, then the Optimize suffix once on
    their mean.  Its flags come in two parts, ([k, prefix ops], [suffix
    ops]), and are returned whether or not they are checked."""
    import jax
    import jax.numpy as jnp

    block, feed_names, fetch_names = t.block, t.feed_names, t.fetch_names
    rw_state, ro_state, wo_state = t.rw_state, t.ro_state, t.wo_state

    def optimizes(op):
        role = int(op.attrs.get(fw.OpRole.ROLE_ATTR_NAME, 0))
        return bool(role & fw.OpRole.Optimize)

    prefix_ops = [op for op in block.ops if not optimizes(op)]
    suffix_ops = [op for op in block.ops if optimizes(op)]
    if not suffix_ops:
        raise ValueError(
            "run_accumulated: program has no Optimize-role ops "
            "(call optimizer.minimize first)")
    # the gradients the optimizer consumes are what we accumulate
    grad_names = sorted({
        n for op in suffix_ops for n in op.inputs.get("Grad", []) if n
    })

    # Fetch split: prefix targets are stashed per micro-batch and
    # returned stacked [K, ...]; Optimize-suffix targets (updated
    # params, lr) return their single post-suffix value — the
    # fetch-from-prefix-only restriction is gone (the pipeline
    # scheduler and plain users both fetch suffix products).
    prefix_avail = set(feed_names) | set(rw_state) | set(ro_state)
    for op in prefix_ops:
        prefix_avail.update(n for n in op.output_arg_names() if n)
    suffix_outputs = {
        n for op in suffix_ops for n in op.output_arg_names() if n
    }
    # suffix takes precedence for names it PRODUCES: fetching an
    # updated param/moment/lr returns the single post-update value
    # (matching PipelineProgram's opt-fetch classification); names
    # only the prefix covers come back stacked per micro-batch
    prefix_fetch = [n for n in fetch_names
                    if n in prefix_avail and n not in suffix_outputs]
    suffix_fetch = [n for n in fetch_names if n in suffix_outputs]
    unknown = [n for n in fetch_names
               if n not in prefix_avail and n not in suffix_outputs]
    if unknown:
        raise KeyError(
            f"fetch target(s) {unknown} produced by neither the "
            f"fwd/bwd prefix nor the Optimize suffix of this program")

    def acc_fn(feed_vals, rw_vals, ro_vals, base_key):
        rw0 = list(rw_vals)

        def run_prefix(i_key, per_step, rw):
            tctx = t.context(i_key)
            env = t.env(per_step, rw, ro_vals)
            trace_block(block, env, tctx, ops=prefix_ops)
            new_rw = [env.get(n, v) for n, v in zip(rw_state, rw)]
            # fetch values are association-isolated (barrier): the
            # reduce producing a fetched loss must not fuse with its
            # scan-body packaging, or the same value compiled in a
            # pipeline stage's straight-line program can differ by an
            # ulp — the bit-parity contract of parallel/pipeline
            # (value-dependent, surfaced under a multi-device-touched
            # compiler state).  Fetch-only: env values downstream ops
            # read stay unbarriered.
            fetches = [jax.lax.optimization_barrier(env[n])
                       for n in prefix_fetch]
            wo = [env.get(n) for n in wo_state]
            return env, new_rw, fetches, wo, t.flags(tctx), tctx

        def body(carry, xs):
            rw, grad_sums = carry
            i, per_step = xs[0], xs[1]
            env, new_rw, fetches, wo, flags, _ = run_prefix(
                jax.random.fold_in(base_key, i), per_step, rw)
            new_sums = [
                s + env[g] for s, g in zip(grad_sums, grad_names)
            ]
            return (new_rw, new_sums), (fetches, wo, flags)

        # step 0 traced inline (gives grad-sum init without a
        # throwaway zeros trace), steps 1..k-1 under lax.scan
        env0, rw1, fetches0, wo0, flags0, tctx0 = run_prefix(
            jax.random.fold_in(base_key, 0),
            [v[0] for v in feed_vals], rw0)
        sums0 = [env0[g] for g in grad_names]

        if k > 1 and unroll:
            # straight-line micro-batches (the reference
            # multi_batch_merge_pass shape): identical math to the
            # scan, fusion context identical to step 0's inline trace
            rw_u, sums_u = rw1, sums0
            fetch_steps = [fetches0]
            wo_last = wo0
            flag_steps = [flags0]
            for i in range(1, k):
                (rw_u, sums_u), (f_i, wo_i, fl_i) = body(
                    (rw_u, sums_u), (jnp.asarray(i),
                                     [v[i] for v in feed_vals]))
                fetch_steps.append(f_i)
                wo_last = [(wi if wi is not None else wl)
                           for wl, wi in zip(wo_last, wo_i)]
                flag_steps.append(fl_i)
            rw_f, sums_f = rw_u, sums_u
            fetches = [jnp.stack(fs) for fs in zip(*fetch_steps)]
            all_flags = jnp.stack(flag_steps)
        elif k > 1:
            xs = (jnp.arange(1, k),
                  [v[1:] for v in feed_vals])
            (rw_f, sums_f), (rest, wo_rest, flag_rest) = jax.lax.scan(
                body, (rw1, sums0), xs)
            fetches = [
                jnp.concatenate([f0[None], fr], axis=0)
                for f0, fr in zip(fetches0, rest)
            ]
            wo_last = [
                (wr[-1] if wr is not None else w0)
                for w0, wr in zip(wo0, wo_rest)
            ]
            all_flags = jnp.concatenate(
                [flags0[None], flag_rest], axis=0)
        else:
            rw_f, sums_f = rw1, sums0
            fetches = [f0[None] for f0 in fetches0]
            wo_last = wo0
            all_flags = flags0[None]

        # optimizer suffix ONCE on the averaged gradients
        envf = t.env((), rw_f, ro_vals)
        for g, s in zip(grad_names, sums_f):
            envf[g] = s / float(k)
        tctxf = t.context(jax.random.fold_in(base_key, k))
        trace_block(block, envf, tctxf, ops=suffix_ops)
        t.note_ops(tctx0, tctxf)
        by_name = dict(zip(rw_state, rw_f))
        by_name.update(zip(wo_state, wo_last))
        # suffix outputs (param updates) win over scanned values
        for n in t.state_writes:
            if n in envf and envf[n] is not None:
                by_name[n] = envf[n]
        new_state = [by_name.get(n) for n in t.state_writes]
        # reassemble fetches in caller order: prefix targets stacked
        # [K, ...], suffix targets as their single post-update value
        fetch_by_name = dict(zip(prefix_fetch, fetches))
        fetch_by_name.update((n, envf[n]) for n in suffix_fetch)
        out_fetches = [fetch_by_name[n] for n in fetch_names]
        return out_fetches, new_state, (all_flags, t.flags(tctxf))

    return acc_fn, True, True


class _Mode(collections.namedtuple("_Mode", "name call_mode stacked build")):
    """One of the ways to call a program: `name` is the entry point's
    (spans, flight events, error texts), `call_mode` its part of the cache
    key, `stacked` whether the feeds carry a leading [count] axis, `build`
    the builder of its step closure."""


_RUN = _Mode("run", "run", False, _one_step)
_STEPS = _Mode("run_steps", "run_steps", True, _scan_steps)
_ACCUMULATED = _Mode("run_accumulated", "run_accumulated", True, _accumulate)
_ACCUMULATED_UNROLLED = _Mode(
    "run_accumulated", "run_accumulated_unrolled", True,
    lambda t, k: _accumulate(t, k, unroll=True))


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class _CompiledEntry:
    """Compiled executable + its state signature.

    State is split so parameter buffers can be donated (updated in place in
    HBM) while read-only state (e.g. a learning-rate var) survives the call:
      rw_state — read AND written (params, optimizer moments): donated
      ro_state — read only: not donated
      state_writes — all written names, in output order
    """

    __slots__ = ("jitted", "rw_state", "ro_state", "state_writes",
                 "needs_key", "nan_check_ops", "shardings", "run_lock")

    def __init__(self, jitted, rw_state, ro_state, state_writes, needs_key,
                 nan_check_ops=None, shardings=None, run_lock=None):
        # the jax.jit-wrapped step closure: what a call dispatches, and
        # what AOT introspection lowers again (Executor.lower)
        self.jitted = jitted
        self.rw_state = rw_state
        self.ro_state = ro_state
        self.state_writes = state_writes
        self.needs_key = needs_key
        # op descriptions for check_nan_inf mode (parallel to the extra flag
        # outputs of jitted); None when the mode is off.  The list is filled
        # in during the first trace.
        self.nan_check_ops = nan_check_ops
        # state name -> NamedSharding under a Layout (state found lying
        # elsewhere is re-placed before the call), None for a plain Program
        self.shardings = shardings
        # A stateful entry donates its rw buffers to the executable:
        # concurrent calls would hand the SAME donated buffer to two
        # executions (use-after-donate) and interleave the scope
        # write-backs (torn state).  The lock's domain is the SHARED
        # SCOPE STATE, not the entry: different feed signatures, call
        # modes and layouts of one program donate the same scope arrays,
        # so every stateful entry of an Executor carries the executor's
        # one stateful-run lock (None for stateless entries — purely
        # functional, serving threads run those concurrently).
        self.run_lock = run_lock if state_writes else None


class _Call:
    """One call's resolved arguments, from `_prepare` to `_fetch`."""

    __slots__ = ("mode", "program", "scope", "feed", "feed_vals",
                 "fetch_names", "user_fetch_n", "counters", "count",
                 "entry", "compiled_now")


class Executor:
    def __init__(self, place: Optional[Place] = None,
                 check_nan_inf: Optional[bool] = None):
        import os
        import threading

        self.place = place or default_place()
        if (isinstance(self.place, TPUPlace)
                and not isinstance(default_place(), TPUPlace)):
            # arrays go where jax's default backend puts them: a TPUPlace
            # on a process that has no TPU would train on the CPU under
            # the same metric names
            raise RuntimeError(
                f"Executor({self.place!r}): jax's default backend in "
                f"this process is not the TPU; use CPUPlace, or run "
                f"where jax finds the chip")
        self._cache: Dict[Any, _CompiledEntry] = {}
        self._ref_names_cache: Dict[Any, tuple] = {}
        self._run_counter = 0
        # numerics failing-step replay (monitor/numerics.py): when set,
        # the next _next_run_id() returns THIS value once, without
        # advancing the counter — the replayed step folds the SAME id
        # into its PRNG key, so dropout masks come out bit-identical to
        # the step being diagnosed
        self._forced_run_id: Optional[int] = None
        # pre-compile static-verification memo: (program fingerprint,
        # scope signature, feeds, fetches) already verified by this
        # executor — verification runs at most once per signature, so a
        # warm serving process never re-walks a program
        # (paddle_tpu/analysis).  Mutation safety rides the module-level
        # _VERIFY_MUTEX (a Program can be shared across executors).
        self._verified = set()
        # Serving threads (paddle_tpu/serving dynamic batcher, user thread
        # pools over Predictor) hammer run() concurrently: the compile
        # cache uses per-key locks so N threads x M signatures compile
        # exactly M times (double-checked under the key's lock), and the
        # run counter draws under a lock so key-deriving programs never
        # fold in a duplicate counter value.
        self._counter_lock = threading.Lock()
        self._compile_locks_guard = threading.Lock()
        self._compile_locks: Dict[Any, threading.Lock] = {}
        # ONE lock for every stateful run of this executor: stateful
        # entries donate scope rw buffers, and entries of DIFFERENT feed
        # signatures (serving bucket ladder) donate the SAME scope
        # arrays — per-entry locking would let two signatures race a
        # use-after-donate.  Predictor hands this same lock to its AOT
        # bundles (inference.py), closing the JIT-vs-bundle race too.
        self._stateful_lock = threading.Lock()
        # recompile-detector state below is shared mutable: serialize
        # lookups/commits so concurrent serving threads cannot tear the
        # pending-stamp bookkeeping (recompile attribution would drift)
        self._detector_lock = threading.Lock()
        # recompile detector state: last cache key per (mode, program)
        # + the program-stamps that have compiled at least once (a later
        # miss on a seen stamp IS a recompile); pending = missed but not
        # yet committed to the cache (a retried failed compile is not a
        # recompile); only written when FLAGS.monitor is on
        self._last_key_by_program = {}
        self._compiled_stamps = set()
        self._pending_stamps = set()
        # debug mode, parity with the reference's FLAGS_check_nan_inf
        # (operator.cc:943): validate every op's outputs are finite
        if check_nan_inf is None:
            from ..flags import FLAGS  # typed flag registry w/ env override

            check_nan_inf = FLAGS.check_nan_inf
        self.check_nan_inf = check_nan_inf
        # jax's compile phases inside this process's Executor calls are
        # totalled from the first Executor on (monitor.compile_phases())
        from ..monitor import flight as _flight

        _flight.listen_for_compile_phases()

    def close(self):
        self._cache.clear()

    # -- public API ------------------------------------------------------
    def run(
        self,
        program: Optional[fw.Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        """Run `program` once.  `use_program_cache` is accepted for Fluid
        scripts and is without effect: every call goes through the
        executable cache."""
        # fault-injection hook (FLAGS_chaos_kill_at_run): one flag read
        # when chaos is off, SIGKILL mid-training when armed — the
        # preemption the checkpoint layer must survive
        from ..testing import chaos as _chaos

        _chaos.on_executor_run()
        # The pipeline programs (parallel/pipeline: several stage programs
        # under a schedule) run themselves through their _run hook, with
        # compile caches of their own, so only coarse telemetry (calls,
        # wall time, errors) is recorded here.
        if program is not None and hasattr(program, "_run"):
            from ..monitor import enabled as _mon_enabled

            if not _mon_enabled():
                return program._run(self, feed, fetch_list, scope,
                                    return_numpy)
            import time as _time

            from .. import monitor

            t0 = _time.perf_counter()
            try:
                outs = program._run(self, feed, fetch_list, scope,
                                    return_numpy)
            except Exception:
                monitor.counter("executor.delegated.errors").inc()
                raise
            dt = _time.perf_counter() - t0
            monitor.counter("executor.delegated.calls").inc()
            monitor.histogram("executor.delegated_seconds").observe(dt)
            return outs
        return self._call(_RUN, None, program, feed, fetch_list, scope,
                          return_numpy)

    def run_steps(
        self,
        program: Optional[fw.Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        steps: Optional[int] = None,
        return_numpy: bool = True,
    ):
        """Run `steps` training iterations in ONE compiled XLA call.

        TPU-first replacement for the reference's prepare-once/run-many
        Executor loop (executor.cc:372 Prepare + :413 RunPreparedContext):
        the whole multi-step loop is a single `lax.scan`, so parameters stay
        in HBM across steps and there is exactly one host round-trip per
        call — host dispatch latency amortizes over `steps`.

        `feed` values must carry a leading [steps, ...] axis (one slice per
        iteration).  Returns fetches stacked along a leading [steps] axis.
        """
        return self._call(_STEPS, steps, program, feed, fetch_list, scope,
                          return_numpy)

    def run_accumulated(
        self,
        program: Optional[fw.Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        accumulate_steps: Optional[int] = None,
        return_numpy: bool = True,
        unroll: bool = False,
    ):
        """Gradient accumulation in ONE compiled XLA call: run the
        forward+backward prefix over K micro-batches (feed arrays carry a
        leading [K, micro_bs, ...] axis) summing every parameter gradient,
        then run the Optimize-role op suffix ONCE on the averaged grads.

        unroll=True traces every micro-batch straight-line instead of
        scanning 1..K-1 — the literal shape of the reference pass (clone
        fwd/bwd K times).  Math is identical; compile time grows ~K-fold;
        the pipeline tier's strict bit-parity gates compare against this
        form because XLA may tile a reduce inside a scan body differently
        from the same reduce compiled straight-line (a fetched loss
        scalar can re-round by 1 ulp between the two — parameter updates
        are bit-identical either way, probed in tests/test_pipeline.py).

        The capability of the reference's multi_batch_merge_pass
        (ir/multi_batch_merge_pass.h:25 — clone fwd/bwd N times, average,
        optimize once), realized as a lax.scan instead of a graph clone.
        Gradient clipping/regularization ops carry the Backward role, so
        they apply per micro-batch (matching the reference pass, which
        clones everything before the optimizer).

        Fetch contract: targets produced by the fwd/bwd prefix (or
        feeds/state) come back stacked along a leading [K] axis, one
        slice per micro-batch; targets produced by the Optimize suffix
        (updated params, lr values) come back UN-stacked — the
        post-update value.  A name neither side produces raises KeyError
        at compile, naming both sets.
        """
        return self._call(_ACCUMULATED_UNROLLED if unroll else _ACCUMULATED,
                          accumulate_steps, program, feed, fetch_list, scope,
                          return_numpy)

    def lower(self, program=None, feed=None, fetch_list=None, scope=None,
              steps: Optional[int] = None):
        """The `jax.stages.Lowered` of the executable that `run` (with
        `steps`: `run_steps` of that many steps) runs for this signature,
        lowered on the live arguments: nothing runs and nothing is donated.
        `.compile()` gives XLA's cost and memory analysis and the optimized
        HLO.  The scope must hold the state the program reads."""
        entry, args = self._lowerable(program, feed, fetch_list, scope, steps)
        return entry.jitted.lower(*args)

    # -- the one call path -----------------------------------------------
    def _call(self, mode, count, program, feed, fetch_list, scope,
              return_numpy):
        """Every call of every mode and layout: `feed`, `key`, [`compile`],
        `gather`, `dispatch`, `writeback`, `fetch`."""
        with _CallSpans(mode.name, self._next_run_id()) as spans:
            call = self._prepare(spans, mode, count, program, feed,
                                 fetch_list, scope)
            fetches = self._execute(spans, call)
            return self._fetch(spans, call, fetches, return_numpy)

    def _lowerable(self, program, feed, fetch_list, scope, steps=None):
        """(entry, arguments) of the signature, for lowering its jitted
        closure again: `lower`, and callers that need the entry's names
        beside it (inference.export_aot_bundle).  Draws no run id."""
        with _CallSpans("lower", 0) as spans:
            call = self._prepare(spans, _RUN if steps is None else _STEPS,
                                 steps, program, feed, fetch_list, scope)
            return call.entry, self._args(call, 0)

    def _prepare(self, spans, mode, count, program, feed, fetch_list, scope):
        """`feed`, `key`, [`compile`]: the arguments resolved, the feeds on
        the device, the entry looked up or compiled."""
        spans.phase("feed")
        c = _Call()
        c.mode = mode
        c.program, layout = _unwrap(program)
        program = c.program
        c.feed = feed = feed or {}
        c.scope = scope = scope or global_scope()
        fetch_names = [
            v.name if isinstance(v, fw.Variable) else v
            for v in (fetch_list or [])
        ]
        # numerics-instrumented programs (analysis/numerics.py) carry
        # packed [N, 4] stats tensors that ride the user's fetch — ONE
        # device->host transfer per step, stripped before returning
        c.user_fetch_n, fetch_names = self._numerics_fetch(program,
                                                           fetch_names)
        # the program's device counters ride every call as outputs of the
        # compiled steps (a traced call must not compile anew); they are
        # read back only while tracing is on (_fetch)
        counters = getattr(program, "_device_counters", None) or {}
        c.counters = tuple(counters)
        c.fetch_names = fetch_names = fetch_names + list(counters.values())
        feed_names = sorted(feed)
        c.feed_vals = feed_vals = [
            self._to_device_array(program, n, feed[n], layout, mode.stacked)
            for n in feed_names
        ]
        if mode.stacked:
            if count is None:
                if not feed_names:
                    raise ValueError(
                        f"{mode.name} needs its count when feed is empty")
                count = int(feed_vals[0].shape[0])
            for n, v in zip(feed_names, feed_vals):
                if v.shape[0] != count:
                    raise ValueError(
                        f"feed {n!r} leading dim {v.shape[0]} != "
                        f"{mode.name}'s count {count}")
        c.count = count

        spans.phase("key")
        # fingerprint (content hash, memoized on the mutation stamp) rather
        # than id(program): a GC'd program's id can be reused by a new object,
        # which would alias cache entries
        key = (
            mode.call_mode,
            program.fingerprint(),
            bool(getattr(program, "_amp_bf16", False)),
            bool(getattr(program, "_is_test", False)),
            bool(self.check_nan_inf),
            self._scope_signature(program, feed_names, scope),
            count,
            tuple(feed_names),
            tuple((tuple(v.shape), str(v.dtype)) for v in feed_vals),
            tuple(fetch_names),
            layout,
        )
        c.entry, c.compiled_now = self._entry(
            spans, key, lambda: self._compile(
                mode, program, feed_names, fetch_names, scope, count, layout))
        return c

    def _entry(self, spans, key, compile_entry):
        """The one look-up-or-compile: (entry, whether this call compiled
        it).  Per-key lock and double check, so N threads missing on M
        signatures compile exactly M times; a hit or a miss is NOTED only
        once that check has resolved it (a race-losing thread must not
        count a spurious miss)."""
        mon = spans.mon
        entry = self._cache.get(key)
        if entry is None:
            spans.compiling()
            with self._compile_locks_guard:
                klock = self._compile_locks.setdefault(
                    key, _threading.Lock())
            with klock:
                entry = self._cache.get(key)
                if entry is None:
                    if mon:
                        self._note_cache_lookup(key, False)
                    try:
                        entry = compile_entry()
                    except Exception:
                        self._count_error(mon)
                        raise
                    self._cache[key] = entry
                    self._commit_stamp(key)
                    return entry, True
        if mon:
            self._note_cache_lookup(key, True)
        return entry, False

    def _args(self, c, rid):
        """The jitted closure's arguments: the feeds, the entry's state out
        of the scope (under a Layout, moved to where the executable wants
        it) and, where the program draws from it, the step key."""
        import jax

        entry, scope = c.entry, c.scope
        rw_vals = [scope.find_var(n) for n in entry.rw_state]
        ro_vals = [scope.find_var(n) for n in entry.ro_state]
        if entry.shardings is not None:
            def placed(n, v):
                want = entry.shardings[n]
                if v is None or getattr(v, "sharding", None) == want:
                    return v
                return jax.device_put(v, want)

            rw_vals = [placed(n, v) for n, v in zip(entry.rw_state, rw_vals)]
            ro_vals = [placed(n, v) for n, v in zip(entry.ro_state, ro_vals)]
        if not entry.needs_key:
            return c.feed_vals, rw_vals, ro_vals
        seed = c.program.random_seed or 0
        return (c.feed_vals, rw_vals, ro_vals,
                jax.random.fold_in(prng_key(seed), rid))

    def _execute(self, spans, c):
        """`gather`, `dispatch`, `writeback`, and the nan/inf check."""
        import contextlib

        entry, mon = c.entry, spans.mon
        # stateful entries serialize (donated rw buffers + scope
        # write-back must be atomic); stateless ones run concurrently
        # (waiting for the lock counts as `gather`)
        spans.phase("gather")
        with entry.run_lock if entry.run_lock is not None \
                else contextlib.nullcontext():
            args = self._args(c, spans.call)
            if not c.mode.stacked:
                # locate-mode capture (the numerics replay re-runs ONE
                # step) must happen HERE: the rw buffers are donated to
                # the executable below, so a post-hoc snapshot would read
                # deleted arrays
                self._maybe_capture_step(c.program, c.feed, c.fetch_names,
                                         entry, args[1], args[2], spans.call)
            spans.phase("dispatch")
            try:
                result = entry.jitted(*args)
            except Exception:
                self._count_error(mon)
                raise
            spans.phase("writeback")
            # Write state back BEFORE any nan/inf raise: the rw buffers
            # were donated to the executable, so skipping this would leave
            # the scope holding deleted arrays and poison every subsequent
            # run.
            for n, v in zip(entry.state_writes, result[1]):
                c.scope.set_var(n, v)
        if entry.nan_check_ops is not None:
            # the flags' three shapes: [ops] of one step, [steps, ops] of
            # a scan (an op is bad if ANY step was), and run_accumulated's
            # ([k, prefix ops], [suffix ops])
            flags = result[2]
            per_op = np.concatenate([
                f.all(axis=0) if f.ndim == 2 else f
                for f in map(np.asarray,
                             flags if isinstance(flags, tuple) else (flags,))
            ])
            bad = [d for d, ok in zip(entry.nan_check_ops, per_op) if not ok]
            if bad:
                self._count_error(mon)
                raise FloatingPointError(
                    "check_nan_inf: non-finite output from op(s):\n  "
                    + "\n  ".join(bad)
                )
        return result[0]

    def _compile(self, mode, program, feed_names, fetch_names, scope, count,
                 layout):
        """The one compile prologue: verify, split the state, build the
        mode's step closure over the shared pieces, jit it."""
        import jax

        self._maybe_verify(program, feed_names, fetch_names, scope)
        state_reads, state_writes = analyze_block_io(
            program.global_block(), feed_names, scope)
        write_set = set(state_writes)
        rw_state = [n for n in state_reads if n in write_set]
        ro_state = [n for n in state_reads if n not in write_set]
        trace = _StepTrace(program, feed_names, fetch_names, rw_state,
                           ro_state, state_writes, self.check_nan_inf,
                           layout.mesh if layout is not None else None)
        fn, needs_key, returns_flags = mode.build(trace, count)
        shardings, placement = None, {}
        if layout is not None:
            shardings = {n: layout.state_sharding(n, scope.find_var(n))
                         for n in state_reads + state_writes}
            placement = dict(
                in_shardings=(
                    [layout.feed_sharding(n, mode.stacked)
                     for n in feed_names],
                    [shardings[n] for n in rw_state],
                    [shardings[n] for n in ro_state],
                ) + ((None,) if needs_key else ()),
                out_shardings=(
                    [None] * len(fetch_names),
                    [shardings[n] for n in state_writes],
                ) + ((None,) if returns_flags else ()))
        jitted = jax.jit(fn, donate_argnums=(1,), **placement)
        return _CompiledEntry(
            jitted, rw_state, ro_state, state_writes, needs_key,
            nan_check_ops=trace.nan_check_ops if self.check_nan_inf else None,
            shardings=shardings, run_lock=self._stateful_lock,
        )

    def run_startup_missing(self, startup_program=None, scope=None):
        """Run only the startup ops whose outputs are NOT yet in the scope
        (init-on-demand).  Needed when graph surgery adds initialized state
        after the startup program already ran — e.g. slim pruning before
        optimizer.minimize(), whose learning-rate/accumulator initializers
        land in an already-executed startup program.  Returns the number
        of ops executed."""
        startup = startup_program or fw.default_startup_program()
        scope = scope or global_scope()
        src = startup.global_block()
        missing = [
            op for op in src.ops
            if any(scope.find_var(n) is None for n in op.output_arg_names())
        ]
        if not missing:
            return 0
        sub = fw.Program()
        blk = sub.global_block()
        names = set()
        for op in missing:
            names.update(op.input_arg_names())
            names.update(op.output_arg_names())
        for n in names:
            v = src._find_var_recursive(n)
            if v is not None:
                blk.create_var(name=n, shape=v.shape, dtype=v.dtype,
                               persistable=getattr(v, "persistable", True))
            else:
                blk.create_var(name=n, dtype="float32", persistable=True)
        for op in missing:
            blk.append_op(op.type, dict(op.inputs), dict(op.outputs),
                          dict(op.attrs))
        self.run(sub, scope=scope)
        return len(missing)

    # -- telemetry internals (callers gate on monitor.enabled()) ---------
    def _note_cache_lookup(self, key, hit: bool):
        """Count the executable-cache hit/miss and run the RECOMPILE
        DETECTOR.  A miss is a RECOMPILE iff this program-stamp compiled
        before (any key): a program whose keys keep missing — ragged feed
        shapes, churning fetch lists — counts one recompile per miss, and
        the cache-key delta vs the previous lookup is VLOG(1)'d naming
        the changed component.  A program's FIRST compile (startup, a new
        eval program mid-training) is never a recompile, no matter what
        the previous lookup was."""
        from .. import monitor
        from ..log import vlog, vlog_is_on

        monitor.counter(
            "executor.cache_hit" if hit else "executor.cache_miss").inc()
        if len(_KEY_PARTS) != len(key):
            # parallel-array drift guard: a cache-key component added
            # without updating _KEY_PARTS would silently mis-attribute
            # recompile causes (zip truncates); telemetry must not raise,
            # so warn and skip the diff instead
            from ..log import warning

            warning("recompile detector: %d key parts named but key has "
                    "%d components — update _KEY_PARTS in "
                    "core/executor.py", len(_KEY_PARTS), len(key))
            return
        stamp = self._stamp(key)
        with self._detector_lock:
            # per-(mode, program) history: diffing against another
            # program's (or call mode's) key would blame
            # program-stamp/call-mode and bury the component that
            # actually churned
            prev = self._last_key_by_program.get(stamp)
            self._last_key_by_program[stamp] = key
            # a fresh lookup supersedes this stamp's uncommitted pending
            # (the prior compile failed); OTHER stamps' pendings belong
            # to concurrent threads and stay
            self._pending_stamps.discard(stamp)
            if hit:
                return
            if stamp not in self._compiled_stamps:
                # first compile of this program — registered only once
                # the entry lands in the cache (_commit_stamp), so
                # retrying a failed compile is still not a recompile
                self._pending_stamps.add(stamp)
                return
        monitor.counter("executor.recompiles").inc()
        if prev is None:
            changed = ["(no prior lookup of this program)"]
        else:
            changed = [n for n, a, b in zip(_KEY_PARTS, prev, key)
                       if a != b] or ["(key unchanged; cache cleared)"]
        # the flight recorder keeps the recompile CAUSE history — after a
        # retrace storm kills a run, the dump names which key component
        # churned (tools/trace_report.py aggregates these)
        from ..monitor import flight as _flight

        _flight.record("executor.recompile", changed=changed)
        if vlog_is_on(1):
            vlog(1, "executor recompile: changed key component(s): %s",
                 ", ".join(changed))

    @staticmethod
    def _stamp(key):
        """(call-mode, program-stamp): what the detector keeps history by.
        Mode-qualified: run / run_steps / run_accumulated executables are
        distinct, so each mode gets its own first compile for free."""
        return key[:2]

    def _commit_stamp(self, key):
        """The compiled entry reached the cache: future misses of this
        program-stamp (in this call mode) are recompiles — even if the
        first execution later fails (e.g. check_nan_inf raises)."""
        stamp = self._stamp(key)
        with self._detector_lock:
            if stamp in self._pending_stamps:
                self._pending_stamps.discard(stamp)
                self._compiled_stamps.add(stamp)

    def _fetch(self, spans, c, fetches, return_numpy):
        """The `fetch` phase, and what the call's record
        (`_CallSpans._record`, at exit) will say.

        Under jax's async dispatch the Python call returns as soon as the
        computation is ENQUEUED, and the first np.asarray blocks until
        the device finishes and the result has come back — so the call
        decomposes into dispatch time (everything but `fetch`) and
        device-wait time (`fetch`, which bounds device execution from
        above): the pair the cost model's launch term is checked
        against."""
        spans.phase("fetch")
        fetch_names = c.fetch_names
        if c.counters:
            # the last len(counters) fetches are the program's device
            # counters: left on the device unless tracing is on
            cut = len(fetches) - len(c.counters)
            if spans.on:
                spans.counters = {
                    name: float(np.mean(np.asarray(v, np.float64)))
                    for name, v in zip(c.counters, fetches[cut:])}
            fetches, fetch_names = fetches[:cut], fetch_names[:cut]
        outs = ([np.asarray(v) for v in fetches] if return_numpy
                else list(fetches))
        user_outs = self._publish_numerics(c.program, fetch_names,
                                           c.user_fetch_n, outs)
        spans.phase(None)
        if spans.on:
            spans.finished(c.compiled_now, c.count, return_numpy,
                           c.feed_vals, outs if return_numpy else None)
        return user_outs

    def _count_error(self, mon):
        """Failed compile/execution: count it so cache_miss vs compiles
        divergence during an incident is explained by executor.errors."""
        if mon:
            import sys

            from .. import monitor
            from ..monitor import flight as _flight

            monitor.counter("executor.errors").inc()
            exc = sys.exc_info()[1]
            _flight.record(
                "executor.error",
                error=(f"{type(exc).__name__}: {str(exc)[:200]}"
                       if exc is not None else "unknown"))

    # -- internals -------------------------------------------------------
    def _maybe_verify(self, program, feed_names, fetch_names, scope):
        """Pre-compile static verification gate (FLAGS_verify_program).

        Runs the paddle_tpu.analysis program verifier BEFORE tracing so
        contract violations (use-before-def, shape mismatches, donation/
        fetch aliasing, unthreaded RNG ops) surface as named findings
        instead of late XLA trace errors — the TPU-side analogue of the
        reference's per-op RuntimeInferShape ENFORCE (operator.cc).

        Cost model: one flag read when off (zero hot-path cost); when on,
        one O(program) walk per (fingerprint, feeds, fetches) signature —
        compile-time only, memoized, so warm serving paths never pay it."""
        from ..flags import FLAGS

        if not FLAGS.verify_program:
            return
        # the scope signature is part of the key for the same reason it
        # is part of the compile-cache key: use-before-def / alias / dead
        # checks read the scope, so a recompile forced by a differently-
        # populated scope must re-verify, not hit the memo
        vkey = (program.fingerprint(),
                self._scope_signature(program, feed_names, scope),
                tuple(feed_names), tuple(fetch_names))
        if vkey in self._verified:
            return
        from ..analysis import verify_or_raise

        # serialized process-wide: the verifier's shape re-inference
        # mutates (then restores) the shared Program's Variable shapes
        with _VERIFY_MUTEX:
            if vkey in self._verified:
                return
            verify_or_raise(program, feed_names=feed_names,
                            fetch_names=fetch_names, scope=scope)
            self._verified.add(vkey)

    def _next_run_id(self) -> int:
        """Draw the next run-counter value under a lock: key-deriving
        programs fold this into their PRNG key, and concurrent serving
        threads must never fold in the same value twice.  A forced id
        (numerics failing-step replay) is consumed exactly once and
        does not advance the counter."""
        with self._counter_lock:
            if self._forced_run_id is not None:
                rid = self._forced_run_id
                self._forced_run_id = None
                return rid
            self._run_counter += 1
            return self._run_counter

    def _numerics_fetch(self, program, fetch_names):
        """Append the instrumented program's packed stats tensors to the
        fetch list (analysis/numerics.py) so the per-step health rows
        ride the existing device->host transfer.  Returns (user fetch
        count, possibly-extended fetch list).  Uninstrumented programs
        pay one getattr."""
        stats_vars = getattr(program, "_numerics_stats_vars", None)
        if not stats_vars:
            return len(fetch_names), fetch_names
        extra = [n for n in stats_vars if n not in fetch_names]
        return len(fetch_names), fetch_names + extra

    def _publish_numerics(self, program, fetch_names, user_n, outs):
        """Strip auto-appended stats tensors off the fetch results and
        hand them to the monitor tier.  Publication is exception-proof:
        telemetry must never fail the run."""
        if len(fetch_names) == user_n and not getattr(
                program, "_numerics_stats_vars", None):
            return outs
        try:
            from ..monitor import numerics as _mnum

            stats_vars = set(program._numerics_stats_vars)
            stats = {n: v for n, v in zip(fetch_names, outs)
                     if n in stats_vars}
            _mnum.publish_step_stats(program, stats)
        except Exception:  # pragma: no cover
            pass
        return outs[:user_n]

    def _maybe_capture_step(self, program, feed, fetch_names, entry,
                            rw_vals, ro_vals, rid):
        """FLAGS_check_numerics=locate: snapshot this step's replay
        context (feed, pre-donation rw-state copies, the PRNG run id)
        so a watchdog nan_loss trip can re-run the failing step
        bit-identically under full per-op instrumentation
        (monitor/numerics.locate_replay).  One flag read when off."""
        from ..flags import FLAGS

        if FLAGS.check_numerics != "locate":
            return
        try:
            import jax.numpy as jnp

            from ..monitor import numerics as _mnum

            if not _mnum.capture_armed():  # a replay run is in flight
                return
            state = {}
            for n, v in zip(entry.rw_state, rw_vals):
                if v is not None:
                    # rw buffers are donated: copy now or never
                    state[n] = jnp.array(v, copy=True)
            for n, v in zip(entry.ro_state, ro_vals):
                if v is not None:
                    state[n] = v
            _mnum.note_step_context({
                "program": program,
                "feed": dict(feed),
                "fetch": list(fetch_names),
                "state": state,
                "run_id": rid,
                "executor": self,
            })
        except Exception:  # pragma: no cover - capture must not fail a step
            pass

    def _scope_signature(self, program, feed_names, scope) -> frozenset:
        """Which program-referenced names resolve to a live scope var.

        analyze_block_io's rw/ro state split depends on scope contents at
        compile time, so the cache key must too — otherwise running the same
        program against a differently-populated scope reuses an executable
        with the wrong state split."""
        # The referenced-name walk is O(program size); memoize it on the
        # program fingerprint so the per-step cost is one scope probe per
        # distinct name, not a full block/op traversal.
        fp = program.fingerprint()
        names = self._ref_names_cache.get(fp)
        if names is None:
            seen = set()
            for blk in program.blocks:
                for op in blk.ops:
                    for n in op.input_arg_names() + op.output_arg_names():
                        if n:
                            seen.add(n)
            names = tuple(seen)
            self._ref_names_cache[fp] = names
        feed_set = set(feed_names)
        return frozenset(
            n
            for n in names
            if n not in feed_set and scope.find_var(n) is not None
        )

    def _to_device_array(self, program, name, value, layout=None,
                         stacked=False):
        """A feed on the device: where jax puts it by default, or under a
        Layout where that says (`stacked`: with a leading steps axis)."""
        import jax
        import jax.numpy as jnp

        v = program.global_block()._find_var_recursive(name)
        as_bf16 = v is not None and v.dtype == "bfloat16"
        if not isinstance(value, jax.Array):
            # (an already device-resident feed never round-trips to host)
            value = np.asarray(value)
            as_bf16 = as_bf16 and value.dtype != np.dtype("O")
            if as_bf16:
                value = value.astype(np.float32)
        if layout is not None:
            value = jax.device_put(value, layout.feed_sharding(name, stacked))
        elif not isinstance(value, jax.Array):
            value = jnp.asarray(value)
        # a declared bfloat16 feed dtype is honored whatever came in
        if as_bf16 and value.dtype != jnp.bfloat16:
            return value.astype(jnp.bfloat16)
        return value


def latest_jitted_entry(exe: "Executor") -> _CompiledEntry:
    """The most recently compiled cache entry, for the scripts and tests
    that lower an executed computation again by hand (tools/hlo_diag.py,
    bench.py memory_probe, the kernel-fusion tests); the package's own
    callers use `Executor.lower`.  Dict insertion order is compile order,
    so the last entry is the caller's most recent compile."""
    entries = list(exe._cache.values())
    if not entries:
        raise RuntimeError(
            "no compiled jitted entry in the executor cache — run the "
            "program once before AOT introspection")
    return entries[-1]


# ---------------------------------------------------------------------------
# feed/fetch helpers (reference: framework/feed_fetch_method.cc)
# ---------------------------------------------------------------------------


def as_numpy(value):
    if isinstance(value, (list, tuple)):
        return [as_numpy(v) for v in value]
    return np.asarray(value)
