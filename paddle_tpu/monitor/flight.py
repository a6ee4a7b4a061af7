"""Flight recorder: a bounded in-memory ring of structured runtime events
that survives to disk when the run does not.

Reference role: the pieces of the reference that *notice* a dying run —
check_nan_inf's offending-op naming (operator.cc:943), the profiler's host
event tables (platform/profiler.cc), and the master service that detects
dead/stuck workers (go/master/service.go:313) — none of which left an
artifact when a multi-hour run crashed.  Here every subsystem that already
emits FLAGS.monitor metrics (executor compile/run/recompile, data-feed
stalls, trace-time collectives, StepMonitor steps) also appends one
structured event to a process-wide ring buffer, and the ring is dumped as
JSONL to FLAGS.flight_dir:

  * on interpreter crash (sys.excepthook chain),
  * at interpreter exit (atexit; trigger "atexit", cheap and idempotent),
  * on SIGTERM / SIGUSR1 (SIGUSR1 dumps and continues — a live-run probe;
    SIGTERM dumps and re-raises so the exit code stays 143),
  * on watchdog trip (monitor/watchdog.py calls dump()).

Every dump starts with one header line: config/flags snapshot, argv, jax
backend, the trigger, and the LAST COMPLETED STEP (maintained by
StepMonitor via note_step) — the first three questions of any postmortem.

Gating matches the PR-1 registry: `record()` is a no-op unless
FLAGS.monitor is on (call sites pay one flag read); the module holds no
threads and opens no files until install()/dump().

The module also owns the executed-op set for the op-contract gate
(FLAGS.record_lowered_ops): trace-time recording of every op type the
executor lowers, exposed via lowered_op_types(); and the process-wide
totals of jax's compile phases inside Executor calls (compile_phases()),
which are kept whether or not FLAGS.monitor is set.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from .registry import _json_safe, enabled

# Thread-local execution-context tag: a subsystem driving the executor from
# its own threads (the serving tier's dynamic batcher) wraps its calls in
# `with context("serving/<model>")` and every flight event recorded inside
# — executor compiles, RECOMPILE-CAUSE events, errors — carries a `ctx`
# field naming the originator.  A retrace storm in /flight is then
# attributable to the serving tier (vs. a training loop) without guessing.
_context = threading.local()


import contextlib as _contextlib


@_contextlib.contextmanager
def context(tag: str):
    """Tag every flight event recorded by this thread inside the block."""
    prev = getattr(_context, "tag", None)
    _context.tag = tag
    try:
        yield
    finally:
        _context.tag = prev


def current_context() -> Optional[str]:
    return getattr(_context, "tag", None)


class FlightRecorder:
    """Thread-safe bounded ring of event dicts + JSONL dump."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            from ..flags import FLAGS

            capacity = FLAGS.flight_events
        # RLock, not Lock: the SIGTERM/SIGUSR1 handlers run on the main
        # thread and call record()/dump(); if the signal lands while that
        # same thread is inside record() a plain lock would deadlock the
        # dying process instead of dumping
        self._lock = threading.RLock()
        self._ring: "collections.deque[dict]" = collections.deque(
            maxlen=max(16, int(capacity)))
        self._seq = itertools.count(1)
        self._dropped = 0
        # postmortem header state (last completed step, last loss), kept
        # outside the ring so eviction can't lose it
        self.last_step: Optional[int] = None
        self.last_loss: Optional[float] = None
        self.last_step_ts: Optional[float] = None

    def record(self, kind: str, **fields) -> None:
        """Append one structured event.  `t0`/`dur` (epoch seconds /
        seconds) mark span events — the unified-timeline export renders
        those as chrome-trace slices; everything else is an instant."""
        ev = {"seq": next(self._seq), "ts": round(time.time(), 6),
              "kind": kind}
        tag = getattr(_context, "tag", None)
        if tag is not None and "ctx" not in fields:
            ev["ctx"] = tag
        ev.update(fields)
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(ev)

    def note_step(self, step: int, loss: Optional[float] = None) -> None:
        """StepMonitor marks a completed step (header state for dumps)."""
        self.last_step = step
        if loss is not None:
            self.last_loss = float(loss)
        self.last_step_ts = time.time()

    def events(self, n: Optional[int] = None,
               kind: Optional[str] = None) -> List[dict]:
        with self._lock:
            evs = list(self._ring)
        if kind is not None:
            evs = [e for e in evs if e["kind"] == kind
                   or e["kind"].startswith(kind + ".")]
        if n is not None:
            evs = evs[-n:]
        return evs

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._dropped = 0
        self.last_step = self.last_loss = self.last_step_ts = None

    # -- dumping ---------------------------------------------------------
    def header(self, trigger: str, extra: Optional[dict] = None) -> dict:
        """The postmortem header: what run, how configured, why dumped."""
        import sys

        from ..flags import FLAGS

        flag_defs = object.__getattribute__(FLAGS, "_defs")
        hdr = {
            "kind": "flight.header",
            "ts": round(time.time(), 6),
            "trigger": trigger,
            "pid": os.getpid(),
            "argv": list(sys.argv),
            "last_step": self.last_step,
            "last_loss": self.last_loss,
            "last_step_ts": self.last_step_ts,
            "events_dropped": self._dropped,
            "flags": {n: getattr(FLAGS, n) for n in sorted(flag_defs)},
        }
        try:  # backend info must never block a crash dump
            import jax

            hdr["jax_backend"] = jax.default_backend()
            hdr["jax_device_count"] = jax.device_count()
        except Exception:
            pass
        # header providers: subsystems with in-flight state worth a
        # postmortem line (monitor/tracing.py reports OPEN request traces
        # — what the process was serving when it died).  Best-effort: a
        # broken provider must not block a crash dump.
        for cb in list(_header_providers):
            try:
                more = cb()
                if more:
                    hdr.update(more)
            except Exception:
                pass
        if extra:
            hdr.update(extra)
        return hdr

    def dump(self, path: Optional[str] = None, trigger: str = "manual",
             extra: Optional[dict] = None) -> Optional[str]:
        """Write header + every ring event as JSONL.  `path` defaults to
        FLAGS.flight_dir/flight-<pid>-<trigger>.jsonl; returns the path
        written, or None when no destination is configured.  Never raises
        (a crash dump must not mask the crash).

        Dying-run triggers (EMERGENCY_TRIGGERS) first run the registered
        emergency callbacks — e.g. io.CheckpointManager's best-effort
        final save — BEFORE the record is written, so the events those
        callbacks emit land in the dump."""
        if trigger in EMERGENCY_TRIGGERS:
            _run_emergency(trigger)
        try:
            if path is None:
                from ..flags import FLAGS

                d = FLAGS.flight_dir
                if not d:
                    return None
                os.makedirs(d, exist_ok=True)
                path = os.path.join(
                    d, f"flight-{os.getpid()}-{trigger}.jsonl")
            with self._lock:
                evs = list(self._ring)
            with open(path, "w") as f:
                f.write(json.dumps(_json_safe(
                    self.header(trigger, extra))) + "\n")
                for ev in evs:
                    f.write(json.dumps(_json_safe(ev)) + "\n")
            return path
        except Exception:
            return None


_default = FlightRecorder()


def default_recorder() -> FlightRecorder:
    return _default


def record(kind: str, **fields) -> None:
    """Module-level append, gated on FLAGS.monitor (one flag read when
    telemetry is off — same contract as the PR-1 registry helpers)."""
    if enabled():
        _default.record(kind, **fields)


def note_step(step: int, loss: Optional[float] = None) -> None:
    if enabled():
        _default.note_step(step, loss)


def dump(path: Optional[str] = None, trigger: str = "manual",
         extra: Optional[dict] = None) -> Optional[str]:
    return _default.dump(path, trigger, extra)


# ---------------------------------------------------------------------------
# Header providers (in-flight state for the dump header)
# ---------------------------------------------------------------------------

_header_providers: List = []


def add_header_provider(cb) -> None:
    """Register `cb() -> dict` to merge into every dump header — the hook
    tracing uses so crash dumps carry the requests that were IN FLIGHT
    when the process died.  Idempotent per callback object."""
    if cb not in _header_providers:
        _header_providers.append(cb)


def remove_header_provider(cb) -> None:
    try:
        _header_providers.remove(cb)
    except ValueError:
        pass


# ---------------------------------------------------------------------------
# Emergency callbacks (preemption-safe saves ride the dump signal path)
# ---------------------------------------------------------------------------

# dump() triggers that mean "this run is dying" (vs. probes/normal exit):
# only these fire the emergency callbacks.
EMERGENCY_TRIGGERS = ("sigterm", "watchdog", "crash")

_emergency_cbs: List = []


def on_emergency(cb) -> None:
    """Register `cb(trigger)` to run when a dying-run dump fires (SIGTERM,
    watchdog trip, crash) — io.CheckpointManager.install_emergency() hangs
    its best-effort final save here.  Idempotent per callback object."""
    if cb not in _emergency_cbs:
        _emergency_cbs.append(cb)


def remove_emergency(cb) -> None:
    try:
        _emergency_cbs.remove(cb)
    except ValueError:
        pass


def _run_emergency(trigger: str) -> None:
    """Best-effort, exception-proof: the dying path must reach the dump
    whatever a callback does."""
    for cb in list(_emergency_cbs):
        try:
            cb(trigger)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Crash / signal / exit hooks
# ---------------------------------------------------------------------------

_installed = False
_prev_excepthook = None


def install(signals: bool = True) -> None:
    """Arm the black box: dump on unhandled exception, at exit, and on
    SIGTERM/SIGUSR1.  Idempotent; signal handlers are only installed from
    the main thread (signal module restriction).  A dead run then leaves
    flight-<pid>-<trigger>.jsonl under FLAGS.flight_dir instead of
    silence."""
    global _installed, _prev_excepthook
    if _installed:
        return
    _installed = True
    import atexit
    import sys

    _prev_excepthook = sys.excepthook

    def _excepthook(tp, val, tb):
        _default.record("crash", error=f"{tp.__name__}: {val}")
        _default.dump(trigger="crash",
                      extra={"error": f"{tp.__name__}: {val}"})
        (_prev_excepthook or sys.__excepthook__)(tp, val, tb)

    sys.excepthook = _excepthook
    atexit.register(lambda: _default.dump(trigger="atexit"))

    if not signals:
        return
    try:
        import signal

        def _on_sigterm(signum, frame):
            _default.record("signal", signum=int(signum), name="SIGTERM")
            _default.dump(trigger="sigterm")
            # restore + re-raise so the exit code is the conventional 143
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)

        def _on_sigusr1(signum, frame):
            _default.record("signal", signum=int(signum), name="SIGUSR1")
            _default.dump(trigger="sigusr1")  # probe: dump and continue

        signal.signal(signal.SIGTERM, _on_sigterm)
        if hasattr(signal, "SIGUSR1"):
            signal.signal(signal.SIGUSR1, _on_sigusr1)
    except (ValueError, OSError):
        # not the main thread / restricted env: excepthook+atexit still armed
        pass


# ---------------------------------------------------------------------------
# Executed-op recording (FLAGS.record_lowered_ops — the op-contract gate)
# ---------------------------------------------------------------------------

_lowered_ops: set = set()
_lowered_lock = threading.Lock()


def note_lowered_ops(op_types) -> None:
    """Called by the executor trace (core/executor.py trace_block) and the
    imperative dispatcher for every op they lower, when
    FLAGS.record_lowered_ops is on.  Accumulates the process-wide executed
    set (always) and appends a flight event naming NEW types (only while
    FLAGS.monitor is on, like every other call site)."""
    with _lowered_lock:
        new = [t for t in op_types if t not in _lowered_ops]
        _lowered_ops.update(new)
    if new and enabled():
        _default.record("ops.lowered", new_types=sorted(set(new)))


def lowered_op_types() -> frozenset:
    """Every op type lowered in this process (under the recording flag)."""
    with _lowered_lock:
        return frozenset(_lowered_ops)


def reset_lowered_ops() -> None:
    with _lowered_lock:
        _lowered_ops.clear()


# ---------------------------------------------------------------------------
# Compile phases (jax.monitoring events inside Executor calls)
# ---------------------------------------------------------------------------

# jax's own event -> the name it is totalled under.  The backend event
# wraps jax's persistent-cache lookup, so `backend_s` CONTAINS
# `cache_load_s` on a cache hit; the two are not to be added.
_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
_COMPILE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# counted by the program itself while an Executor call traces its block
# (note_compile_count): grad ops lowered by a registered grad op fed from
# its forward's residuals, and by the generic vjp of the forward lowering;
# fused_qkv_attention sites whose grad op was fed Q, K, V, Ctx, Lse from
# the forward (ops/fused_ops.py): the bthd backward kernels between XLA
# projection dots, nothing recomputed; and the (query tile, key tile)
# pairs a head and sequence that the masked flash forward walks visit, of
# the pairs of the whole square, summed over the sites traced
# (kernels/attention.py `bd_tiles_visited`); and the short_conv sites
# lowered to the Pallas kernel pair / to the XLA composition
# (kernels/short_conv.py)
_TRACE_COUNTS = ("grad_direct", "grad_generic", "qkv_bwd_composed",
                 "attn_tiles_visited", "attn_tiles_total",
                 "short_conv_sites_kernel", "short_conv_sites_xla")
_compile_totals: Dict[str, float] = dict.fromkeys(
    list(_COMPILE_DURATIONS.values()) + list(_COMPILE_COUNTS.values())
    + list(_TRACE_COUNTS), 0)
_compile_lock = threading.Lock()
_compile_listening = False
# per thread: how deep inside Executor calls it is, and for each duration
# the (end, seconds) of the events already counted in the outermost call
_in_executor = threading.local()


def _on_compile_duration(event: str, duration_secs: float, **_kw) -> None:
    name = _COMPILE_DURATIONS.get(event)
    if name is None or not getattr(_in_executor, "depth", 0):
        return
    # a jit traced while another is being traced reports first and lies
    # inside the outer one's duration: take it back out when the outer
    # one arrives, so that nested traces are counted once
    now = time.monotonic()
    counted = _in_executor.counted.setdefault(name, [])
    nested = 0.0
    while counted and counted[-1][0] >= now - duration_secs:
        nested += counted.pop()[1]
    counted.append((now, duration_secs))
    with _compile_lock:
        _compile_totals[name] += duration_secs - nested


def _on_compile_event(event: str, **_kw) -> None:
    name = _COMPILE_COUNTS.get(event)
    if name is not None and getattr(_in_executor, "depth", 0):
        with _compile_lock:
            _compile_totals[name] += 1


def note_compile_count(name: str, amount: int = 1) -> None:
    """`amount` more of `name` (one of _TRACE_COUNTS), inside an Executor
    call only, like every other compile total."""
    if getattr(_in_executor, "depth", 0):
        with _compile_lock:
            _compile_totals[name] += amount


def listen_for_compile_phases() -> None:
    """Register the two jax.monitoring listeners, once per process (the
    first Executor does).  They run once per compilation, never per
    step, and count whether or not FLAGS.monitor is set: set-up happens
    before any profiler session exists."""
    global _compile_listening
    with _compile_lock:
        if _compile_listening:
            return
        _compile_listening = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_duration)
    jax.monitoring.register_event_listener(_on_compile_event)


@_contextlib.contextmanager
def executor_call():
    """Marks this thread as inside an Executor call, so that only the
    program's own compilations are totalled: not a benchmark's jits nor
    a reference that runs in the same process."""
    depth = getattr(_in_executor, "depth", 0)
    if not depth:
        _in_executor.counted = {}
    _in_executor.depth = depth + 1
    try:
        yield
    finally:
        _in_executor.depth = depth


def compile_phases() -> Dict[str, float]:
    """Process-wide totals of jax's compile phases inside Executor calls:
    seconds `trace_s` (jaxpr trace: the op loop runs here), `lower_s`
    (jaxpr -> MLIR), `backend_s` (XLA compile, or the persistent cache's
    load where it hit: `cache_load_s` is that part), the counts
    `cache_hits` / `cache_misses` of the persistent cache, and the counts
    `grad_direct` / `grad_generic` of grad ops lowered from their
    forward's residuals / by the generic vjp (core/registry.py) and
    `qkv_bwd_composed` of fused_qkv_attention grad ops fed q, k, v from
    their forward, and `attn_tiles_visited` / `attn_tiles_total` of the
    masked attention walks (their ratio is the share of the score square
    the kernels visit), and `short_conv_sites_kernel` /
    `short_conv_sites_xla` of the short_conv ops lowered to the Pallas
    kernels / to the XLA composition."""
    with _compile_lock:
        return dict(_compile_totals)


def device_counter(program, name: str, var) -> None:
    """Declare `var` (a variable of `program`, a scalar a step) a device
    counter: the compiled steps return it with every call, whichever of
    `Executor.run` / `run_steps` / `run_accumulated` runs them, and while
    `monitor.spans_on()` holds the call's `executor.<mode>` flight event
    carries its mean over the call's steps under `counters[name]`.  With
    tracing off the value stays on the device and nothing reads it."""
    counters = getattr(program, "_device_counters", None)
    if counters is None:
        counters = program._device_counters = {}
    counters[name] = getattr(var, "name", var)
    program._mod_count += 1
