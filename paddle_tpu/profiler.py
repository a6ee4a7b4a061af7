"""Profiler (reference: python/paddle/fluid/profiler.py + platform/profiler.cc
host event tables + CUPTI device tracer → chrome trace).

TPU equivalent: jax.profiler captures XPlane traces viewable in
TensorBoard/Perfetto (the reference's tools/timeline.py chrome-trace role),
plus a lightweight host-side step timer table for the per-op summary role."""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Optional


class _HostEvents:
    def __init__(self):
        import threading

        self.totals = defaultdict(float)
        self.counts = defaultdict(int)
        self.maxes = defaultdict(float)
        # per-thread range stack: concurrent record_event() ranges on
        # different threads must not pop each other's (name, t0)
        self._local = threading.local()
        # add() is reached from serving/executor threads via
        # profiler.add_event; unlocked += would drop increments
        self._lock = threading.Lock()

    @property
    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def push(self, name):
        self._stack.append((name, time.perf_counter()))

    def pop(self):
        name, t0 = self._stack.pop()
        self.add(name, time.perf_counter() - t0)

    def add(self, name, dt):
        with self._lock:
            self.totals[name] += dt
            self.counts[name] += 1
            self.maxes[name] = max(self.maxes[name], dt)

    def summary(self, sorted_key="total"):
        rows = []
        with self._lock:  # add() on worker threads may insert new names
            names = list(self.totals)
            for name in names:
                total = self.totals[name]
                cnt = self.counts[name]
                rows.append(
                    (name, cnt, total, total / cnt, self.maxes[name]))
        key_idx = {"total": 2, "calls": 1, "ave": 3, "max": 4}.get(sorted_key, 2)
        rows.sort(key=lambda r: r[key_idx], reverse=True)
        return rows

    def reset(self):
        with self._lock:  # don't interleave with a worker thread's add()
            self.totals.clear()
            self.counts.clear()
            self.maxes.clear()


_events = _HostEvents()
_profiling = False


@contextlib.contextmanager
def record_event(name):
    """RAII range (reference: platform/profiler.h:72 RecordEvent)."""
    _events.push(name)
    try:
        yield
    finally:
        _events.pop()


def add_event(name, seconds: float):
    """Record an already-measured host range into the event table — used
    by instrumentation that owns its timer (the executor's monitored
    run/compile paths), so the profiler summary covers the runtime hot
    paths without nesting context managers through their control flow."""
    _events.add(name, seconds)


def host_events(sorted_key="total"):
    """Rows of (name, calls, total_s, avg_s, max_s) from the host event
    table, without printing (stop_profiler's table, accessor form)."""
    return _events.summary(sorted_key)


# clock bridge for the unified timeline: xplane event timestamps are
# relative to the trace-session start, flight-recorder events are epoch
# seconds — stamping time.time() at start_trace lets the export put both
# on one axis (skew = the microseconds start_trace takes to return)
_trace_start_epoch: Optional[float] = None
_trace_dir: Optional[str] = None


def start_profiler(state="All", trace_dir: Optional[str] = None):
    global _profiling, _trace_start_epoch, _trace_dir
    _profiling = True
    _events.reset()
    if trace_dir:
        import jax

        _trace_dir = trace_dir
        _trace_start_epoch = time.time()
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key="total", profile_path: Optional[str] = None,
                  tracing: bool = False):
    global _profiling
    _profiling = False
    if tracing:
        import jax

        jax.profiler.stop_trace()
    rows = _events.summary(sorted_key)
    lines = ["Event                          Calls     Total(s)    Ave(s)      Max(s)"]
    for name, cnt, total, ave, mx in rows:
        lines.append(f"{name:<30} {cnt:>6} {total:>12.6f} {ave:>10.6f} {mx:>10.6f}")
    report = "\n".join(lines)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(report)
    print(report)
    return rows


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             trace_dir: Optional[str] = None):
    """reference: fluid.profiler.profiler contextmanager."""
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path, tracing=trace_dir is not None)


# ---------------------------------------------------------------------------
# Per-op DEVICE cost attribution (reference: platform/device_tracer.cc CUPTI
# kernel records correlated to host ranges; here two TPU-native sources:
# XLA's compiled cost analysis and captured xplane traces)
# ---------------------------------------------------------------------------


def cost_analysis(program, feed, fetch_list=None, scope=None):
    """Static device cost estimate from XLA's compiled cost model
    ({'flops': .., 'bytes accessed': .., 'utilization...': ..}) for one
    executor call over `program` — the reference's per-op FLOP accounting
    role (platform/profiler per-op tables), exact and without executing."""
    from .core import executor as ex

    lowered = ex.Executor().lower(program, feed, fetch_list, scope)
    cost = lowered.compile().cost_analysis()
    # jax returns one properties dict per partition on some versions and a
    # bare dict on others; normalize to ONE dict (numeric keys summed)
    if isinstance(cost, (list, tuple)):
        merged = {}
        for entry_props in cost:
            for k, v in (entry_props or {}).items():
                if isinstance(v, (int, float)):
                    merged[k] = merged.get(k, 0.0) + v
        cost = merged
    return cost


def _leaf_events(events):
    """The events of one line that hold no other event of it: a `while`
    (the scan of a run_steps call) holds its body's operations, and
    counting both would count the body twice."""
    evs = sorted(events, key=lambda e: (e.offset_ps, -e.duration_ps))
    keep, stack = [], []
    for ev in evs:
        while stack and stack[-1][0] <= ev.offset_ps:
            stack.pop()
        if ev.duration_ps > 0:
            for open_ev in stack:
                open_ev[1] = True
        item = [ev.offset_ps + ev.duration_ps, False, ev]
        if ev.duration_ps > 0:
            stack.append(item)
        keep.append(item)
    return [ev for _, holds, ev in keep if not holds]


NO_SCOPE = "(no scope)"


def op_scope(tf_op: str) -> str:
    """The op-type scope of a device event, from its `tf_op` stat (the
    HLO op_name: `jit(scan_fn)/while/body/closed_call/layer_norm_grad/
    transpose(jvp())/mul:`).  trace_block lowers each op under
    `jax.named_scope(op.type)`, so the scope is the first component
    under jax's own (`jit(..)`, `while/body`, `closed_call`), if that is
    an op type and not the path's leaf (the leaf is a jax primitive,
    which may share a name with an op: `mul`).  NO_SCOPE where there is
    none: parameters, the scan's own plumbing, fusions XLA made up."""
    from .core import registry

    parts = str(tf_op).rsplit(":", 1)[0].split("/")
    i = 0
    while i < len(parts) - 1:
        c, nxt = parts[i], parts[i + 1]
        if c.startswith(("jit(", "pjit(")) or c in ("closed_call",
                                                    "checkpoint"):
            i += 1
        elif (c == "while" and nxt in ("body", "cond")) or (
                c == "cond" and nxt.startswith("branch")):
            i += 2
        else:
            base = c[:-5] if c.endswith("_grad") else c
            return c if registry.lookup(base) is not None else NO_SCOPE
    return NO_SCOPE


def kernel_name(hlo_text: str):
    """The Pallas kernel's name if the event is one (`pallas_call(name=)`
    as the trace prints it: `%jvp_flash_bthd_fwd_.95 = .. custom-call(..),
    custom_call_target="tpu_custom_call"` -> `jvp_flash_bthd_fwd_`), else
    None."""
    if 'custom_call_target="tpu_custom_call"' not in hlo_text:
        return None
    head = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    stem, _, num = head.rpartition(".")
    return stem if stem and num.isdigit() else head


def xplane_op_table(trace_dir: str, top_k: Optional[int] = 30,
                    by: str = "group"):
    """Aggregate per-op device time from a jax.profiler trace directory
    (the reference's profiler table role, device-side).  Returns rows of
    (name, total_seconds) sorted descending.  `by` chooses the grouping:

      "group"   op names collapsed to their fusion-group prefix (every
                event of the `XLA Ops` lines, containers included)
      "scope"   the op TYPE each leaf operation was lowered from
                (`op_scope`); the NO_SCOPE row is the share no op owns
      "kernel"  Pallas kernels by `pallas_call(name=)` (leaf operations)

    Requires a trace captured with start_profiler(trace_dir=...) around
    device work.  Decodes xplane.pb natively (paddle_tpu.xplane) — no
    TensorFlow proto dependency."""
    from collections import defaultdict

    from . import xplane as _xp

    if by not in ("group", "scope", "kernel"):
        raise ValueError(f"xplane_op_table: unknown grouping {by!r}")
    files = _xp.find_xplane_files(trace_dir)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    agg = defaultdict(float)
    for path in files:
        space = _xp.parse_xspace_file(path)
        for plane in space.planes:
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            for line in plane.lines:
                if "Ops" not in line.name or "Async" in line.name:
                    continue
                if by == "group":
                    for ev in line.events:
                        agg[ev.name.split(".")[0]] += ev.duration_ps / 1e12
                    continue
                for ev in _leaf_events(line.events):
                    if by == "scope":
                        key = op_scope(ev.meta_stats.get("tf_op", ""))
                    else:
                        key = kernel_name(ev.name)
                        if key is None:
                            continue
                    agg[key] += ev.duration_ps / 1e12
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:top_k]
    return rows


def print_op_table(trace_dir: str, top_k: int = 30, by: str = "group"):
    """Print the table with each row's share of the grouping's total;
    returns the `top_k` rows."""
    every = xplane_op_table(trace_dir, None, by)
    total = sum(t for _, t in every)
    rows = every[:top_k]
    head = {"group": "Device op group", "scope": "Op type (scope)",
            "kernel": "Pallas kernel"}[by]
    lines = [f"{head:<40} {'Total(s)':>10} {'Share':>7}"]
    for name, t in rows:
        lines.append(f"{name:<40} {t:>10.6f} "
                     f"{100 * t / total if total else 0:>6.1f}%")
    report = "\n".join(lines)
    print(report)
    return rows


def _xplane_chrome_events(trace_dir: str, max_events: int,
                          first_pid: int = 100):
    """Chrome-trace events (ts in trace-relative microseconds) for every
    xplane plane under `trace_dir`: one pid per plane (per-device tracks),
    one tid per line."""
    from . import xplane as _xp

    files = _xp.find_xplane_files(trace_dir)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    events = []
    n_slices = 0
    pid = first_pid - 1
    for path in files:
        space = _xp.parse_xspace_file(path)
        for plane in space.planes:
            if not plane.lines:
                continue
            pid += 1
            events.append({
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": plane.name,
                         "source": "xplane",
                         "device": _xp.is_device_plane(plane.name)}})
            for tid, line in enumerate(plane.lines):
                events.append({
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": line.name}})
                base = line.timestamp_ns
                for ev in line.events:
                    if n_slices >= max_events:
                        break
                    events.append({
                        "name": ev.name[:96],
                        "ph": "X",
                        "pid": pid,
                        "tid": tid,
                        "ts": (base + ev.offset_ps / 1000) / 1000.0,
                        "dur": ev.duration_ps / 1e6,
                    })
                    n_slices += 1
    return events


def export_chrome_trace(trace_dir: str, out_path: str, max_events=50000):
    """Convert a captured xplane trace to chrome://tracing JSON (the
    reference's tools/timeline.py role over its protobuf profile).  Each
    plane becomes a pid, each line a tid; op events carry their XLA
    names.  Decoded natively — no TensorFlow proto dependency."""
    import json as _json

    events = _xplane_chrome_events(trace_dir, max_events)
    with open(out_path, "w") as f:
        _json.dump({"traceEvents": events}, f)
    return len(events)


# ---------------------------------------------------------------------------
# Unified host+device timeline (tentpole of the flight-recorder PR): ONE
# chrome-trace file holding the flight recorder's host spans (executor
# compile/run, feed stalls, steps, collectives) and the XLA xplane device
# ops, on a shared clock.  The reference needed two tools (timeline.py for
# CUPTI + the host event table print); here one file answers "was the chip
# idle while the host stalled?" by inspection.
# ---------------------------------------------------------------------------

# flight-event kind prefix -> stable tid on the host process (chrome sorts
# tids numerically; keep executor on top).  "trace" carries the request-
# scoped serving spans (monitor/tracing.py trace.span / trace.request) —
# their own track next to the executor spans and xplane device ops, all
# on the one bridged clock.
_HOST_TIDS = (
    ("executor", 0), ("step", 1), ("feed", 2), ("collective", 3),
    ("trace", 4),
)


def _host_tid(kind: str):
    for prefix, tid in _HOST_TIDS:
        if kind == prefix or kind.startswith(prefix + "."):
            return tid
    return len(_HOST_TIDS)  # misc


def _flight_chrome_events(flight_events, trace_start_epoch, pid=1):
    """Flight-recorder events as chrome slices/instants, on the xplane
    clock (trace-relative microseconds)."""
    events = [{
        "name": "process_name", "ph": "M", "pid": pid,
        "args": {"name": "paddle_tpu host (flight)", "source": "flight"}}]
    for prefix, tid in _HOST_TIDS + (("misc", len(_HOST_TIDS)),):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": f"host:{prefix}"}})
    for ev in flight_events:
        kind = ev.get("kind", "?")
        tid = _host_tid(kind)
        args = {k: v for k, v in ev.items()
                if k not in ("kind", "t0", "dur", "seq", "ts")
                and isinstance(v, (int, float, str, bool))}
        # request-trace events carry their span/request identity — name
        # the chrome slice after it, not the generic event kind
        name = kind
        if kind == "trace.span":
            name = f"trace:{ev.get('name', 'span')}"
        elif kind == "trace.request":
            name = f"request:{ev.get('model', '?')}"
        if "t0" in ev and "dur" in ev:  # span
            events.append({
                "name": name, "ph": "X", "pid": pid, "tid": tid,
                "ts": (ev["t0"] - trace_start_epoch) * 1e6,
                "dur": float(ev["dur"]) * 1e6,
                "args": args,
            })
        else:  # instant (recompile, watchdog trip, signal, ...)
            events.append({
                "name": name, "ph": "i", "s": "p", "pid": pid, "tid": tid,
                "ts": (ev.get("ts", trace_start_epoch)
                       - trace_start_epoch) * 1e6,
                "args": args,
            })
    return events


def export_unified_chrome_trace(out_path: str,
                                trace_dir: Optional[str] = None,
                                flight=None,
                                trace_start_epoch: Optional[float] = None,
                                max_events: int = 50000):
    """Merge host flight spans + xplane device ops into one chrome trace.

    trace_dir defaults to the directory of the last start_profiler
    (trace_dir=...) call; trace_start_epoch to the time.time() stamped
    there (the clock bridge).  `flight` defaults to the process flight
    recorder.  Device planes keep one pid per plane — per-device tracks.
    The flight header + raw events are embedded under the top-level
    "flight" key (chrome ignores it; tools/trace_report.py reads it)."""
    import json as _json

    from .monitor import flight as _flight

    rec = flight if flight is not None else _flight.default_recorder()
    trace_dir = trace_dir if trace_dir is not None else _trace_dir
    epoch = (trace_start_epoch if trace_start_epoch is not None
             else _trace_start_epoch)
    fl_events = rec.events()
    if epoch is None:
        # no trace session: host-only timeline anchored at the first event
        spans = [e["t0"] for e in fl_events if "t0" in e]
        epoch = min(spans) if spans else (
            min((e.get("ts", 0.0) for e in fl_events), default=0.0))

    events = _flight_chrome_events(fl_events, epoch)
    if trace_dir:
        events += _xplane_chrome_events(trace_dir, max_events)
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ns",
        "flight": {
            "header": rec.header("unified_trace"),
            "trace_start_epoch": epoch,
            "events": fl_events,
        },
    }
    from .monitor.registry import _json_safe

    with open(out_path, "w") as f:
        _json.dump(_json_safe(doc), f)
    return len(events)
