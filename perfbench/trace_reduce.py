"""From the profiler's trace (.xplane.pb) to the numbers the per-layer
metrics read: the device's busy union and idle share, the idle gaps with
what the host was doing in each, the gap between `run_steps` calls, and the
operations that took most time.  Reads the file with JAX's own
`ProfileData`; the arithmetic below works on plain lists of
(name, start_ns, duration_ns), and perfbench/tests/test_trace_reduce.py
checks it on a recorded trace.

A TPU plane's `XLA Ops` line nests: a `while` (the scan of a `run_steps`
call) holds the operations of its body.  Busy time is the union of the
LEAF operations, so that a container does not hide the idle time inside
it."""

import re

HOST_SPAN_PREFIX = "pb."
WINDOW_SPAN = "pb.window"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
TOP_N = 10


def is_mosaic(text):
    """A TPU trace names an operation by its HLO text; a Pallas kernel is
    the custom call whose target is Mosaic's."""
    return MOSAIC_TARGET in text


def short_name(text):
    """`%fusion.7 = (f32[8,128]{...}, ...) fusion(...)` -> `fusion.7
    f32[8,128]`, with `mosaic` added for a Pallas kernel."""
    head, _, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    shape = _SHAPE.search(rest)
    if shape:
        name += " " + shape.group(0)
    if is_mosaic(text):
        name += " mosaic"
    return name[:120]


def events_of(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def read_planes(path):
    """{plane name: {line name: [(name, start_ns, dur_ns)]}}."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(events_of(line))
    return out


def leaves_only(events):
    """Drop every event that holds another event of its line that takes
    time: a `while` holds its body's operations.  (A zero-length marker
    inside an operation does not make that operation a container.)"""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    keep, stack = [], []
    for ev in evs:
        while stack and stack[-1][0] <= ev[1]:
            stack.pop()
        if ev[2] > 0:
            for s in stack:
                s[1] = True  # an open event holds this one: a container
        item = [ev[1] + ev[2], False, ev]
        if ev[2] > 0:
            stack.append(item)
        keep.append(item)
    return [s[2] for s in keep if not s[1]]


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps_of(busy, lo, hi):
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def attribute(gap, spans, programs=()):
    """What the host was doing in `gap`: the `pb.*` span that covers most
    of it, else `other`; with `:between_programs` where no program of the
    device (`programs`: merged intervals) was running at its middle."""
    best, name = 0.0, "other"
    for n, s, d in spans:
        cover = min(gap[1], s + d) - max(gap[0], s)
        if cover > best:
            best, name = cover, n[len(HOST_SPAN_PREFIX):]
    mid = (gap[0] + gap[1]) / 2
    if programs and not any(s <= mid < e for s, e in programs):
        name += ":between_programs"
    return name


def reduce_device(ops, modules, spans, window):
    """The numbers of one device.  `ops`: the XLA Ops line; `modules`: the
    XLA Modules line; `spans`: the host's `pb.*` spans other than the
    window; `window`: (start_ns, end_ns)."""
    lo, hi = window
    leaf = [e for e in leaves_only(ops) if e[1] + e[2] > lo and e[1] < hi]
    busy = clip(union([(s, s + d) for _, s, d in leaf]), lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    gaps = gaps_of(busy, lo, hi)

    by_name, mosaic_ns = {}, 0.0
    for n, s, d in leaf:
        t = min(s + d, hi) - max(s, lo)
        short = short_name(n)
        by_name[short] = by_name.get(short, 0.0) + t
        if is_mosaic(n):
            mosaic_ns += t

    programs = union([(s, s + d) for _, s, d in modules])
    named = [(attribute(g, spans, programs), g[1] - g[0]) for g in gaps]
    gap_by_span = {}
    for n, t in named:
        gap_by_span[n] = gap_by_span.get(n, 0.0) + t

    # the step modules: launches of the executable that does most work
    total = {}
    for n, s, d in modules:
        if s + d > lo and s < hi:
            total[n] = total.get(n, 0.0) + d
    between = []
    if total:
        step_mod = max(total, key=total.get)
        runs = sorted((s, s + d) for n, s, d in modules
                      if n == step_mod and s >= lo and s + d <= hi)
        for (_, e0), (s1, _) in zip(runs, runs[1:]):
            idle = sum(g1 - g0 for g0, g1 in clip(gaps, e0, s1))
            between.append(idle)
    return {
        "busy_ns": busy_ns, "window_ns": hi - lo, "mosaic_ns": mosaic_ns,
        "call_gap_ns": between, "ops": by_name, "gap_by_span": gap_by_span,
        "longest_gaps": sorted(named, key=lambda x: -x[1])[:TOP_N],
    }


def reduce_planes(planes, chips):
    host = []
    for name, lines in planes.items():
        if name.startswith("/host:CPU"):
            for evs in lines.values():
                host.extend(e for e in evs
                            if e[0].startswith(HOST_SPAN_PREFIX))
    windows = [e for e in host if e[0] == WINDOW_SPAN]
    if not windows:
        raise RuntimeError("the trace has no pb.window span")
    _, w0, wd = windows[0]
    spans = [e for e in host if e[0] != WINDOW_SPAN]
    devices = sorted(n for n in planes if n.startswith("/device:TPU:"))
    per_dev = []
    for name in devices[:chips]:
        lines = planes[name]
        ops = lines.get("XLA Ops", [])
        if not ops:
            continue
        per_dev.append(reduce_device(ops, lines.get("XLA Modules", []),
                                     spans, (w0, w0 + wd)))
    if not per_dev:
        raise RuntimeError(
            f"no device operations in the trace (planes: {sorted(planes)})")
    n = len(per_dev)
    ops = {}
    for d in per_dev:
        for k, t in d["ops"].items():
            ops[k] = ops.get(k, 0.0) + t / n
    call_gaps = [g for d in per_dev for g in d["call_gap_ns"]]
    first = per_dev[0]
    return {
        "busy_s": sum(d["busy_ns"] for d in per_dev) / n / 1e9,
        "window_s": wd / 1e9,
        "mosaic_s": sum(d["mosaic_ns"] for d in per_dev) / n / 1e9,
        "call_gap_ms": [g / 1e6 for g in call_gaps],
        "gap_by_span_s": {k: v / 1e9
                          for k, v in first["gap_by_span"].items()},
        "ops_s": {k: v / 1e9 for k, v in ops.items()},
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:TOP_N]],
            "idle_gaps": [[k, v / 1e9] for k, v in first["longest_gaps"]],
        },
    }


def reduce_file(path, chips=1):
    return reduce_planes(read_planes(path), chips)
