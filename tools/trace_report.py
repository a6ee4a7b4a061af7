#!/usr/bin/env python
"""Summarize a unified chrome trace (profiler.export_unified_chrome_trace)
— the text-report half of the timeline tentpole:

  * top device ops by total time (per-device xplane tracks; host XLA
    lines when the trace has no device plane, e.g. the CPU mesh),
  * compile vs run vs feed-stall host time (the "where did the wall
    clock go" breakdown, from the flight spans),
  * recompile causes (which cache-key component churned, aggregated),
  * a "Requests" section from the request-scoped traces
    (monitor/tracing.py trace.request events): slowest traces with their
    latency decomposition, and the padding-waste top-K (rows padded vs
    real — wasted compute attributed per request),
  * watchdog trips and the last completed step (from the embedded
    flight header).

Usage: python tools/trace_report.py merged_trace.json [--top 20]

Also accepts a raw jax trace DIRECTORY (the start_profiler trace_dir):
then only the device-op table is available.  Plain stdlib — the report
must be runnable on the barest postmortem host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict


def load_trace(path: str) -> dict:
    if os.path.isdir(path):
        # raw jax trace dir: build the xplane-only event list in-process
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), ".."))
        from paddle_tpu.profiler import _xplane_chrome_events

        return {"traceEvents": _xplane_chrome_events(path, 500000)}
    with open(path) as f:
        return json.load(f)


def _index_processes(events):
    """pid -> {"name": ..., "device": bool, "source": ...}."""
    procs = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            procs[ev["pid"]] = dict(ev.get("args", {}))
    return procs


def top_ops(doc: dict, k: int = 20):
    """(rows, scope): rows of (op_name, total_s, calls) over device-plane
    events; falls back to host XLA runtime lines on device-less traces."""
    events = doc.get("traceEvents", [])
    procs = _index_processes(events)
    device_pids = {p for p, a in procs.items() if a.get("device")}
    xplane_pids = {p for p, a in procs.items()
                   if a.get("source") == "xplane"}
    scope = "device"
    pids = device_pids
    if not pids:
        scope, pids = "host-xplane", xplane_pids
    agg = defaultdict(lambda: [0.0, 0])
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in pids:
            continue
        dur = float(ev.get("dur", 0.0))
        if dur <= 0:
            continue
        a = agg[ev.get("name", "?")]
        a[0] += dur / 1e6
        a[1] += 1
    rows = sorted(((n, t, c) for n, (t, c) in agg.items()),
                  key=lambda r: -r[1])[:k]
    return rows, scope


def host_breakdown(doc: dict):
    """Compile / run / feed-stall / step seconds from the flight spans."""
    fl = doc.get("flight", {})
    agg = defaultdict(lambda: [0.0, 0])
    for ev in fl.get("events", []):
        if "dur" not in ev:
            continue
        kind = ev.get("kind", "?")
        if kind.startswith("executor.compile"):
            key = "compile"
        elif kind.startswith("executor."):
            key = "run"
        elif kind.startswith("feed."):
            key = "feed_stall"
        elif kind == "step":
            key = "step"
        else:
            key = kind
        agg[key][0] += float(ev["dur"])
        agg[key][1] += 1
    return dict(agg)


def recompile_causes(doc: dict):
    agg = defaultdict(int)
    for ev in doc.get("flight", {}).get("events", []):
        if ev.get("kind") == "executor.recompile":
            for comp in ev.get("changed", []):
                agg[comp] += 1
    return dict(agg)


def watchdog_trips(doc: dict):
    return [ev for ev in doc.get("flight", {}).get("events", [])
            if ev.get("kind") == "watchdog.trip"]


def numerics_info(doc: dict):
    """(locate verdict, last summary event, locate events) from the
    numerics tier (monitor/numerics.py): the header provider embeds the
    NaN-origin verdict; `numerics.summary` events carry the per-step
    training-dynamics aggregates."""
    hdr = doc.get("flight", {}).get("header", {})
    verdict = hdr.get("numerics")
    last_summary = None
    locates = []
    for ev in doc.get("flight", {}).get("events", []):
        if ev.get("kind") == "numerics.summary":
            last_summary = ev
        elif ev.get("kind") == "numerics.locate":
            locates.append(ev)
    if verdict is None and locates:
        verdict = locates[-1]
    return verdict, last_summary, locates


def request_traces(doc: dict, k: int = 10):
    """(all trace.request events, slowest-K, padding-waste top-K) from
    the request-scoped tracing tier (monitor/tracing.py)."""
    reqs = [ev for ev in doc.get("flight", {}).get("events", [])
            if ev.get("kind") == "trace.request"]
    slowest = sorted(reqs, key=lambda e: -float(e.get("dur", 0.0)))[:k]
    padded = sorted((e for e in reqs if e.get("padded_rows")),
                    key=lambda e: -int(e.get("padded_rows", 0)))[:k]
    return reqs, slowest, padded


def pipeline_stages(doc: dict):
    """Per-stage span aggregation + the last schedule summary from the
    pipeline tier's flight events (parallel/pipeline/trainer.py:
    `pipeline.stage` spans carry ctx `pipeline/<stage>`;
    `pipeline.schedule` carries bubble-fraction / in-flight gauges)."""
    stages = defaultdict(lambda: defaultdict(lambda: [0.0, 0]))
    sched = None
    for ev in doc.get("flight", {}).get("events", []):
        if ev.get("kind") == "pipeline.stage":
            agg = stages[ev.get("ctx", f"pipeline/{ev.get('stage')}")]
            a = agg[ev.get("phase", "?")]
            a[0] += float(ev.get("dur", 0.0))
            a[1] += 1
        elif ev.get("kind") == "pipeline.schedule":
            sched = ev
    return {k: {p: tuple(v) for p, v in d.items()}
            for k, d in stages.items()}, sched


def memory_plans(doc: dict):
    """Last memory.plan event per plan name (memory/planner.py
    publish_plan: peak watermark + per-class split + offloaded bytes)."""
    plans = {}
    for ev in doc.get("flight", {}).get("events", []):
        if ev.get("kind") == "memory.plan":
            plans[ev.get("name", "main")] = ev
    return plans


def cost_attribution(doc: dict):
    """Last `cost.program` event per program name (analysis/costmodel
    publish_cost: the static roofline's predicted step time, launch-bound
    fraction, and bound-class census)."""
    costs = {}
    for ev in doc.get("flight", {}).get("events", []):
        if ev.get("kind") == "cost.program":
            costs[ev.get("name", "?")] = ev
    return costs


def phase_split(doc: dict):
    """({phase: seconds}, n) summed over the executor run spans that carry
    their host `phases` ([name, start offset, seconds] in call order:
    core/executor.py _CallSpans).  `fetch` is the blocking device->host
    conversion (the device wait); everything before it is dispatch."""
    totals = {}
    n = 0
    for ev in doc.get("flight", {}).get("events", []):
        kind = str(ev.get("kind", ""))
        if not kind.startswith("executor.") or kind == "executor.compile" \
                or not ev.get("phases"):
            continue
        for name, _, dur in ev["phases"]:
            totals[name] = totals.get(name, 0.0) + float(dur)
        n += 1
    return totals, n


def embedding_census(doc: dict):
    """Last sparse-tier trace census (gather launches / rows touched per
    step — the embedding.* gauges, mirrored into the flight ring at
    trace time by core/executor.py)."""
    last = None
    for ev in doc.get("flight", {}).get("events", []):
        if ev.get("kind") == "embedding.census":
            last = ev
    return last


def kv_page_activity(doc: dict):
    """Per-model aggregation of the paged-KV-cache `kv.page` flight
    events (serving/generation.py ContinuousBatcher: block alloc/free,
    shared-prefix hits, copy-on-write copies)."""
    agg = {}
    for ev in doc.get("flight", {}).get("events", []):
        if ev.get("kind") != "kv.page":
            continue
        a = agg.setdefault(ev.get("model", "?"),
                           {"alloc": 0, "hit": 0, "free": 0, "cow": 0,
                            "blocks_alloc": 0, "blocks_shared": 0})
        event = ev.get("event", "?")
        if event == "alloc":
            a["alloc"] += 1
            a["blocks_alloc"] += (int(ev.get("self_blocks", 0))
                                  + int(ev.get("cross_blocks", 0)))
        elif event == "hit":
            a["hit"] += 1
            a["blocks_alloc"] += int(ev.get("self_blocks", 0))
            a["blocks_shared"] += int(ev.get("shared_blocks", 0))
        elif event == "free":
            a["free"] += 1
        elif event == "cow":
            a["cow"] += int(ev.get("copies", 1))
    return agg


def report(doc: dict, k: int = 20) -> str:
    lines = []
    hdr = doc.get("flight", {}).get("header", {})
    if hdr:
        lines.append(
            f"run: pid={hdr.get('pid')} backend={hdr.get('jax_backend')} "
            f"devices={hdr.get('jax_device_count')} "
            f"last_step={hdr.get('last_step')} "
            f"last_loss={hdr.get('last_loss')}")

    rows, scope = top_ops(doc, k)
    lines.append("")
    lines.append(f"Top ops by total time ({scope} tracks)")
    lines.append(f"{'op':<56} {'total(s)':>10} {'calls':>8}")
    for name, total, calls in rows:
        lines.append(f"{name[:56]:<56} {total:>10.6f} {calls:>8}")
    if not rows:
        lines.append("(no xplane events in this trace)")

    bd = host_breakdown(doc)
    lines.append("")
    lines.append("Host time breakdown (flight spans)")
    if bd:
        lines.append(f"{'category':<16} {'total(s)':>10} {'spans':>8}")
        order = ("compile", "run", "step", "feed_stall")
        for key in [o for o in order if o in bd] + sorted(
                set(bd) - set(order)):
            t, c = bd[key]
            lines.append(f"{key:<16} {t:>10.4f} {c:>8}")
    else:
        lines.append("(no flight spans — was FLAGS.monitor on?)")

    causes = recompile_causes(doc)
    lines.append("")
    if causes:
        lines.append("Recompile causes (changed cache-key components)")
        for comp, n in sorted(causes.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {comp:<32} x{n}")
    else:
        lines.append("Recompiles: none recorded")

    costs = cost_attribution(doc)
    phases, nrun = phase_split(doc)
    if costs or nrun:
        lines.append("")
        lines.append("Attribution (static cost model + dispatch split)")
    if costs:
        lines.append(
            f"{'program':<28} {'launches':>8} {'pred(us)':>10} "
            f"{'launch%':>8} {'bound c/m/l':>12}  device")
        for name in sorted(costs):
            ev = costs[name]
            bc = ev.get("bound_counts") or {}
            lines.append(
                f"{name[:28]:<28} {ev.get('n_launches', 0):>8} "
                f"{float(ev.get('predicted_seconds', 0)) * 1e6:>10.1f} "
                f"{float(ev.get('launch_bound_fraction', 0)):>8.1%} "
                f"{bc.get('compute', 0):>4}/{bc.get('memory', 0)}"
                f"/{bc.get('launch', 0):<5} "
                f"{ev.get('device', '?')} ({ev.get('device_source', '?')})")
    if nrun:
        wait = phases.get("fetch", 0.0)
        tot = sum(phases.values())
        disp = tot - wait
        frac = disp / tot if tot > 0 else 0.0
        lines.append(
            f"  executor split over {nrun} runs: dispatch {disp:.4f}s vs "
            f"device-wait {wait:.4f}s ({frac:.1%} host-side dispatch)")
        lines.append("  host phases: " + "  ".join(
            f"{name} {sec:.4f}s" for name, sec in phases.items()))

    census = embedding_census(doc)
    if census:
        lines.append("")
        lines.append("Sparse embedding census (per traced step)")
        lines.append(f"  gather launches      {census.get('gather_launches')}")
        lines.append(
            f"  sparse rows touched  {census.get('sparse_rows_touched')}")

    plans = memory_plans(doc)
    if plans:
        lines.append("")
        lines.append("Memory (planner table, memory.plan events)")
        lines.append(
            f"{'plan':<14} {'peak MB':>9} {'act MB':>9} {'offl MB':>9} "
            f"{'peak op':<24} {'warn':>5}")
        for name in sorted(plans):
            ev = plans[name]
            by = ev.get("peak_by_class") or {}
            lines.append(
                f"{name[:14]:<14} "
                f"{float(ev.get('peak_bytes', 0)) / 1e6:>9.2f} "
                f"{float(ev.get('activation_peak_bytes', 0)) / 1e6:>9.2f} "
                f"{float(ev.get('offloaded_bytes', 0)) / 1e6:>9.2f} "
                f"{str(ev.get('peak_op_type', '?'))[:20]:<20} "
                f"@{ev.get('peak_op_index', '?'):<4} "
                f"{ev.get('warnings', 0):>4}")
            if by:
                lines.append("    at peak: " + ", ".join(
                    f"{c} {float(by.get(c, 0)) / 1e6:.2f} MB"
                    for c in ("params", "opt_state", "kv_cache",
                              "activations", "workspace", "feeds")
                    if by.get(c)))

    stages, sched = pipeline_stages(doc)
    if stages or sched:
        lines.append("")
        lines.append("Pipeline stages (flight spans)")
        if sched:
            lines.append(
                f"  schedule {sched.get('schedule')}: "
                f"{sched.get('n_stages')} stages x "
                f"{sched.get('n_micro')} micro-batches, bubble fraction "
                f"{sched.get('bubble_fraction')}, peak in-flight "
                f"{sched.get('peak_in_flight')}")
        for ctx in sorted(stages):
            parts = ", ".join(
                f"{p}: {t:.4f}s/{c}" for p, (t, c) in
                sorted(stages[ctx].items()))
            lines.append(f"  {ctx:<16} {parts}")

    reqs, slowest, padded = request_traces(doc, k)
    if reqs:
        lines.append("")
        kinds = {}
        for ev in reqs:
            key = f"{ev.get('model', '?')}:{ev.get('trace_kind', '?')}"
            kinds[key] = kinds.get(key, 0) + 1
        lines.append(
            "Requests (request-scoped traces; "
            + ", ".join(f"{k_}: {n}" for k_, n in sorted(kinds.items()))
            + ")")
        lines.append(
            f"{'trace':<18} {'model':<12} {'status':<14} {'total':>9} "
            f"{'queue':>8} {'exec':>8} {'decode':>8} {'unattr':>8}")

        def ms(v):
            return "-" if v is None else f"{float(v):.2f}"

        for ev in slowest:
            comp = (ev.get("decomposition") or {}).get(
                "components_ms", {})
            unattr = (ev.get("decomposition") or {}).get(
                "unattributed_ms")
            lines.append(
                f"{str(ev.get('trace', '?'))[:16]:<18} "
                f"{str(ev.get('model', '?'))[:12]:<12} "
                f"{str(ev.get('status', '?'))[:14]:<14} "
                f"{ms(ev.get('total_ms')):>9} "
                f"{ms(comp.get('queue.wait')):>8} "
                f"{ms(comp.get('batch.exec')):>8} "
                f"{ms(comp.get('decode')):>8} "
                f"{ms(unattr):>8}")
        if padded:
            lines.append("")
            lines.append("Padding waste (rows padded to reach the "
                         "bucket — top requests)")
            for ev in padded:
                pad = (ev.get("decomposition") or {}).get("padding", {})
                lines.append(
                    f"  {str(ev.get('trace', '?'))[:16]:<18} "
                    f"model={ev.get('model', '?')} "
                    f"padded={ev.get('padded_rows')} "
                    f"bucket={pad.get('bucket')} "
                    f"fill={pad.get('fill')}")

    pages = kv_page_activity(doc)
    if pages:
        lines.append("")
        lines.append("Generation (paged KV cache, kv.page events)")
        lines.append(
            f"{'model':<14} {'admits':>7} {'hits':>6} {'frees':>6} "
            f"{'cow':>5} {'blk alloc':>10} {'blk shared':>11}")
        for name in sorted(pages):
            a = pages[name]
            lines.append(
                f"{name[:14]:<14} {a['alloc'] + a['hit']:>7} "
                f"{a['hit']:>6} {a['free']:>6} {a['cow']:>5} "
                f"{a['blocks_alloc']:>10} {a['blocks_shared']:>11}")

    verdict, num_summary, _locates = numerics_info(doc)
    if verdict is not None or num_summary is not None:
        lines.append("")
        lines.append("Numerics (check_numerics tier)")
        if verdict is not None:
            stat = verdict.get("stat") or {}
            first = verdict.get("first_bad_op")
            if first:
                lines.append(
                    f"  first non-finite output: {first} "
                    f"(var {verdict.get('var')!r}, step "
                    f"{verdict.get('step')}, "
                    f"{'replayed' if verdict.get('replayed') else 'in-step'})")
                lines.append(
                    f"    nonfinite={stat.get('nonfinite')} "
                    f"abs_max={stat.get('abs_max')} "
                    f"abs_mean={stat.get('abs_mean')} l2={stat.get('l2')}")
            else:
                lines.append(
                    f"  locate replay found no non-finite op output "
                    f"(step {verdict.get('step')}, "
                    f"{verdict.get('rows_checked')} rows checked)")
        if num_summary is not None:
            lines.append(
                f"  last summary: grad_norm={num_summary.get('grad_norm')} "
                f"grad_nonfinite={num_summary.get('grad_nonfinite')} "
                f"nonfinite_rows={num_summary.get('nonfinite_rows')} "
                f"groups={num_summary.get('groups')}")

    trips = watchdog_trips(doc)
    if trips:
        lines.append("")
        lines.append("Watchdog trips")
        for t in trips:
            lines.append(f"  [{t.get('trip')}] step {t.get('step')}: "
                         f"{t.get('detail')}")
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="summarize a unified chrome trace / jax trace dir")
    p.add_argument("trace", help="merged trace JSON (or a jax trace dir)")
    p.add_argument("--top", type=int, default=20,
                   help="rows in the top-op table")
    args = p.parse_args(argv)
    print(report(load_trace(args.trace), args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
