#!/usr/bin/env python
"""bench_diff: the noise-aware bench regression sentry.

Compares a fresh bench/smoke JSON-lines artifact (bench.py output)
against a committed baseline ledger and exits nonzero naming every
regressed (workload, metric) pair — turning the BENCH_r* trajectory from
a human-reread document into an enforced contract (run_ci.sh gate).

Noise model: a record's `config.runs[]` (the PERF.md repeated-run
protocol) gives its observed envelope [min(runs), max(runs)]; both
envelopes are further widened by --rel-tol x value (cross-box variance
the runs of ONE box cannot see).  A regression is flagged only
when the widened envelopes SEPARATE in the bad direction — overlap is
noise, never a finding.  Direction comes from the record's unit
("…/sec" higher-better; "ms"/"us"/"seconds" lower-better; anything else
is skipped with a note).

Records are keyed by (metric, occurrence index) — the A/B artifacts
archive the same metric twice with different flags (fused on/off,
kv_cache on/off) in a fixed order, so position is identity.

Exit codes: 0 clean, 1 regression / baseline metric missing from fresh,
2 usage or unreadable input.

Usage:
  python tools/bench_diff.py BASELINE.json FRESH.json [--rel-tol 0.30]
"""

from __future__ import annotations

import argparse
import json
import sys

LOWER_BETTER_UNITS = ("ms", "us", "seconds", "us/launch")
HIGHER_BETTER_MARK = "/sec"


def load_keyed(path):
    """[(key, record)] in file order; key = metric#occurrence."""
    seen = {}
    out = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        print(f"bench_diff: cannot read {path}: {e}", file=sys.stderr)
        sys.exit(2)
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        m = rec.get("metric")
        if not m:
            continue
        n = seen.get(m, 0)
        seen[m] = n + 1
        out.append((m if n == 0 else f"{m}#{n + 1}", rec))
    return out


def direction(rec):
    """+1 higher-better, -1 lower-better, 0 not comparable."""
    unit = str(rec.get("unit", ""))
    if HIGHER_BETTER_MARK in unit:
        return 1
    if unit in LOWER_BETTER_UNITS:
        return -1
    return 0


def envelope(rec, rel_tol):
    """(lo, hi) of the record's plausible true value: observed runs[]
    spread widened by rel_tol x value."""
    v = rec.get("value")
    if v is None:
        return None
    v = float(v)
    runs = (rec.get("config") or {}).get("runs")
    if isinstance(runs, list) and runs:
        lo, hi = float(min(runs)), float(max(runs))
    else:
        lo = hi = v
    pad = abs(v) * rel_tol
    return lo - pad, hi + pad


def provenance_line(tag, rec):
    p = rec.get("provenance") or {}
    commit = str(p.get("git_commit", "?"))[:12]
    dirty = "+dirty" if p.get("git_dirty") else ""
    return f"  {tag}: commit {commit}{dirty} jax {p.get('jax', '?')}"


def diff(baseline, fresh, rel_tol):
    """Returns (regressions, notes): regressions are the exit-nonzero
    findings, each naming the (workload, metric) pair."""
    fresh_map = dict(fresh)
    regressions, notes = [], []
    for key, base in baseline:
        d = direction(base)
        workload = key.split("_")[0]
        cur = fresh_map.pop(key, None)
        if cur is None:
            regressions.append(
                f"({workload}, {key}): present in baseline but MISSING "
                f"from the fresh artifact")
            continue
        if base.get("value") is None:
            notes.append(f"({workload}, {key}): baseline value is null; "
                         f"skipped")
            continue
        if cur.get("value") is None:
            regressions.append(
                f"({workload}, {key}): fresh value is null (workload "
                f"failed) vs baseline {base['value']}")
            continue
        if d == 0:
            notes.append(f"({workload}, {key}): unit "
                         f"{base.get('unit')!r} has no better-direction; "
                         f"skipped")
            continue
        b_lo, b_hi = envelope(base, rel_tol)
        c_lo, c_hi = envelope(cur, rel_tol)
        bv, cv = float(base["value"]), float(cur["value"])
        rel = (cv - bv) / abs(bv) if bv else 0.0
        if d > 0 and c_hi < b_lo:
            regressions.append(
                f"({workload}, {key}): REGRESSED {bv:g} -> {cv:g} "
                f"{base.get('unit')} ({rel:+.1%}); fresh envelope "
                f"[{c_lo:g}, {c_hi:g}] entirely below baseline "
                f"[{b_lo:g}, {b_hi:g}] at rel-tol {rel_tol:.0%}")
        elif d < 0 and c_lo > b_hi:
            regressions.append(
                f"({workload}, {key}): REGRESSED {bv:g} -> {cv:g} "
                f"{base.get('unit')} ({rel:+.1%}); fresh envelope "
                f"[{c_lo:g}, {c_hi:g}] entirely above baseline "
                f"[{b_lo:g}, {b_hi:g}] at rel-tol {rel_tol:.0%}")
        elif (d > 0 and c_lo > b_hi) or (d < 0 and c_hi < b_lo):
            notes.append(f"({workload}, {key}): improved {bv:g} -> "
                         f"{cv:g} {base.get('unit')} ({rel:+.1%})")
        else:
            notes.append(f"({workload}, {key}): ok {bv:g} -> {cv:g} "
                         f"({rel:+.1%}, within noise)")
    for key, _ in fresh:
        if key in fresh_map:
            notes.append(f"(new, {key}): present only in the fresh "
                         f"artifact; not compared")
    return regressions, notes


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("baseline", help="committed baseline ledger (JSONL)")
    ap.add_argument("fresh", help="fresh bench/smoke artifact (JSONL)")
    ap.add_argument("--rel-tol", type=float, default=0.30,
                    help="envelope widening as a fraction of value "
                         "(default 0.30: cross-box honesty; tighten for "
                         "same-box trend tracking)")
    ap.add_argument("--quiet", action="store_true",
                    help="print regressions only")
    args = ap.parse_args()

    baseline = load_keyed(args.baseline)
    fresh = load_keyed(args.fresh)
    if not baseline:
        print(f"bench_diff: no records in baseline {args.baseline}",
              file=sys.stderr)
        return 2
    regressions, notes = diff(baseline, fresh, args.rel_tol)
    if not args.quiet:
        if baseline and fresh:
            print(provenance_line("baseline", baseline[0][1]))
            print(provenance_line("fresh   ", fresh[0][1]))
        for n in notes:
            print(f"  note {n}")
    for r in regressions:
        print(f"  REGRESSION {r}")
    if regressions:
        print(f"bench_diff: {len(regressions)} regression(s) vs "
              f"{args.baseline}")
        return 1
    print(f"bench_diff: clean ({len(baseline)} baseline record(s), "
          f"rel-tol {args.rel_tol:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
