"""Arithmetic over the executor's first-calls record, for the five
`setup_*_s.train` readers under metrics/: the flight events its Executor
writes for each of its first `FIRST_CALLS` calls whatever tracing says
(paddle_tpu/core/executor.py `_CallSpans`: `first_calls` 1, `t0` on the
epoch clock, `dur`, `phases` [name, start offset, seconds]; a miss is an
`executor.compile` event that also carries jax's `trace_s`, `lower_s`,
`backend_s` deltas of the call).  The calls of set-up are those that end
before `setup_s` does.  Pure functions over lists and dicts but
`setup_calls`; perfbench/tests/test_setup_spans.py checks them on
hand-made events.  A parent commit that records no first calls gives
None, and so does a record that filled up before set-up ended."""

import json
import sys

MISS = "executor.compile"
# backend_s holds cache_load_s: the two are not to be added
JAX_PHASES = ("trace_s", "lower_s", "backend_s")


def select(events, setup_end, cap):
    """The first-calls events that end by `setup_end` (epoch seconds),
    or None where there are none or `cap` of them: the record may have
    filled up before set-up ended.  perf_counter and the wall clock are
    slewed alike, so only a step of the wall clock could move a call
    across the boundary, which lies some tens of microseconds after the
    last set-up call's exit and before the window's first entry."""
    calls = [e for e in events if e.get("first_calls")
             and e["t0"] + e["dur"] <= setup_end]
    if not calls or len(calls) >= cap:
        return None
    return calls


def setup_calls(ctx):
    """`select` over the program's flight ring for this run, or None."""
    try:
        from paddle_tpu.core import executor
        from paddle_tpu.monitor import flight
    except ImportError:
        return None
    cap = getattr(executor, "FIRST_CALLS", None)
    if cap is None:
        return None
    end = ctx["cell"].t_start + ctx["result"]["setup_s"]
    return select(flight.default_recorder().events(kind="executor"), end,
                  cap)


def misses(calls):
    return [e for e in calls if e["kind"] == MISS]


def hits(calls):
    return [e for e in calls if e["kind"] != MISS]


def phase_s(event, name):
    return sum(d for n, _, d in event["phases"] if n == name)


def miss_calls_s(calls):
    return sum(e["dur"] for e in misses(calls))


def hit_calls_s(calls):
    return sum(e["dur"] for e in hits(calls))


def exec_prep_s(calls):
    """The misses' `compile` phase: verify, state split, the mode's step
    closure and `jax.jit` (which compiles nothing yet)."""
    return sum(phase_s(e, "compile") for e in misses(calls))


def jit_other_s(calls):
    """The misses' `dispatch` less jax's trace, lower and backend seconds
    that fell inside it (`jax_by_phase`): what jax does on a miss beyond
    its named phases (cache keys, loading the executable, the arguments,
    the first launch).  The whole call's deltas would also hold the
    eager jits of another phase: the step key's in the first call's
    `gather`."""
    return sum(phase_s(e, "dispatch") - sum(
        e.get("jax_by_phase", {}).get("dispatch", {}).get(k, 0.0)
        for k in JAX_PHASES) for e in misses(calls))


def outside_s(setup_s, calls):
    """Set-up outside every Executor call: interpreter, imports, program
    build, the harness's weights, feeds and checks."""
    return setup_s - miss_calls_s(calls) - hit_calls_s(calls)


def describe(calls, totals):
    """One stderr line `setup_calls {..}`: each set-up call's kind, mode,
    seconds, its compile and dispatch phases and jax's deltas, beside the
    process's totals of jax's phases (`compile_phases()`, or None)."""
    rows = [{"kind": e["kind"], "mode": e.get("mode"), "dur": e["dur"],
             "compile": phase_s(e, "compile"),
             "dispatch": phase_s(e, "dispatch"),
             **{k: e[k] for k in JAX_PHASES + (
                 "cache_load_s", "cache_hits", "cache_misses", "jax_by_phase")
                if k in e}}
            for e in calls]
    process = totals and {k: totals[k] for k in JAX_PHASES}
    print(f"setup_calls {json.dumps({'calls': rows, 'process': process})}",
          file=sys.stderr)
