"""Metrics registry: counters, gauges, bounded-bucket histograms.

Prometheus-inspired but dependency-free; metric names are dotted strings
("executor.cache_miss") which the Prometheus exposition sanitizes to
underscore form.  All mutation goes through per-metric locks so parse
workers / serving threads can hammer the same counter safely (the GIL makes
`+=` *mostly* atomic in CPython, but "mostly" is not a contract).

The registry itself is intentionally always-on and cheap; the FLAGS.monitor
gate lives at the instrumentation call-sites (executor, data_feed,
inference, collectives) so the hot paths skip even the helper call when
telemetry is off.
"""

from __future__ import annotations

import bisect
import collections
import json
import threading
import time
from typing import Dict, List, Optional, Sequence


def enabled() -> bool:
    """Whether telemetry call-sites should write (the FLAGS.monitor gate)."""
    from ..flags import FLAGS

    return FLAGS.monitor


def spans_on() -> bool:
    """Whether the executor should stamp its host phases into the flight
    ring: FLAGS.monitor, or ANY active jax.profiler session (the
    benchmark's, `profiler.start_profiler(trace_dir=...)`, an operator's
    xprof capture).  Narrower than `enabled()` on purpose: a profiler
    session switches the spans on, not every histogram."""
    from ..flags import FLAGS

    if FLAGS.monitor:
        return True
    from jax.profiler import TraceAnnotation

    return TraceAnnotation.is_enabled()


# latency-flavored default buckets (seconds): 100us .. 30s, bounded
DEFAULT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)


class Counter:
    """Monotonically increasing float counter."""

    kind = "counter"
    __slots__ = ("name", "help", "_lock", "_value")

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, n: float = 1.0):
        if n < 0:
            raise ValueError(f"counter {self.name!r}: negative increment {n}")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> dict:
        return {"metric": self.name, "type": self.kind, "value": self._value}


class Gauge:
    """Instantaneous value (queue depth, last loss, ...)."""

    kind = "gauge"
    __slots__ = ("name", "help", "_lock", "_value")

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float):
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0):
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0):
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> dict:
        return {"metric": self.name, "type": self.kind, "value": self._value}


class Histogram:
    """Fixed-bucket histogram (bounded memory: len(buckets)+1 counts).

    `buckets` are upper bounds in ascending order; an implicit +Inf bucket
    catches the tail.  Exposition is cumulative (Prometheus `le` form).
    """

    kind = "histogram"
    __slots__ = ("name", "help", "buckets", "_lock", "_counts", "_sum",
                 "_count", "_max")

    def __init__(self, name: str, buckets: Sequence[float] = DEFAULT_BUCKETS,
                 help: str = ""):
        bs = tuple(float(b) for b in buckets)
        if not bs or list(bs) != sorted(bs):
            raise ValueError(
                f"histogram {name!r}: buckets must be ascending, got {bs}")
        self.name = name
        self.help = help
        self.buckets = bs
        self._lock = threading.Lock()
        self._counts = [0] * (len(bs) + 1)  # +1: the +Inf tail
        self._sum = 0.0
        self._count = 0
        self._max = 0.0  # largest observed value (the +Inf bucket's clamp)

    def observe(self, v: float):
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            if v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def max(self) -> float:
        """Largest observed value (0.0 before any observation)."""
        return self._max

    def quantile(self, q: float) -> Optional[float]:
        """Bucket-resolution quantile estimate (the upper bound of the
        bucket holding the q-th observation, Prometheus histogram_quantile
        style).  Returns None with no observations.  The +Inf tail bucket
        clamps to the LARGEST OBSERVED value instead of returning inf —
        a single outlier past the top bound must not make a p99 report
        `inf` in /v1/models info."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile {q} outside [0, 1]")
        with self._lock:
            counts = list(self._counts)
            total = self._count
            vmax = self._max
        if total == 0:
            return None
        target = q * total
        cum = 0
        for le, c in zip(self.buckets + (float("inf"),), counts):
            cum += c
            if cum >= target:
                return vmax if le == float("inf") else le
        return vmax

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            total, s, vmax = self._count, self._sum, self._max
        cum, cum_counts = 0, []
        for le, c in zip(self.buckets + (float("inf"),), counts):
            cum += c
            cum_counts.append([le, cum])
        return {"metric": self.name, "type": self.kind, "count": total,
                "sum": s, "max": vmax, "buckets": cum_counts}


class SloTracker:
    """Good/bad SLO event accounting behind the serving burn-rate gauges.

    A request is GOOD when it completed inside its latency objective, BAD
    when it missed it, errored, or was shed.  Events land in coarse
    fixed-width time buckets (bounded memory: one [start, good, bad] row
    per BUCKET_S over the horizon), so the multi-window burn rates the
    SRE playbook asks for — observed bad fraction over the window divided
    by the error budget (1 - target); 1.0 means burning the budget
    exactly at the sustainable rate — come from one deque walk at scrape
    time, not a per-request histogram."""

    BUCKET_S = 10.0

    __slots__ = ("name", "objective_ms", "target", "_lock", "_buckets",
                 "good_total", "bad_total")

    def __init__(self, name: str, objective_ms: float,
                 target: float = 0.999, horizon_s: float = 3600.0):
        if not 0.0 < target < 1.0:
            raise ValueError(f"slo target must be in (0, 1), got {target}")
        self.name = name
        self.objective_ms = float(objective_ms)
        self.target = float(target)
        self._lock = threading.Lock()
        self._buckets: "collections.deque" = collections.deque(
            maxlen=int(horizon_s / self.BUCKET_S) + 2)
        self.good_total = 0
        self.bad_total = 0

    def observe(self, good: bool, now: Optional[float] = None) -> None:
        now = time.time() if now is None else now
        start = now - (now % self.BUCKET_S)
        with self._lock:
            if not self._buckets or self._buckets[-1][0] != start:
                self._buckets.append([start, 0, 0])
            self._buckets[-1][1 if good else 2] += 1
            if good:
                self.good_total += 1
            else:
                self.bad_total += 1

    def window_counts(self, window_s: float,
                      now: Optional[float] = None) -> tuple:
        """(good, bad) over the trailing window (bucket resolution)."""
        now = time.time() if now is None else now
        cut = now - float(window_s)
        good = bad = 0
        with self._lock:
            for start, g, b in self._buckets:
                if start + self.BUCKET_S > cut:
                    good += g
                    bad += b
        return good, bad

    def burn_rate(self, window_s: float,
                  now: Optional[float] = None) -> float:
        good, bad = self.window_counts(window_s, now)
        n = good + bad
        if n == 0:
            return 0.0
        return (bad / n) / max(1.0 - self.target, 1e-9)


class MetricsRegistry:
    """Name -> metric store; get-or-create, thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}
        # collect hooks run at the top of every snapshot() (and therefore
        # every /metrics scrape) OUTSIDE the registry lock — the place to
        # refresh derived gauges (SLO burn rates) lazily instead of per
        # request.  Exception-proof: a broken hook must not fail a scrape.
        self._collect_hooks: List = []

    def _get_or_create(self, name, cls, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, **kwargs)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(name, Counter, help=help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(name, Gauge, help=help)

    def histogram(self, name: str,
                  buckets: Optional[Sequence[float]] = None,
                  help: str = "") -> Histogram:
        h = self._get_or_create(
            name, Histogram, buckets=buckets or DEFAULT_BUCKETS, help=help)
        # explicit buckets that don't match the live metric would put
        # observations past the old top bucket in +Inf; warn (never
        # raise — instrumentation must not be able to fail a run)
        if buckets is not None and tuple(float(b) for b in buckets) != h.buckets:
            from ..log import warning

            warning(
                "histogram %r already registered with buckets %s; "
                "requested %s ignored", name, h.buckets, tuple(buckets))
        return h

    def get(self, name: str):
        return self._metrics.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    def reset(self):
        with self._lock:
            self._metrics.clear()

    def add_collect_hook(self, fn) -> None:
        """Register `fn()` to run before every snapshot()/scrape (derived-
        gauge refresh).  Idempotent per callable; hooks survive reset()."""
        if fn not in self._collect_hooks:
            self._collect_hooks.append(fn)

    def remove_collect_hook(self, fn) -> None:
        try:
            self._collect_hooks.remove(fn)
        except ValueError:
            pass

    # -- exposition ------------------------------------------------------
    def snapshot(self) -> List[dict]:
        for fn in list(self._collect_hooks):
            try:
                fn()
            except Exception:  # noqa: BLE001 — a hook must not fail a scrape
                pass
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        return [m.snapshot() for m in metrics]

    def prometheus_text(self) -> str:
        """Prometheus text exposition format (scrape-ready)."""
        lines = []
        for snap in self.snapshot():
            name = _prom_name(snap["metric"])
            lines.append(f"# TYPE {name} {snap['type']}")
            if snap["type"] == "histogram":
                for le, cum in snap["buckets"]:
                    le_s = "+Inf" if le == float("inf") else _prom_num(le)
                    lines.append(f'{name}_bucket{{le="{le_s}"}} {cum}')
                lines.append(f"{name}_sum {_prom_num(snap['sum'])}")
                lines.append(f"{name}_count {snap['count']}")
            else:
                lines.append(f"{name} {_prom_num(snap['value'])}")
        return "\n".join(lines) + ("\n" if lines else "")

    def jsonl(self) -> str:
        """One JSON object per line per metric (BENCH-artifact style).
        Non-finite values (a NaN loss gauge from a diverged run) become
        strings so the output stays strict JSON."""
        ts = time.time()
        return "\n".join(
            json.dumps(_json_safe(dict(snap, ts=round(ts, 3))))
            for snap in self.snapshot()
        ) + ("\n" if self._metrics else "")

    def write_jsonl(self, path: str):
        with open(path, "w") as f:
            f.write(self.jsonl())

    def write_prometheus(self, path: str):
        with open(path, "w") as f:
            f.write(self.prometheus_text())


def _json_safe(v):
    import math

    if isinstance(v, float) and not math.isfinite(v):
        return "NaN" if math.isnan(v) else (
            "Infinity" if v > 0 else "-Infinity")
    if isinstance(v, dict):
        return {k: _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    return v


def _prom_name(name: str) -> str:
    out = "".join(c if (c.isalnum() or c in "_:") else "_" for c in name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _prom_num(v) -> str:
    import math

    f = float(v)
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


_default = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    return _default


def counter(name: str, help: str = "") -> Counter:
    return _default.counter(name, help=help)


def gauge(name: str, help: str = "") -> Gauge:
    return _default.gauge(name, help=help)


def histogram(name: str, buckets: Optional[Sequence[float]] = None,
              help: str = "") -> Histogram:
    return _default.histogram(name, buckets=buckets, help=help)
