"""Runtime telemetry subsystem (reference role: the glog VLOG counters +
platform/profiler.h host ranges + the benchmark/fluid metric prints; none of
which exposed a scrapeable registry — this is the production-serving gap
named in ROADMAP.md).

Five pieces:

  * `registry.py` — a thread-safe metrics registry (counters, gauges,
    histograms with bounded buckets) with Prometheus-text and JSONL
    exposition.  A process-wide default registry backs the module-level
    `counter()/gauge()/histogram()` helpers.
  * `step.py` — `StepMonitor`, per-step training telemetry (loss,
    examples/sec, tokens/sec, rolling MFU via `profiler.cost_analysis` or
    analytic FLOPs) written as BENCH-format-compatible JSONL.
  * `flight.py` — the flight recorder: a bounded ring of structured
    runtime events (steps, compile/run spans, recompile causes, feed
    stalls, collective traces) dumped as JSONL on crash / SIGTERM /
    watchdog trip, so a dead run leaves a black box.
  * `watchdog.py` — anomaly detection fed by StepMonitor: NaN/Inf loss,
    loss-spike z-score, throughput collapse, and a hang monitor on a
    daemon thread; actions log / dump / raise.
  * `serve.py` — stdlib-http exposition: /metrics (Prometheus), /health,
    /flight (last-N events), behind FLAGS.monitor_port.
  * `numerics.py` — the monitor half of the FLAGS_check_numerics tier:
    per-param-group training-dynamics gauges from the in-graph stats
    fetch (analysis/numerics.py), amp overflow accounting, and the
    failing-step capture/replay that names the first op with a
    non-finite output on a watchdog nan_loss trip.
  * instrumentation call-sites live in the runtime itself
    (`core/executor.py` compile/run/recompile, `data_feed.py` queue
    gauges, `inference.py` request histograms, `parallel/distributed.py`
    collective counters), every one gated on `FLAGS.monitor` so the hot
    paths pay nothing when telemetry is off.

Usage:

    from paddle_tpu.flags import FLAGS
    FLAGS.monitor = True                      # or env FLAGS_monitor=1
    ... run training ...
    import paddle_tpu.monitor as monitor
    print(monitor.default_registry().prometheus_text())
"""

from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    DEFAULT_BUCKETS,
    counter,
    gauge,
    histogram,
    default_registry,
    enabled,
    spans_on,
)
from .registry import SloTracker  # noqa: F401
from .step import StepMonitor  # noqa: F401
from . import flight  # noqa: F401
from .flight import (  # noqa: F401
    FlightRecorder, compile_phases, device_counter)
from .watchdog import Watchdog, WatchdogError  # noqa: F401
from . import serve  # noqa: F401
from . import numerics  # noqa: F401
from . import tracing  # noqa: F401
from .tracing import RequestTrace, TraceStore  # noqa: F401
