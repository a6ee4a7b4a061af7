"""Production serving tier: multi-model inference server with dynamic
batching on the AOT-bundle path (ROADMAP item 1 — the reference's
out-of-Python serving property, api/paddle_api.h:153, grown into the
"heavy traffic" story).

Three layers:

  * `model.py`   — ServingModel: a Predictor (+ optional int8 replica via
    contrib.quantize.freeze_int8) with a pad-to-bucket batch ladder,
    startup warmup, and serving-tier recompile tagging.
  * `batcher.py` — DynamicBatcher: per-model request queue drained by a
    scheduler thread that coalesces concurrent requests into bucket
    shapes (max-wait deadline, max-batch cap), so every executed batch
    hits a warm entry in the executor's compile cache.
  * `server.py`  — InferenceServer: stdlib-HTTP multi-model endpoint
    (JSON + npz), /v1/models introspection, /metrics //health //flight
    inherited from the monitor stack, persistent XLA compilation cache.

A fourth layer serves autoregressive generation (ROADMAP item 2):

  * `generation.py` — GenerationServingModel + ContinuousBatcher:
    continuous TOKEN-level batching of decode steps across in-flight
    sequences on the KV-cache program pair (paddle_tpu/generation); new
    sequences join at prefill via the active-mask feed, finished ones
    retire their cache slot, and nothing ever retraces.  Endpoint:
    POST /v1/models/<name>:generate.

The tier is overload-hardened (ISSUE 13): bounded queues + in-flight
cap shed with 429/Retry-After, request deadlines propagate into the
schedulers (expired work is dropped before dispatch), SIGTERM drains
gracefully (503 new work, finish admitted work, dump flight, exit 0),
a per-model circuit breaker fails fast past consecutive executor
failures, and /health reports `draining` / `scheduler_dead`.  Chaos
kinds in testing/chaos.py (serve latency / transient executor errors /
request flood) drive the CI overload gate.

Scale-out (ISSUE 18): `router.py` + `fleet.py` turn N replicas into one
durable endpoint — a health-probe-driven Router (least-inflight +
SLO-weighted balancing, deadline-budgeted retry-with-failover, optional
tail-latency hedging, traceparent passthrough) fronting a
ReplicaSupervisor that crash-restarts replicas with capped backoff and
rolling-restarts them with zero downtime against the shared persistent
compilation cache.  Both are lazy exports: the single-replica serving
path never imports them.

CLI: `python -m paddle_tpu.serving --model name=/path/to/export ...`
     (add `--demo-generation NAME` for the seeded tiny generation model;
      add `--replicas N` for a supervised fleet behind the router)
Load test: `python tools/loadgen.py --url http://host:port --model name`
           (`--generate` for prompt-in/tokens-out TTFT + tokens/sec;
            `--router` to scrape router fleet metrics into the artifact).
"""

from .batcher import (  # noqa: F401
    CircuitBreaker,
    DynamicBatcher,
    FILL_BUCKETS,
    Overloaded,
    Unavailable,
)
from .generation import (  # noqa: F401
    ContinuousBatcher,
    GenerationConfig,
    GenerationServingModel,
    build_demo_generation_model,
)
from .model import ModelConfig, ServingModel, parse_buckets  # noqa: F401
from .server import (  # noqa: F401
    InferenceServer,
    RequestError,
    ServingHandler,
)


def __getattr__(name):
    # the scale-out tier stays un-imported until someone asks for it:
    # single-replica serving pays nothing for the router/fleet code
    if name in ("Router", "RouterHandler", "Replica"):
        from . import router as _router

        return getattr(_router, name)
    if name == "ReplicaSupervisor":
        from .fleet import ReplicaSupervisor

        return ReplicaSupervisor
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
