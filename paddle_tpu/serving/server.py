"""InferenceServer: multi-model HTTP inference on the stdlib HTTP stack.

The production serving tier the ROADMAP north star asks for (the
reference's out-of-Python serving property, api/paddle_api.h:153, scaled
to many models + concurrent clients): load one or more exported model
dirs (AOT bundles opt-in for trusted artifacts), accept concurrent
JSON / npz requests, and drain them through per-model dynamic batchers
so every executed batch lands on a warm compiled signature.

Endpoints (handler subclasses monitor/serve.py's MonitorHandler, so the
observability routes come for free):

  * POST /v1/models/<name>:predict   (also .../predict) — run inference;
      JSON body  {"inputs": {feed: nested-list | {"b64","dtype","shape"}},
                  "precision": "fp32"|"int8"}  ->
                 {"outputs": {fetch: nested-list}, "batch": {...}}
      npz body   (Content-Type: application/x-npz, arrays keyed by feed
                 name; add ?format=npz for an npz response) — the binary
                 path for large tensors, np.load(allow_pickle=False).
  * GET  /v1/models            — model list w/ readiness, buckets, stats
  * GET  /v1/models/<name>     — one model's info
  * GET  /metrics /health /flight — inherited; /health reports serving
      READINESS (distinct from trainer liveness) via the registered
      readiness provider.

Startup: `InferenceServer([...ModelConfig...]).start()` enables
telemetry, starts the batcher threads + HTTP listener, then warms every
model's bucket ladder.  The persistent XLA compilation cache is the
entry point's business (`python -m paddle_tpu.serving` calls
inference.enable_compile_cache — warmup compiles survive restarts).
"""

from __future__ import annotations

import base64
import io as _io
import json
import threading
import time
from http.server import ThreadingHTTPServer
from typing import Dict, List, Optional
from urllib.parse import parse_qs, urlparse

import numpy as np

from ..monitor import serve as mserve
from ..monitor import tracing
from ..monitor.registry import _json_safe
from .batcher import (DynamicBatcher, Overloaded, Unavailable,
                      _record_shed, _slo_bad)
from .model import ModelConfig, ServingModel


class RequestError(Exception):
    """Client-side error -> HTTP 4xx with a JSON body."""

    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code


def _decode_inputs(body: bytes, ctype: str, specs) -> tuple:
    """Request body -> (feed dict, options dict).  JSON (nested lists or
    b64 raw buffers) and npz (allow_pickle=False) are supported; values
    are cast to the program's declared feed dtypes."""
    if "json" in ctype or ctype.startswith("text/plain"):
        try:
            payload = json.loads(body.decode())
        except (ValueError, UnicodeDecodeError) as e:
            raise RequestError(400, f"malformed JSON body: {e}")
        if not isinstance(payload, dict) or "inputs" not in payload:
            raise RequestError(400, 'JSON body must carry an "inputs" map')
        raw = payload["inputs"]
        if not isinstance(raw, dict):
            raise RequestError(400, '"inputs" must map feed name -> value')
        feed = {}
        for n, v in raw.items():
            dtype = np.dtype(specs[n][1]) if (
                n in specs and specs[n][1] != "bfloat16") else np.float32
            try:
                if isinstance(v, dict) and "b64" in v:
                    buf = base64.b64decode(v["b64"])
                    a = np.frombuffer(buf, dtype=np.dtype(v.get(
                        "dtype", str(dtype))))
                    if "shape" in v:
                        a = a.reshape([int(d) for d in v["shape"]])
                    feed[n] = a.astype(dtype, copy=False)
                else:
                    feed[n] = np.asarray(v, dtype=dtype)
            except (ValueError, TypeError) as e:
                raise RequestError(400, f"input {n!r}: {e}")
        opts = {k: v for k, v in payload.items() if k != "inputs"}
        return feed, opts
    if "npz" in ctype or "octet-stream" in ctype:
        try:
            with np.load(_io.BytesIO(body), allow_pickle=False) as z:
                feed = {n: z[n] for n in z.files}
        except (ValueError, OSError) as e:
            raise RequestError(400, f"malformed npz body: {e}")
        return feed, {}
    raise RequestError(
        415, f"unsupported Content-Type {ctype!r} "
             "(use application/json or application/x-npz)")


def _encode_outputs(fetch_names, outs, meta, want_npz: bool):
    """-> (body bytes, content type)."""
    if want_npz:
        buf = _io.BytesIO()
        np.savez(buf, **{n: np.asarray(o)
                         for n, o in zip(fetch_names, outs)})
        return buf.getvalue(), "application/x-npz"
    body = {
        "outputs": {n: np.asarray(o).tolist()
                    for n, o in zip(fetch_names, outs)},
        "batch": meta,
    }
    return (json.dumps(_json_safe(body)) + "\n").encode(), \
        "application/json"


class _ServingHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    inference_server: "InferenceServer" = None


class ServingHandler(mserve.MonitorHandler):
    server_version = "paddle-tpu-serving/1.0"

    # -- GET: model listing + inherited monitor routes -------------------
    def _route_get(self, url) -> bool:
        srv = self.server.inference_server
        if url.path == "/v1/models":
            self._send_json(200, {"models": srv.models_info()})
        elif url.path.startswith("/v1/models/"):
            name = url.path[len("/v1/models/"):]
            model = srv.model(name)
            if model is None:
                self._send_json(404, {"error": f"no model {name!r}"})
            else:
                self._send_json(200, model.info())
        else:
            return super()._route_get(url)
        return True

    # -- POST: prediction ------------------------------------------------
    def do_POST(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        from ..testing import chaos

        # whole-request-path chaos hooks (one flag read each when off):
        # straggler latency BEFORE admission, replica death AFTER the
        # response is written — the router sees a slow replica / a dead
        # socket on its next request, never a half-written response
        chaos.maybe_replica_latency()
        try:
            self._do_post_inner()
        finally:
            chaos.on_request_done()

    def _do_post_inner(self):
        trace = None
        try:
            t_req0 = time.perf_counter()
            url = urlparse(self.path)
            gen_name = self._generate_target(url.path)
            if gen_name is not None:
                self._do_generate(gen_name, t_req0)
                return
            name = self._predict_target(url.path)
            if name is None:
                self._send_json(404, {
                    "error": "POST /v1/models/<name>:predict "
                             "(or :generate for generation models)"})
                return
            srv = self.server.inference_server
            model = srv.model(name)
            if model is None:
                self._send_json(404, {"error": f"no model {name!r}"})
                return
            # request trace: accept the client's W3C traceparent (the
            # id correlates client and server records), generate one
            # otherwise; the root span opens at request arrival
            trace = tracing.start(
                "predict", name,
                traceparent=self.headers.get("traceparent"),
                t0=tracing.pc_to_epoch(t_req0))
            length = int(self.headers.get("Content-Length", 0))
            if length <= 0:
                raise RequestError(411, "request body required")
            body = self.rfile.read(length)
            ctype = (self.headers.get("Content-Type")
                     or "application/json").lower()
            specs = model.feed_specs
            feed, opts = _decode_inputs(body, ctype, specs)
            if trace is not None:
                trace.add_span("parse", tracing.pc_to_epoch(t_req0),
                               tracing.pc_to_epoch(time.perf_counter()),
                               bytes=length)
            q = parse_qs(url.query)
            precision = str(opts.get(
                "precision", q.get("precision", ["fp32"])[0]))
            if precision not in model.precisions:
                raise RequestError(
                    400, f"model {name!r} has no {precision!r} replica "
                         f"(available: {model.precisions})")
            try:
                timeout = float(opts.get("timeout_s", 30.0))
            except (TypeError, ValueError):
                raise RequestError(
                    400, f'"timeout_s" must be a number, got '
                         f'{opts.get("timeout_s")!r}')
            try:
                outs, meta = srv.submit(name, feed, precision=precision,
                                        timeout=timeout, trace=trace)
            except (KeyError, ValueError) as e:
                raise RequestError(400, str(e))
            except TimeoutError as e:
                raise RequestError(504, str(e))
            if trace is not None:
                # the in-response decomposition block (partial: the
                # respond span lands in the stored trace, which the
                # traceparent header points the client at)
                meta = dict(meta, trace=trace.meta_block())
            t_resp0 = time.perf_counter()
            want_npz = ("npz" in q.get("format", [""])[0]
                        or "npz" in (self.headers.get("Accept") or ""))
            data, out_ctype = _encode_outputs(
                model.fetch_names, outs, meta, want_npz)
            self.send_response(200)
            self.send_header("Content-Type", out_ctype)
            self.send_header("Content-Length", str(len(data)))
            if trace is not None:
                self.send_header("traceparent", trace.traceparent())
            self.end_headers()
            self.wfile.write(data)
            if trace is not None:
                t_done = time.perf_counter()
                trace.add_span("respond", tracing.pc_to_epoch(t_resp0),
                               tracing.pc_to_epoch(t_done),
                               bytes=len(data))
                trace.finish(status="ok",
                             t_end=tracing.pc_to_epoch(t_done))
        except RequestError as e:
            if trace is not None:
                trace.finish(status=f"error:client:{e.code}")
            self._send_json(e.code, {"error": str(e)})
        except Overloaded as e:
            # admission control shed: fail fast, tell the client when a
            # retry would realistically be served (queue-latency EWMA).
            # The batcher already closed the trace with the shed reason.
            if trace is not None:
                trace.finish(status=f"rejected:{e.reason}")
            self._send_json(
                429, {"error": str(e), "reason": e.reason,
                      "retry_after_s": round(e.retry_after_s, 4)},
                headers={"Retry-After": e.retry_after_header})
        except Unavailable as e:
            if trace is not None:
                trace.finish(status=f"rejected:{e.reason}")
            hdr = e.retry_after_header
            self._send_json(503, {"error": str(e), "reason": e.reason},
                            headers={"Retry-After": hdr} if hdr else None)
        except Exception as e:  # noqa: BLE001 — a request must not kill serving
            if trace is not None:
                trace.finish(status="error:server")
            try:
                self._send_json(500, {
                    "error": f"{type(e).__name__}: {e}"})
            except OSError:
                pass

    @staticmethod
    def _predict_target(path: str) -> Optional[str]:
        if not path.startswith("/v1/models/"):
            return None
        rest = path[len("/v1/models/"):]
        if rest.endswith(":predict"):
            return rest[:-len(":predict")]
        if rest.endswith("/predict"):
            return rest[:-len("/predict")]
        return None

    @staticmethod
    def _generate_target(path: str) -> Optional[str]:
        if not path.startswith("/v1/models/"):
            return None
        rest = path[len("/v1/models/"):]
        for suffix in (":generate", "/generate"):
            if rest.endswith(suffix):
                return rest[:-len(suffix)]
        return None

    def _do_generate(self, name: str,
                     t_req0: Optional[float] = None) -> None:
        """POST /v1/models/<name>:generate — continuous-batched
        autoregressive generation.  JSON body:
            {"prompt": [token ids...], "max_tokens": N,
             "timeout_s": S}  ->
            {"tokens": [...], "meta": {"ttft_ms", "total_ms", ...}}
        The request joins the model's in-flight decode stream at prefill
        (no retrace, no stall of other sequences) and returns when its
        sequence emits eos or exhausts its token budget."""
        srv = self.server.inference_server
        trace = None
        if t_req0 is None:
            t_req0 = time.perf_counter()
        try:
            gen = srv.generation_model(name)
            if gen is None:
                raise RequestError(
                    404, f"no generation model {name!r} "
                         f"(served: {sorted(srv._gen_models)})")
            trace = tracing.start(
                "generate", name,
                traceparent=self.headers.get("traceparent"),
                t0=tracing.pc_to_epoch(t_req0))
            length = int(self.headers.get("Content-Length", 0))
            if length <= 0:
                raise RequestError(411, "request body required")
            try:
                payload = json.loads(self.rfile.read(length).decode())
            except (ValueError, UnicodeDecodeError) as e:
                raise RequestError(400, f"malformed JSON body: {e}")
            if not isinstance(payload, dict) or "prompt" not in payload:
                raise RequestError(
                    400, 'JSON body must carry a "prompt" id list')
            if trace is not None:
                trace.add_span("parse", tracing.pc_to_epoch(t_req0),
                               tracing.pc_to_epoch(time.perf_counter()),
                               bytes=length)
            try:
                timeout = float(payload.get("timeout_s", 60.0))
            except (TypeError, ValueError):
                raise RequestError(400, '"timeout_s" must be a number')
            try:
                tokens, meta = srv.submit_generate(
                    name, payload["prompt"],
                    max_tokens=payload.get("max_tokens"),
                    timeout=timeout, trace=trace)
            except (TypeError, ValueError) as e:
                raise RequestError(400, str(e))
            except TimeoutError as e:
                raise RequestError(504, str(e))
            if trace is not None:
                meta = dict(meta or {}, trace=trace.meta_block())
            t_resp0 = time.perf_counter()
            body = json.dumps(_json_safe(
                {"tokens": [int(t) for t in tokens],
                 "meta": meta})) + "\n"
            self._send(200, body, "application/json",
                       extra_headers=({"traceparent": trace.traceparent()}
                                      if trace is not None else None))
            if trace is not None:
                t_done = time.perf_counter()
                trace.add_span("respond", tracing.pc_to_epoch(t_resp0),
                               tracing.pc_to_epoch(t_done),
                               bytes=len(body))
                trace.finish(status="ok",
                             t_end=tracing.pc_to_epoch(t_done))
        except RequestError as e:
            if trace is not None:
                trace.finish(status=f"error:client:{e.code}")
            self._send_json(e.code, {"error": str(e)})
        except (Overloaded, Unavailable) as e:
            if trace is not None:
                trace.finish(status=f"rejected:{e.reason}")
            raise
        except Exception:
            # anything else (e.g. BrokenPipeError writing the response)
            # escapes to do_POST's generic 500 path, whose own `trace`
            # local is None — close THIS trace here or it leaks open
            # (never stored, never flight-recorded) until evicted
            if trace is not None:
                trace.finish(status="error:server")
            raise

    def _send_json(self, code: int, body: dict,
                   headers: Optional[dict] = None) -> None:
        self._send(code, json.dumps(_json_safe(body)) + "\n",
                   "application/json", extra_headers=headers)


# Hot-serving policy for the static verifier (FLAGS_verify_program):
# planned warmup compiles ALWAYS verify; once any warmup in this process
# completes, the gate drops so cold-signature stragglers (already
# flight-tagged unplanned compiles) reach the trace as fast as possible.
# The flag is process-global, so the did-WE-drop-it bookkeeping is too —
# per-server (or per-model) state would let a second server's warmup, or
# a late add_model, compile unverified while believing the gate was never
# touched.  [0] = a warmup in this process dropped the gate.  The lock
# serializes whole restore->warm->drop sequences: a concurrent add_model
# finishing mid-way through another warmup's ladder would otherwise drop
# the gate under the first warmup's remaining planned compiles.
_VERIFY_DROPPED = [False]
_WARMUP_LOCK = threading.Lock()


def _warmup_verified(warm_fn) -> int:
    """Run warmup compiles with the verify gate restored (if a prior
    warmup dropped it), then drop the gate again once warm.  A warmup
    that warms zero signatures leaves an untouched gate alone — those
    signatures compile (and verify) on first request instead.  The drop
    runs in a finally: a warmup that RAISES after the gate was restored
    must not leave the whole process re-verifying (the hot-serving
    contract) — a first-warmup failure leaves the untouched gate on, as
    the process never got warm."""
    from ..flags import FLAGS

    with _WARMUP_LOCK:
        if _VERIFY_DROPPED[0] and not FLAGS.verify_program:
            FLAGS.verify_program = True
        warmed = 0
        try:
            warmed = warm_fn()
        finally:
            if (warmed or _VERIFY_DROPPED[0]) and FLAGS.verify_program:
                FLAGS.verify_program = False
                if not _VERIFY_DROPPED[0]:
                    _VERIFY_DROPPED[0] = True
                    from ..log import vlog

                    vlog(1, "serving: FLAGS_verify_program off after "
                            "warmup (%d signatures verified)", warmed)
        return warmed


class InferenceServer:
    """Load-many, serve-many: the multi-model production server."""

    def __init__(self, configs=None, host: str = "127.0.0.1",
                 port: int = 0, monitor: bool = True):
        # telemetry goes on BEFORE any model loads: load-time events (a
        # corrupted AOT bundle's inference.aot_bundle_errors counter +
        # flight event) must be counted, not lost to a late flag flip
        if monitor:
            from ..flags import FLAGS

            FLAGS.monitor = True
        self._monitor = monitor
        self.host = host
        self._requested_port = port
        self._models: Dict[str, ServingModel] = {}
        self._batchers: Dict[str, DynamicBatcher] = {}
        # decode-aware generation tier (continuous token-level batching)
        self._gen_models: Dict[str, "GenerationServingModel"] = {}
        self._gen_batchers: Dict[str, "ContinuousBatcher"] = {}
        self._httpd: Optional[_ServingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._started = False
        self._draining = False
        self._drain_reason = ""
        # server-level in-flight accounting: the FLAGS_serving_max_inflight
        # admission cap, and the drain path's "every admitted request has
        # written its response" condition
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        # scheduler-death is flight-recorded once per batcher, not once
        # per health poll
        self._reported_dead: set = set()
        for c in configs or []:
            self.add_model(c)

    # -- model management ------------------------------------------------
    def add_model(self, config: ModelConfig) -> ServingModel:
        if (config.name in self._models
                or config.name in self._gen_models):
            raise ValueError(f"model {config.name!r} already served")
        model = ServingModel(config)
        batcher = DynamicBatcher(model)
        self._models[config.name] = model
        self._batchers[config.name] = batcher
        if self._started:
            batcher.start()
            # a late-added model's planned compiles verify like any other
            _warmup_verified(model.warmup)
        return model

    def add_generation_model(self, model) -> "GenerationServingModel":
        """Serve a GenerationServingModel (serving/generation.py) at
        POST /v1/models/<name>:generate with continuous token-level
        batching.  Accepts a built model or a GenerationConfig."""
        from .generation import (ContinuousBatcher, GenerationConfig,
                                 GenerationServingModel)

        if isinstance(model, GenerationConfig):
            model = GenerationServingModel(model)
            model.init_params()
        if model.name in self._models or model.name in self._gen_models:
            raise ValueError(f"model {model.name!r} already served")
        batcher = ContinuousBatcher(model)
        self._gen_models[model.name] = model
        self._gen_batchers[model.name] = batcher
        if self._started:
            _warmup_verified(model.warmup)
            batcher.start()
        return model

    def model(self, name: str) -> Optional[ServingModel]:
        return self._models.get(name)

    def generation_model(self, name: str):
        return self._gen_models.get(name)

    @property
    def model_names(self) -> List[str]:
        return sorted(self._models) + sorted(self._gen_models)

    def models_info(self) -> List[dict]:
        return ([self._models[n].info() for n in sorted(self._models)]
                + [self._gen_models[n].info()
                   for n in sorted(self._gen_models)])

    # -- lifecycle -------------------------------------------------------
    def start(self, warmup: bool = True) -> int:
        """Boot the serving tier; returns the bound port.  Construction
        already turned FLAGS.monitor on (unless monitor=False) — a serving
        process without its latency histograms and compile counters is
        undebuggable, and the hot-path cost is the PR-1 contract (cheap
        registry writes)."""
        if self._started:
            return self.port
        from ..flags import FLAGS

        self._draining = False
        if self._monitor:
            FLAGS.monitor = True
        for b in self._batchers.values():
            b.start()
        for b in self._gen_batchers.values():
            b.start()
        self._httpd = _ServingHTTPServer(
            (self.host, int(self._requested_port)), ServingHandler)
        self._httpd.inference_server = self
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="paddle-tpu-serving-http", daemon=True)
        self._thread.start()
        self._started = True
        # /health (here AND on a separately-started monitor endpoint)
        # now reports serving readiness distinct from trainer liveness
        mserve.set_readiness_provider(self.readiness)
        if warmup:
            self.warmup()
        from ..log import vlog

        vlog(1, "serving: listening on %s:%d (models: %s)",
             self.host, self.port, ", ".join(self.model_names) or "-")
        return self.port

    def warmup(self) -> int:
        """Pre-compile every model's (precision x bucket) ladder and
        every generation model's prefill+decode pair; where the entry
        point enabled the compile cache the compiles persist across
        restarts.  Returns total signatures warmed."""
        return _warmup_verified(
            lambda: sum(m.warmup() for m in self._models.values())
            + sum(m.warmup() for m in self._gen_models.values()))

    def stop(self, timeout: float = 5.0) -> None:
        for b in self._batchers.values():
            b.stop(timeout=timeout)
        for b in self._gen_batchers.values():
            b.stop(timeout=timeout)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        if mserve._readiness_provider == self.readiness:
            mserve.set_readiness_provider(None)
        self._started = False

    @property
    def port(self) -> int:
        if self._httpd is None:
            return 0
        return self._httpd.server_address[1]

    # -- serving ---------------------------------------------------------
    def submit(self, name: str, feed, precision: str = "fp32",
               timeout: float = 30.0, trace=None):
        """Programmatic entry (the HTTP handler and in-process callers
        share the same batcher path).  `trace` is the HTTP handler's
        RequestTrace; an in-process caller with tracing on gets a root
        trace of its own (finished here — there is no respond phase)."""
        batcher = self._batchers.get(name)
        if batcher is None:
            raise KeyError(f"no model {name!r} "
                           f"(served: {self.model_names})")
        own_trace = None
        if trace is None:
            trace = own_trace = tracing.start("predict", name)
        if self._draining:
            # server-level rejects are SLO bad events like batcher-level
            # ones — burn rates must not read healthy mid-outage
            _slo_bad(name)
            tracing.reject(trace, "draining")
            raise Unavailable("server draining", reason="draining")
        self._chaos_flood(name, feed, precision)
        self._admit_inflight(batcher.retry_after, trace=trace, model=name)
        try:
            outs, meta = batcher.submit(feed, precision=precision,
                                        timeout=timeout, trace=trace)
        except Exception:
            # in-process root: close it even on paths the batcher never
            # saw (validation 4xx) — idempotent past a batcher finish
            if own_trace is not None:
                own_trace.finish(status="error")
            raise
        finally:
            self._release_inflight()
        if own_trace is not None:
            # no respond phase in-process: finish first so the meta block
            # carries the FULL decomposition (total + unattributed)
            own_trace.finish(status="ok")
            meta = dict(meta, trace=own_trace.meta_block())
        return outs, meta

    def submit_generate(self, name: str, prompt, max_tokens=None,
                        timeout: float = 60.0, trace=None):
        """Programmatic generation entry (the HTTP :generate handler and
        in-process callers share the same continuous batcher)."""
        batcher = self._gen_batchers.get(name)
        if batcher is None:
            raise KeyError(f"no generation model {name!r} "
                           f"(served: {sorted(self._gen_models)})")
        own_trace = None
        if trace is None:
            trace = own_trace = tracing.start("generate", name)
        if self._draining:
            _slo_bad(name)
            tracing.reject(trace, "draining")
            raise Unavailable("server draining", reason="draining")
        self._admit_inflight(batcher.retry_after, trace=trace, model=name)
        try:
            tokens, meta = batcher.submit(prompt, max_tokens=max_tokens,
                                          timeout=timeout, trace=trace)
        except Exception:
            if own_trace is not None:
                own_trace.finish(status="error")
            raise
        finally:
            self._release_inflight()
        if own_trace is not None:
            own_trace.finish(status="ok")
            meta = dict(meta or {}, trace=own_trace.meta_block())
        return tokens, meta

    # -- admission (server-level) ----------------------------------------
    def _admit_inflight(self, retry_after, trace=None,
                        model: Optional[str] = None) -> None:
        """Count one admitted request; at the FLAGS_serving_max_inflight
        cap, shed with 429 instead (Retry-After from the target
        batcher's queue-latency EWMA).  The count always runs (it is the
        drain path's completion condition); only the cap is flag-gated."""
        from ..flags import FLAGS

        cap = FLAGS.serving_max_inflight
        with self._inflight_lock:
            if cap > 0 and self._inflight >= cap:
                shed = True
            else:
                self._inflight += 1
                shed = False
        if shed:
            ra = retry_after()
            _record_shed("serving.inflight_shed_total", "inflight_cap",
                         ra, cap=cap)
            if model is not None:
                _slo_bad(model)
            tracing.reject(trace, "inflight_cap")
            raise Overloaded(
                f"server in-flight cap reached ({cap} admitted)",
                retry_after_s=ra, reason="inflight_cap")

    def _release_inflight(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    def _chaos_flood(self, name: str, feed, precision: str) -> None:
        """FLAGS_chaos request-flood: one deterministic burst of
        synthetic duplicate requests piles queue pressure on `name`
        (admission control must shed, not stall).  One flag read when
        chaos is off."""
        from ..testing import chaos

        burst = chaos.serve_flood()
        if not burst:
            return
        batcher = self._batchers[name]

        def _one():
            try:
                batcher.submit(feed, precision=precision, timeout=0.5)
            except Exception:  # noqa: BLE001 — synthetic load, outcome moot
                pass

        for _ in range(burst):
            threading.Thread(target=_one, daemon=True).start()

    # -- graceful drain ---------------------------------------------------
    def drain(self, timeout_s: Optional[float] = None,
              reason: str = "shutdown") -> bool:
        """Graceful drain (the SIGTERM path): flip /health readiness to
        'draining' (load balancers stop sending), reject new requests
        with 503, let in-flight and queued-admitted work complete up to
        FLAGS_serving_drain_timeout_s, then stop the serving tier.
        `reason` lands in the /health body (draining_reason) so a fleet
        router can tell a PLANNED drain (rolling restart: keep the slot,
        re-admit soon) from an unexplained one.  Returns True when every
        admitted request completed inside the budget."""
        from ..flags import FLAGS
        from ..monitor import flight

        if timeout_s is None:
            timeout_s = FLAGS.serving_drain_timeout_s
        self._drain_reason = reason
        self._draining = True
        batchers = (list(self._batchers.values())
                    + list(self._gen_batchers.values()))
        for b in batchers:
            b.begin_drain()
        flight.record("serving.drain", timeout_s=float(timeout_s),
                      models=self.model_names, reason=reason)
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        ok = True
        for b in batchers:
            ok = b.drain(max(0.0, deadline - time.monotonic())) and ok
        # admitted work has left the batchers; wait for handler threads
        # to finish writing responses (the in-flight count spans the
        # whole submit), then a short grace for the final socket writes
        while True:
            with self._inflight_lock:
                n = self._inflight
            if n == 0:
                break
            if time.monotonic() >= deadline:
                ok = False
                break
            time.sleep(0.02)
        time.sleep(0.1)
        # a stuck batch past the budget is ABANDONED (daemon scheduler),
        # not waited out: the drain deadline is the whole point
        self.stop(timeout=max(0.5, deadline - time.monotonic()))
        return ok

    @property
    def draining(self) -> bool:
        return self._draining

    def readiness(self) -> dict:
        models = {
            n: m.readiness_detail()
            for n, m in self._models.items()
        }
        models.update({
            n: m.readiness_detail()
            for n, m in self._gen_models.items()
        })
        all_models = list(self._models.values()) \
            + list(self._gen_models.values())
        ready = bool(all_models) and all(m.ready for m in all_models)
        # chaos probe-flap rides the readiness verdict itself (one flag
        # read when chaos is off): the flapped probe reports not_ready
        # while every model detail still says ready/warming — exactly the
        # flicker a router's eviction hysteresis must ride out
        from ..testing import chaos

        ready = chaos.probe_flap(ready)
        out = {
            "ready": ready,
            "models": models,
        }
        if self._draining:
            out["ready"] = False
            out["draining"] = True
            out["draining_reason"] = self._drain_reason
        # liveness satellite: a dead scheduler thread leaves a healthy-
        # LOOKING server that times out every request — name it so the
        # probe can evict the process
        dead = sorted(
            n for n, b in {**self._batchers, **self._gen_batchers}.items()
            if not b.scheduler_alive)
        if dead:
            out["ready"] = False
            out["scheduler_dead"] = dead
            from ..monitor import flight

            for n in dead:
                if n not in self._reported_dead:
                    self._reported_dead.add(n)
                    flight.record("serving.scheduler_dead", model=n,
                                  fatal=True)
        return out
