"""Tracing + profiling.

Capability-equivalent of the reference's tracing/profiling stack
(reference: python/ray/util/tracing/tracing_helper.py — opt-in span
decorators around .remote() and execution, context propagated in task
specs; _private/profiling.py + `ray timeline` for chrome traces;
dashboard's py-spy hooks for CPU profiles):

- span(name): context manager with two sinks. Every span enters a
  jax.profiler.TraceAnnotation("ray_tpu:<name>"), so a running profiler
  session (profile_tpu below, or anybody's start_trace) shows it on the
  clock of the device's operations; and, when a hook is registered or a
  runtime keeps a timeline, it records a chrome-trace span into the
  runtime's task-event buffer, with parent links via a contextvar.
  With neither, a span costs the annotation alone.
  Spans root a Dapper-style trace: the first span in a context mints a
  trace_id, nested spans inherit it, and trace_context() re-installs a
  propagated (trace_id, parent_span_id) pair on the far side of a
  process boundary so worker-side spans link into the driver's trace.
- setup_tracing(hook): register an exporter callback invoked with every
  finished span (the reference's _tracing_startup_hook analog); also
  reads RAY_TPU_TRACING_HOOK="module:function" at init and, when
  RAY_TPU_OTLP_ENDPOINT is set, auto-registers the OTLP exporter —
  workers and daemons inherit the env from the driver, so one variable
  wires the whole cluster.
- trace_sampled(trace_id): head-based sampling (RAY_TPU_TRACE_SAMPLE).
  The decision is a pure hash of the trace id, so every process in the
  cluster independently reaches the same keep/drop verdict and a trace
  is exported whole or not at all.
- OTLPSpanExporter: dependency-free OTLP/HTTP JSON exporter (stdlib
  urllib), batched with a background flusher; the analog of the
  reference's opentelemetry exporter wiring without the dependency.
- profile_tpu(logdir): the TPU-native profiler — wraps jax.profiler
  (xprof/tensorboard trace), replacing the reference's py-spy path.
- export_chrome_trace(path): dump everything `ray timeline`-style.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import os
import sys
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional

_current_span: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("ray_tpu_span", default=None)
_current_trace: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("ray_tpu_trace", default=None)

_hooks: List[Callable[[Dict[str, Any]], None]] = []
_hooks_lock = threading.Lock()
_env_hook_added = False
# enable_timeline value before the first setup_tracing() flipped it;
# None = tracing never set up (nothing to restore).
_prev_enable_timeline: Optional[bool] = None

# Chrome-trace `pid` for spans from this process. The driver keeps the
# stable label "driver"; worker processes call set_process_label() at
# startup so a merged trace separates processes.
_process_label: str = "driver"

# Process-wide OTLP exporter auto-registered from RAY_TPU_OTLP_ENDPOINT
# by setup_tracing(); torn down by clear_tracing().
_otlp_exporter: Optional["OTLPSpanExporter"] = None


def set_process_label(label: str) -> None:
    global _process_label
    _process_label = str(label)


def trace_sampled(trace_id: Optional[str],
                  rate: Optional[float] = None) -> bool:
    """Head-based sampling verdict for a trace id.

    Deterministic and PYTHONHASHSEED-independent (sha1, not hash()), so
    the driver, every worker, and every daemon agree on keep-vs-drop for
    the same trace_id without coordination — a sampled-out trace
    produces zero spans anywhere, a sampled-in trace stays complete.
    Rate comes from RAY_TPU_TRACE_SAMPLE (default 1.0 = keep all).
    """
    if rate is None:
        raw = os.environ.get("RAY_TPU_TRACE_SAMPLE")
        if not raw:
            return True
        try:
            rate = float(raw)
        except ValueError:
            return True
    rate = min(1.0, max(0.0, float(rate)))
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    if not trace_id:
        return True
    bucket = int(hashlib.sha1(trace_id.encode()).hexdigest()[:8], 16)
    return bucket / 0xFFFFFFFF < rate


def setup_tracing(hook: Optional[Callable[[Dict[str, Any]], None]] = None
                  ) -> None:
    """Enable span export. `hook(span_dict)` runs for every finished
    span. Also honors RAY_TPU_TRACING_HOOK=module:function and
    RAY_TPU_OTLP_ENDPOINT=http://collector:4318/v1/traces."""
    from .._private.config import config

    global _env_hook_added, _prev_enable_timeline, _otlp_exporter

    if _prev_enable_timeline is None:
        _prev_enable_timeline = bool(config.enable_timeline)
    config.enable_timeline = True
    with _hooks_lock:
        if hook is not None:
            _hooks.append(hook)
    env = os.environ.get("RAY_TPU_TRACING_HOOK")
    if env and ":" in env and not _env_hook_added:
        mod, _, fn = env.partition(":")
        import importlib

        with _hooks_lock:
            _hooks.append(getattr(importlib.import_module(mod), fn))
            _env_hook_added = True
    endpoint = os.environ.get("RAY_TPU_OTLP_ENDPOINT")
    if endpoint and _otlp_exporter is None:
        exporter = OTLPSpanExporter(endpoint)
        with _hooks_lock:
            _hooks.append(exporter.export)
        _otlp_exporter = exporter


def clear_tracing() -> None:
    """Fully reset exporter state: drop all hooks (including the env
    hook, so a later setup_tracing() re-registers it), flush + drop the
    OTLP exporter, and restore enable_timeline to its pre-setup value."""
    from .._private.config import config

    global _env_hook_added, _prev_enable_timeline, _otlp_exporter
    with _hooks_lock:
        _hooks.clear()
        _env_hook_added = False
    exporter, _otlp_exporter = _otlp_exporter, None
    if exporter is not None:
        exporter.shutdown()
    if _prev_enable_timeline is not None:
        config.enable_timeline = _prev_enable_timeline
        _prev_enable_timeline = None


PROFILER_PREFIX = "ray_tpu:"


def name_thread(name: str) -> None:
    """Give the calling thread `name` (its first 15 bytes) as the OS
    knows it. A profiler session labels a thread's line of spans by that
    name, and Python leaves every thread its process's: two threads of
    one name read as one timeline, and their spans nest into each other.
    Call it before the thread's first span. Linux only (the thread's
    `comm` file); elsewhere, and on any failure, nothing happens."""
    try:
        with open(f"/proc/self/task/{threading.get_native_id()}/comm",
                  "wb") as f:
            f.write(name.encode()[:15])
    except OSError:
        pass


def _recording() -> bool:
    """Whether a finished span has anywhere to go besides the profiler:
    an exporter hook, or the timeline of a runtime in this process."""
    if _hooks:
        return True
    runtime = sys.modules.get("ray_tpu.core.runtime")
    if runtime is None or runtime.global_runtime_or_none() is None:
        return False
    from .._private.config import config

    return bool(config.enable_timeline)


class span:
    """Record a span: `with span("engine.tick", tick=3): ...`. Two sinks.

    - Always, `jax.profiler.TraceAnnotation("ray_tpu:" + name,
      **attributes)`: while a profiler session runs (`profile_tpu`, or
      anybody's `start_trace`) the span lands in its `.xplane.pb` beside
      the device's operations, on their clock, attributes as the event's
      stats (keep commas out of string values: the profiler cuts there).
      With no session it costs an object and a flag test; a process
      that never imported jax has no session and skips it.
    - Under `setup_tracing()` or a runtime's timeline, a chrome-trace
      event with ids: nests via contextvar parent links, the outermost
      span in a context roots a new trace id. `with ... as span_id`
      gives the id.

    With neither a hook nor a timeline to record into, nothing else
    happens and the id is `None`: no ids, no environment read, no hash.
    `set()` adds attributes that are known only before the span ends.

    `cpu=True` adds the attribute `cpu_us` at exit, to both sinks: the
    CPU time this thread spent inside the span, its children's included
    (`time.thread_time_ns`). The span's length less `cpu_us` is time the
    thread stood blocked: in a call that waits, or for the interpreter
    lock. Two clock reads; off by default.
    """

    __slots__ = ("name", "category", "attributes", "_annotation", "_id",
                 "_parent", "_trace_id", "_tokens", "_ts", "_t0", "_cpu0")

    def __init__(self, name: str, category: str = "span", cpu: bool = False,
                 **attributes):
        self.name, self.category = name, category
        self.attributes = attributes
        self._annotation = self._id = None
        self._cpu0 = 0 if cpu else None

    def set(self, **attributes) -> None:
        self.attributes.update(attributes)
        if self._annotation is not None:
            self._annotation.set_metadata(**attributes)

    def __enter__(self) -> Optional[str]:
        # Looked up, never imported: a process that has not imported
        # jax's profiler has no session, and one that is importing it on
        # another thread right now has half a module.
        annotation = getattr(sys.modules.get("jax.profiler"),
                             "TraceAnnotation", None)
        if annotation is not None:
            self._annotation = annotation(
                PROFILER_PREFIX + self.name, **self.attributes)
            self._annotation.__enter__()
        if _recording():
            self._id = uuid.uuid4().hex[:16]
            self._parent = _current_span.get()
            self._trace_id = _current_trace.get()
            trace_token = None
            if self._trace_id is None:
                self._trace_id = uuid.uuid4().hex[:16]
                trace_token = _current_trace.set(self._trace_id)
            self._tokens = (_current_span.set(self._id), trace_token)
            # `ts` is wall time so that merged chrome traces line up
            # across processes; the duration comes from a clock that
            # cannot step.
            self._ts, self._t0 = time.time(), time.monotonic()
        # Read last here and first at exit: inside both sinks' spans.
        if self._cpu0 is not None:
            self._cpu0 = time.thread_time_ns()
        return self._id

    def __exit__(self, *exc) -> bool:
        if self._cpu0 is not None:
            self.set(cpu_us=(time.thread_time_ns() - self._cpu0) // 1000)
        if self._id is not None:
            dur = time.monotonic() - self._t0
            span_token, trace_token = self._tokens
            _current_span.reset(span_token)
            if trace_token is not None:
                _current_trace.reset(trace_token)
            # Record-time sampling gate: the trace id always propagates
            # so every hop can evaluate the same deterministic verdict;
            # only the recording is skipped.
            if trace_sampled(self._trace_id):
                _record({
                    "name": self.name, "cat": self.category, "ph": "X",
                    "ts": self._ts * 1e6, "dur": dur * 1e6,
                    "pid": _process_label, "tid": f"span:{self._id}",
                    "args": {"parent": self._parent,
                             "trace_id": self._trace_id,
                             **self.attributes},
                })
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


@contextlib.contextmanager
def trace_context(trace_id: Optional[str],
                  parent_span_id: Optional[str] = None):
    """Re-enter a propagated trace on the receiving side of a process
    or task boundary: spans opened inside the block carry `trace_id`
    and parent-link to `parent_span_id`."""
    if trace_id is None:
        yield
        return
    trace_token = _current_trace.set(trace_id)
    span_token = _current_span.set(parent_span_id) \
        if parent_span_id is not None else None
    try:
        yield
    finally:
        if span_token is not None:
            _current_span.reset(span_token)
        _current_trace.reset(trace_token)


def _record(ev: Dict[str, Any]) -> None:
    from ..core.runtime import global_runtime_or_none

    rt = global_runtime_or_none()
    if rt is not None:
        rt.events.record_raw(ev)
    with _hooks_lock:
        hooks = list(_hooks)
    for h in hooks:
        try:
            h(ev)
        except Exception:  # noqa: BLE001 - exporters must not break apps
            pass


class OTLPSpanExporter:
    """Dependency-free OTLP/HTTP JSON span exporter (stdlib urllib).

    Spans batch in memory and a background thread flushes them to the
    collector endpoint; flush() forces a drain (tests and shutdown).
    Network errors are swallowed — an unreachable collector must never
    affect the application.
    """

    def __init__(self, endpoint: str, *,
                 service_name: str = "ray_tpu",
                 batch_size: int = 64,
                 flush_interval_s: float = 2.0) -> None:
        self.endpoint = endpoint
        self.service_name = service_name
        self.batch_size = max(1, int(batch_size))
        self._buf: List[Dict[str, Any]] = []
        self._buf_lock = threading.Lock()
        self._stop = threading.Event()
        self._flusher = threading.Thread(
            target=self._flush_loop, args=(float(flush_interval_s),),
            name="ray-tpu-otlp-flush", daemon=True)
        self._flusher.start()

    def export(self, ev: Dict[str, Any]) -> None:
        """Span hook: enqueue one finished span (chrome-ev dict)."""
        flush_now = False
        with self._buf_lock:
            self._buf.append(ev)
            if len(self._buf) >= self.batch_size:
                flush_now = True
        if flush_now:
            self.flush()

    def flush(self) -> int:
        """Drain the buffer to the collector. → spans posted."""
        with self._buf_lock:
            batch, self._buf = self._buf, []
        if not batch:
            return 0
        self._post(batch)
        return len(batch)

    def shutdown(self) -> None:
        self._stop.set()
        self.flush()
        self._flusher.join(timeout=2)

    def _flush_loop(self, interval_s: float) -> None:
        while not self._stop.wait(max(0.1, interval_s)):
            try:
                self.flush()
            except Exception:  # noqa: BLE001 - exporter must not die
                pass

    # -- OTLP/HTTP JSON encoding --------------------------------------

    def _post(self, batch: List[Dict[str, Any]]) -> None:
        import json
        import urllib.request

        try:
            payload = json.dumps(self._encode(batch)).encode()
            req = urllib.request.Request(
                self.endpoint, data=payload,
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=5):
                pass
        except Exception:  # noqa: BLE001 - collector down: drop batch
            pass

    def _encode(self, batch: List[Dict[str, Any]]) -> Dict[str, Any]:
        spans = [self._encode_span(ev) for ev in batch]
        resource_attrs = [
            {"key": "service.name",
             "value": {"stringValue": self.service_name}},
            {"key": "process.label",
             "value": {"stringValue": str(_process_label)}},
        ]
        return {"resourceSpans": [{
            "resource": {"attributes": resource_attrs},
            "scopeSpans": [{
                "scope": {"name": "ray_tpu"},
                "spans": spans,
            }],
        }]}

    @staticmethod
    def _encode_span(ev: Dict[str, Any]) -> Dict[str, Any]:
        args = ev.get("args") or {}
        tid = str(ev.get("tid") or "")
        span_id = tid.split(":", 1)[1] if ":" in tid else tid
        start_ns = int(float(ev.get("ts", 0)) * 1000)  # µs → ns
        end_ns = start_ns + int(float(ev.get("dur", 0)) * 1000)
        attributes = [
            {"key": "category",
             "value": {"stringValue": str(ev.get("cat", ""))}},
        ]
        for k, v in args.items():
            if k in ("parent", "trace_id"):
                continue
            attributes.append(
                {"key": str(k), "value": {"stringValue": str(v)}})
        out = {
            "traceId": str(args.get("trace_id") or "").rjust(32, "0"),
            "spanId": span_id.rjust(16, "0"),
            "name": str(ev.get("name", "")),
            "kind": 1,  # SPAN_KIND_INTERNAL
            "startTimeUnixNano": str(start_ns),
            "endTimeUnixNano": str(end_ns),
            "attributes": attributes,
        }
        parent = args.get("parent")
        if parent:
            out["parentSpanId"] = str(parent).rjust(16, "0")
        return out


def get_otlp_exporter() -> Optional[OTLPSpanExporter]:
    return _otlp_exporter


def flush_otlp() -> int:
    """Force-drain the env-registered OTLP exporter. → spans posted."""
    exporter = _otlp_exporter
    return exporter.flush() if exporter is not None else 0


def parse_traceparent(header: Optional[str]
                      ) -> Optional[Dict[str, str]]:
    """Parse a W3C `traceparent` header (version 00:
    `00-<32hex trace-id>-<16hex parent-id>-<2hex flags>`) into
    {"trace_id", "parent_span_id", "flags"}, or None if malformed /
    all-zero ids (the spec says treat those as absent). Internal ids
    are 16-hex, so the incoming 32-hex trace id is kept verbatim —
    trace_context() and the OTLP exporter both handle either width."""
    if not header or not isinstance(header, str):
        return None
    parts = header.strip().lower().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, parent_id, flags = parts[0], parts[1], \
        parts[2], parts[3]
    if version == "ff" or len(version) != 2:
        return None
    if len(trace_id) != 32 or len(parent_id) != 16:
        return None
    try:
        int(version, 16)
        int(trace_id, 16)
        int(parent_id, 16)
        int(flags[:2], 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or parent_id == "0" * 16:
        return None
    return {"trace_id": trace_id, "parent_span_id": parent_id,
            "flags": flags[:2]}


def format_traceparent(trace_id: Optional[str] = None,
                       span_id: Optional[str] = None,
                       sampled: bool = True) -> Optional[str]:
    """Format the current (or given) trace/span as a W3C `traceparent`
    for outbound propagation / response echo. Internal 16-hex ids are
    left-padded to the wire widths. → None when there is no trace."""
    trace_id = trace_id or _current_trace.get()
    span_id = span_id or _current_span.get()
    if not trace_id or not span_id:
        return None
    t = str(trace_id).rjust(32, "0")[-32:]
    s = str(span_id).rjust(16, "0")[-16:]
    return f"00-{t}-{s}-{'01' if sampled else '00'}"


def current_span_id() -> Optional[str]:
    return _current_span.get()


def current_trace_id() -> Optional[str]:
    return _current_trace.get()


def export_chrome_trace(path: str) -> int:
    """Dump all runtime events (tasks + spans) as chrome://tracing JSON.
    → number of events."""
    import json

    from ..core.runtime import global_runtime

    events = global_runtime().timeline()
    with open(path, "w") as f:
        json.dump(events, f)
    return len(events)


@contextlib.contextmanager
def profile_tpu(logdir: str, *, host_tracer_level: int = 2):
    """TPU-native profiler capture: everything inside the block is
    recorded by the jax/XLA profiler (view with tensorboard/xprof —
    MXU utilisation, HBM traffic, ICI transfers), and every `span()`
    that runs meanwhile is in the same trace as a `ray_tpu:<name>` host
    event. Replaces the reference's py-spy/memray host profiling for
    device work."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
