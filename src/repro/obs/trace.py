"""Structured low-overhead tracing (DESIGN.md §8).

One :class:`Obs` hub per engine bundles a tracer, a flight recorder, and
the exporters; the serving server and async runtime share the engine's
hub so one event stream sees offer → assemble → handoff → engine stages
→ merge → fan-out across threads.

Spans are context managers timed with ``perf_counter`` and emitted as
Chrome ``trace_event`` "complete" events (``ph: "X"``, microsecond
``ts``/``dur``) into a bounded ring — no I/O, no locks on the hot path.
``Tracer.context(step=..., batch=...)`` scopes thread-local ids that
every span emitted inside inherits into ``args``, which is what lets a
post-mortem follow one micro-batch offer→delta across the ingress and
executor threads.

The enabled tracer also opens a ``jax.profiler.TraceAnnotation`` per
span (name only), so a profiler capture shows the program's spans on
their thread's host line above the device ops.

A traced step owns a :class:`StepScope` bound to the calling thread
(``begin_step`` → ``take_step``). Three things fill it besides the
engine's own stage timers: :func:`to_host` / :func:`uploaded` count the
bytes the step moves across the host link; a process-wide
``jax.monitoring`` listener turns every trace, lowering and backend
compile (or cache load) into an ``engine/compile`` span; a
``gc.callbacks`` hook turns every generation-2 collection into an
``engine/gc`` span. Both hooks are registered once, while at least one
enabled hub lives, and route each event to the step of the thread it
fired on (outside a step: to the newest enabled hub, span only).

Zero-cost disabled: ``Obs(ObsConfig())`` wires the :data:`NULL_TRACER`
singleton whose ``span()`` returns a shared no-op context manager,
registers no hook, and every *extra* device fence in the engine sits
behind ``if obs.enabled``. The disabled path is pinned bitwise + by
compiled-trace-count in ``tests/test_obs.py``.
"""

from __future__ import annotations

import gc
import threading
import time
import weakref
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config.base import ObsConfig
from repro.obs import export as _export
from repro.obs.flight import FlightRecorder


class _NullSpan:
    """Shared no-op span: the whole disabled-tracing fast path."""

    __slots__ = ()
    dur_s = 0.0

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "dur_s", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0
        self.dur_s = 0.0
        self._ann = None

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.dur_s = t1 - self._t0
        self._tracer._emit(self.name, "X", self._t0, t1, self.args)
        return False


class StepScope:
    """What one traced step measured beyond its stage spans: ``stage``
    (the engine's per-stage seconds, plus ``compile`` and ``gc`` filled by
    the hooks) and ``counts`` (``d2h_bytes``, ``h2d_bytes``, backend
    ``compiles``). Each device array counts once per step, the first time
    the host reads it: jax keeps the host copy, so later reads move
    nothing."""

    __slots__ = ("tracer", "stage", "counts", "_seen", "_compile_iv")

    def __init__(self, tracer: "Tracer"):
        self.tracer = tracer
        self.stage: Dict[str, float] = {"compile": 0.0, "gc": 0.0}
        self.counts: Dict[str, int] = {"d2h_bytes": 0, "h2d_bytes": 0,
                                       "compiles": 0}
        # id → array, held until the step ends so an id is never reused
        self._seen: Dict[int, Any] = {}
        # disjoint compile intervals: nested events (a trace inside a
        # trace) add only the time no earlier event of the step covered
        self._compile_iv: List[Tuple[float, float]] = []

    def pulled(self, x: Any) -> None:
        if isinstance(x, jax.Array) and id(x) not in self._seen:
            self._seen[id(x)] = x
            self.counts["d2h_bytes"] += int(x.nbytes)

    def add_compile(self, t0: float, t1: float, backend: bool) -> None:
        covered = sum(max(0.0, min(t1, b) - max(t0, a))
                      for a, b in self._compile_iv)
        self.stage["compile"] += max(t1 - t0 - covered, 0.0)
        keep = []
        for a, b in self._compile_iv:
            if b < t0 or a > t1:
                keep.append((a, b))
            else:
                t0, t1 = min(t0, a), max(t1, b)
        keep.append((t0, t1))
        self._compile_iv = keep
        if backend:
            self.counts["compiles"] += 1


class _Ctx:
    """Scopes thread-local span annotations (step/batch ids)."""

    __slots__ = ("_tls", "_kw", "_saved")

    def __init__(self, tls: threading.local, kw: Dict[str, Any]):
        self._tls = tls
        self._kw = kw
        self._saved: Dict[str, Any] = {}

    def __enter__(self) -> "_Ctx":
        ids = getattr(self._tls, "ids", None)
        self._saved = ids
        merged = dict(ids) if ids else {}
        merged.update(self._kw)
        self._tls.ids = merged
        return self

    def __exit__(self, *exc) -> bool:
        self._tls.ids = self._saved
        return False


class Tracer:
    """Enabled tracer: bounded event ring + per-step grouping."""

    enabled = True

    def __init__(self, cfg: ObsConfig):
        self.cfg = cfg
        self._epoch = time.perf_counter()
        self._events: Deque[Dict[str, Any]] = deque(maxlen=cfg.event_cap)
        self._meta: List[Dict[str, Any]] = []
        self._tls = threading.local()
        self._tids: Dict[int, int] = {}
        self._lock = threading.Lock()
        self.n_spans = 0
        # out-of-step spans also stream here when a flight recorder is
        # attached (Obs wires this to FlightRecorder.loose)
        self.loose_sink: Optional[Deque[Dict[str, Any]]] = None

    def span(self, name: str, **args: Any) -> _Span:
        return _Span(self, name, args)

    def context(self, **ids: Any) -> _Ctx:
        return _Ctx(self._tls, ids)

    def instant(self, name: str, **args: Any) -> None:
        t = time.perf_counter()
        self._emit(name, "i", t, t, args)

    # -- step grouping (flight recorder) ---------------------------------

    def begin_step(self, step: int) -> StepScope:
        """Start grouping the calling thread's spans into a step, and
        bind the step's :class:`StepScope` to the thread."""
        self._tls.step_events = []
        scope = StepScope(self)
        _THREAD.scope = scope
        return scope

    def take_step(self) -> List[Dict[str, Any]]:
        events = getattr(self._tls, "step_events", None) or []
        self._tls.step_events = None
        scope = getattr(_THREAD, "scope", None)
        if scope is not None and scope.tracer is self:
            _THREAD.scope = None
        return events

    # -- emission --------------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids) + 1)
            self._meta.append({
                "name": "thread_name", "ph": "M", "ts": 0.0, "pid": 1,
                "tid": tid,
                "args": {"name": threading.current_thread().name},
            })
        return tid

    def _emit(self, name: str, ph: str, t0: float, t1: float,
              args: Dict[str, Any]) -> None:
        tls = self._tls
        ids = getattr(tls, "ids", None)
        if ids:
            merged = dict(ids)
            merged.update(args)
            args = merged
        ev: Dict[str, Any] = {
            "name": name,
            "cat": name.split("/", 1)[0],
            "ph": ph,
            "ts": round(1e6 * (t0 - self._epoch), 3),
            "pid": 1,
            "tid": self._tid(),
            "args": args,
        }
        if ph == "X":
            ev["dur"] = round(1e6 * (t1 - t0), 3)
        self.n_spans += 1
        self._events.append(ev)
        step_events = getattr(tls, "step_events", None)
        if step_events is not None:
            step_events.append(ev)
        elif self.loose_sink is not None:
            self.loose_sink.append(ev)

    def events(self) -> List[Dict[str, Any]]:
        """Metadata + ring contents, export-ready."""
        return list(self._meta) + list(self._events)


class NullTracer:
    """Disabled tracer: every call is a constant-time no-op."""

    enabled = False
    n_spans = 0

    def span(self, name: str, **args: Any) -> _NullSpan:
        return NULL_SPAN

    def context(self, **ids: Any) -> _NullSpan:
        return NULL_SPAN

    def instant(self, name: str, **args: Any) -> None:
        return None

    def begin_step(self, step: int) -> None:
        return None

    def take_step(self) -> List[Dict[str, Any]]:
        return []

    def events(self) -> List[Dict[str, Any]]:
        return []


NULL_TRACER = NullTracer()


# -- process-wide hooks: host-link bytes, compiles, collections ---------------

# the traced step the calling thread is in (a StepScope), if any
_THREAD = threading.local()
# weak references to the live enabled tracers, oldest first; the hooks are
# registered exactly while this is non-empty
_HUBS: List["weakref.ref[Tracer]"] = []
_HOOKS_LOCK = threading.RLock()
_hooked = False

# jax.monitoring duration events of a jit's way to an executable
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # a backend compile, or a load of the program from the persistent cache
    "/jax/core/compile/backend_compile_duration": "backend",
}


def to_host(x: Any, dtype: Any = None) -> np.ndarray:
    """``np.asarray(x[, dtype])``, counted as ``d2h_bytes`` of the calling
    thread's traced step when ``x`` is a device array (once per array and
    step). Without an enabled hub this is ``np.asarray`` alone."""
    if _HUBS:
        scope = getattr(_THREAD, "scope", None)
        if scope is not None:
            scope.pulled(x)
    return np.asarray(x) if dtype is None else np.asarray(x, dtype)


def uploaded(tree: Any) -> Any:
    """Count the device arrays of ``tree`` (just made from host data) as
    ``h2d_bytes`` of the calling thread's traced step; returns ``tree``."""
    if _HUBS:
        scope = getattr(_THREAD, "scope", None)
        if scope is not None:
            scope.counts["h2d_bytes"] += sum(
                int(a.nbytes) for a in jax.tree.leaves(tree)
                if isinstance(a, jax.Array))
    return tree


def _route() -> Tuple[Optional[Tracer], Optional[StepScope]]:
    """The calling thread's traced step and its tracer; outside a step,
    the newest live tracer and no step."""
    scope = getattr(_THREAD, "scope", None)
    if scope is not None:
        return scope.tracer, scope
    for ref in reversed(_HUBS):
        tracer = ref()
        if tracer is not None:
            return tracer, None
    return None, None


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    phase = COMPILE_PHASES.get(event)
    if phase is None:
        return
    tracer, scope = _route()
    if tracer is None:
        return
    t1 = time.perf_counter()
    t0 = t1 - duration
    tracer._emit("engine/compile", "X", t0, t1,
                 {"fun_name": str(kw.get("fun_name", "")), "phase": phase})
    if scope is not None:
        scope.add_compile(t0, t1, phase == "backend")


class _Collection:
    """The generation-2 collection in progress (one at a time)."""

    t0 = 0.0
    ann: Optional[TraceAnnotation] = None


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if info.get("generation") != 2:
        return
    if phase == "start":
        if _route()[0] is not None:
            _Collection.ann = TraceAnnotation("engine/gc")
            _Collection.ann.__enter__()
            _Collection.t0 = time.perf_counter()
        return
    if _Collection.ann is None:
        return
    t1 = time.perf_counter()
    _Collection.ann.__exit__(None, None, None)
    _Collection.ann = None
    tracer, scope = _route()
    if tracer is None:
        return
    tracer._emit("engine/gc", "X", _Collection.t0, t1,
                 {"collected": info.get("collected", 0),
                  "uncollectable": info.get("uncollectable", 0)})
    if scope is not None:
        scope.stage["gc"] += t1 - _Collection.t0


def _attach(tracer: Tracer) -> "weakref.ref[Tracer]":
    global _hooked
    ref = weakref.ref(tracer)
    with _HOOKS_LOCK:
        _HUBS.append(ref)
        if not _hooked:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            gc.callbacks.append(_on_gc)
            _hooked = True
    return ref


def _release(ref: "weakref.ref[Tracer]") -> None:
    """Drop a hub (closed or collected); the last one takes the hooks."""
    global _hooked
    with _HOOKS_LOCK:
        _HUBS[:] = [r for r in _HUBS if r is not ref and r() is not None]
        if not _HUBS and _hooked:
            jax.monitoring.unregister_event_duration_listener(_on_duration)
            if _on_gc in gc.callbacks:
                gc.callbacks.remove(_on_gc)
            _hooked = False


def hooks_registered() -> bool:
    """Whether the compile listener and the GC callback are installed."""
    return _hooked


class Obs:
    """Observability hub: tracer + flight recorder + exporters."""

    def __init__(self, cfg: Optional[ObsConfig] = None):
        self.cfg = cfg if cfg is not None else ObsConfig()
        self.enabled = bool(self.cfg.enabled)
        self.tracer = Tracer(self.cfg) if self.enabled else NULL_TRACER
        # the compile/GC hooks live while an enabled hub does: released
        # by close() or when the tracer is collected, whichever is first
        self._release = (weakref.finalize(self.tracer, _release,
                                          _attach(self.tracer))
                         if self.enabled else None)
        self.flight: Optional[FlightRecorder] = None
        if self.enabled and self.cfg.flight_n > 0:
            self.flight = FlightRecorder(self.cfg.flight_n,
                                         self.cfg.flight_path)
            self.tracer.loose_sink = self.flight.loose
        # bound the delegates so the disabled hot path is one attribute
        # load + one constant return, with no Obs-level frame
        self.span = self.tracer.span
        self.context = self.tracer.context
        self.instant = self.tracer.instant

    # -- step lifecycle ---------------------------------------------------

    def begin_step(self, step: int) -> Optional[StepScope]:
        """Open a traced step on the calling thread; returns its
        :class:`StepScope` (None with tracing off)."""
        return self.tracer.begin_step(step)

    def end_step(self, step: int) -> None:
        events = self.tracer.take_step()
        if self.flight is not None and events:
            self.flight.push(step, events)

    def observe_e2e(self, e2e_ms: float) -> Optional[str]:
        """SLO trigger: dump the flight ring when an end-to-end latency
        sample crosses the configured threshold."""
        if (self.flight is not None and self.cfg.slo_e2e_ms > 0
                and e2e_ms > self.cfg.slo_e2e_ms):
            return self.flight.dump(
                reason=f"slo:e2e {e2e_ms:.1f}ms > {self.cfg.slo_e2e_ms:g}ms",
                triggered=True)
        return None

    def flight_dump(self, reason: str = "manual",
                    path: Optional[str] = None,
                    triggered: bool = False) -> Optional[str]:
        if self.flight is None:
            return None
        return self.flight.dump(reason=reason, path=path,
                                triggered=triggered)

    def close(self) -> None:
        """Release this hub's share of the process-wide compile and GC
        hooks (the last enabled hub to close removes them)."""
        if self._release is not None:
            self._release()

    # -- export -----------------------------------------------------------

    def export(self, snapshot: Optional[Dict[str, Any]] = None
               ) -> Dict[str, str]:
        """Write every configured artifact; returns ``{kind: path}``.

        ``trace_path`` prefix → ``<prefix>.jsonl`` (span stream) and
        ``<prefix>.json`` (Perfetto-loadable); ``prometheus_path`` +
        a telemetry ``snapshot`` → text-format gauges.
        """
        out: Dict[str, str] = {}
        if self.enabled and self.cfg.trace_path:
            events = self.tracer.events()
            out["trace_jsonl"] = _export.write_jsonl(
                events, self.cfg.trace_path + ".jsonl")
            out["trace_chrome"] = _export.write_chrome(
                events, self.cfg.trace_path + ".json")
        if self.cfg.prometheus_path and snapshot is not None:
            out["prometheus"] = _export.write_prometheus(
                snapshot, self.cfg.prometheus_path)
        return out
