"""Request tracing: span trees across threads and processes.

A :class:`Tracer` mints :class:`Span` objects — named, timed on
:mod:`repro.obs.clock`'s monotonic source, carrying a ``trace_id``
shared by every span of one request and a ``parent_id`` forming the
tree. Finished spans are kept in a bounded per-trace store so a
request id can be resolved to its full tree afterwards (the gateway's
``GET /v1/traces/<id>``).

Span names map onto the paper's Table-3 latency vocabulary: spans the
client wants projected into a :class:`~repro.core.metrics.Breakdown`
carry a ``component`` attribute naming the Table-3 column —

* ``token``     — tokenize (Step 1)
* ``bloom``     — catalog probe / fetch planning (Step 2)
* ``redis``     — cache-fabric transfer time (per-(peer, range)
  attempt spans, est-vs-actual as attributes)
* ``p_decode``  — prefill: full local, resumed, or streamed (Step 3)
* ``r_decode``  — response decode (Step 4)
* ``sample``    — sampling

so ``InferResult.wall`` is a *projection* of the span tree
(``Breakdown.from_spans``), not a second bookkeeping path.

**Cross-thread handoff is explicit**: ``span.ctx`` is a picklable
:class:`SpanContext`; another thread passes it as ``parent=`` (or
enters ``tracer.attach(span)`` to adopt it as the ambient parent).
Nothing leaks through thread ancestry.

**Cross-process propagation** rides the request payload envelope:
:func:`inject_trace` adds a ``_trace`` key to an op payload,
:func:`extract_trace` pops it server-side. Peers that predate tracing
simply ignore the key (every handler reads named fields) and return no
``_spans`` — version negotiation by construction, tested both ways in
``tests/test_obs.py``. A trace-aware server times its handler and
returns compact span *descriptors* (``{"name", "rel_s", "dur_s",
"attrs"}`` — relative seconds, since the two processes share no
clock); the client re-anchors them inside its own network span
(:meth:`Tracer.fold_remote`), splitting the residual RTT evenly, so
one request yields one tree spanning client and daemon processes.

**Engine spans** are the work no single request owns — a serving
loop's iterations, its idle waits, a batched decode step, a garbage
collection. They share the trace id :data:`ENGINE_TRACE` and live in a
bounded ring of their own (:meth:`Tracer.engine`), so hundreds of
iterations per second never evict a request's tree from the per-trace
store; ``tracer.trace("engine")`` returns the ring.

**Two clocks, one span.** A process may install a host-annotation
factory (:func:`set_annotation`): every span entered as a context
manager then also opens ``factory(name, attrs)`` on entry and closes it
on exit, so the same name lands in an external profiler's timeline
(the serving engine installs ``jax.profiler.TraceAnnotation``). This
package itself never imports a profiler. Spans recorded after the fact
(:meth:`Tracer.add`, :meth:`Tracer.fold_remote`) are not annotated.
"""
from __future__ import annotations

import gc
import os
import threading
import weakref
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

from repro.obs import clock

TRACE_KEY = "_trace"          # payload-envelope key carrying the context
SPANS_KEY = "_spans"          # response key carrying server descriptors


def _hex_id(nbytes: int = 8) -> str:
    return os.urandom(nbytes).hex()


class SpanContext(NamedTuple):
    """The picklable handle another thread/process parents onto."""
    trace_id: str
    span_id: str


ENGINE_TRACE = "engine"       # trace id shared by every engine span
ENGINE_SPANS = 16384          # engine ring capacity: the newest are kept


# process-wide host-annotation hook: factory(name, attrs) -> a context
# manager entered/exited with each context-managed span (None: off)
_annotate: Optional[Callable] = None


def set_annotation(factory: Optional[Callable]) -> None:
    """Install (or, with ``None``, remove) the process-wide
    host-annotation factory."""
    global _annotate
    _annotate = factory


# thread-local ambient state: the tracer+span most recently entered on
# THIS thread — what module-level ``phase(...)`` instrumentation (e.g.
# in state_io) parents onto without threading a tracer through every
# call signature. Handoff between threads stays explicit (attach/ctx).
_ambient = threading.local()


class Span:
    """One timed, attributed node of a trace tree.

    Use as a context manager (enters as the thread's ambient parent)
    or call :meth:`end` explicitly for spans held across callbacks or
    threads. ``end()`` is idempotent; attributes may be added until
    then via :meth:`set`.
    """

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "proc",
                 "t0", "dur", "attrs", "_tracer", "_prev", "_ended",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: str, parent_id: str, proc: str, t0: float,
                 attrs: Optional[dict] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.proc = proc
        self.t0 = t0
        self.dur = 0.0
        self.attrs = dict(attrs or {})
        self._tracer = tracer
        self._prev = None
        self._ended = False
        self._ann = None

    @property
    def ctx(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self, t1: Optional[float] = None) -> "Span":
        if not self._ended:
            self._ended = True
            self.dur = max((t1 if t1 is not None else clock.monotonic())
                           - self.t0, 0.0)
            self._tracer._record(self)
        return self

    def as_dict(self) -> dict:
        return {"name": self.name, "trace": self.trace_id,
                "span": self.span_id, "parent": self.parent_id,
                "proc": self.proc, "t0": self.t0, "dur": self.dur,
                "attrs": dict(self.attrs)}

    # -- ambient-parent plumbing --------------------------------------
    def __enter__(self) -> "Span":
        self._prev = (getattr(_ambient, "tracer", None),
                      getattr(_ambient, "span", None))
        _ambient.tracer, _ambient.span = self._tracer, self
        if _annotate is not None:
            self._ann = _annotate(self.name, self.attrs)
            self._ann.__enter__()
        return self

    def __exit__(self, etype, exc, tb) -> None:
        _ambient.tracer, _ambient.span = self._prev
        self._prev = None
        if self._ann is not None:
            ann, self._ann = self._ann, None
            ann.__exit__(None, None, None)
        if etype is not None:
            self.attrs.setdefault("error", repr(exc))
        self.end()

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, trace={self.trace_id[:8]}, "
                f"dur={self.dur * 1e3:.2f}ms)")


class _NullSpan:
    """Inert span: every op is a no-op so disabled-tracer call sites
    stay branch-free."""

    __slots__ = ()
    name = ""
    trace_id = span_id = parent_id = proc = ""
    t0 = dur = 0.0
    attrs: dict = {}
    ctx = None

    def set(self, **attrs):
        return self

    def end(self, t1=None):
        return self

    def as_dict(self):
        return {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def __bool__(self):
        return False


NULL_SPAN = _NullSpan()


class Tracer:
    """Thread-safe span factory + bounded finished-trace store.

    ``proc`` labels which process/component minted each span (e.g.
    ``"client"``, ``"gateway"``, ``"peer:peer0"``). ``max_traces``
    bounds memory: oldest complete traces are evicted FIFO. The engine
    ring holds the newest :data:`ENGINE_SPANS` engine spans and never
    competes with request traces.
    """

    def __init__(self, proc: str = "", enabled: bool = True,
                 max_traces: int = 256, max_spans_per_trace: int = 2048):
        self.proc = proc
        self.enabled = enabled
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self._lock = threading.Lock()
        self._spans: "OrderedDict[str, List[dict]]" = OrderedDict()
        self._aliases: "OrderedDict[str, str]" = OrderedDict()
        self._ring: "deque[dict]" = deque(maxlen=ENGINE_SPANS)
        # engine spans noted from a gc callback, which may run while
        # this thread holds ``_lock``: appended here without the lock,
        # moved into the ring by the next call that takes it
        self._pending: "deque[dict]" = deque(maxlen=ENGINE_SPANS)

    # -- span creation -------------------------------------------------
    def _resolve_parent(self, parent) -> Optional[SpanContext]:
        if parent is None:
            amb = getattr(_ambient, "span", None)
            if amb is not None and amb._tracer is self:
                return amb.ctx
            return None
        if isinstance(parent, (Span, _NullSpan)):
            return parent.ctx
        if isinstance(parent, SpanContext):
            return parent
        if isinstance(parent, (tuple, list)) and len(parent) == 2:
            return SpanContext(str(parent[0]), str(parent[1]))
        raise TypeError(f"cannot parent a span on {parent!r}")

    def start(self, name: str, parent=None, attrs: Optional[dict] = None,
              t0: Optional[float] = None):
        """Open a span. ``parent`` is a Span, a :class:`SpanContext`
        (cross-thread/process handoff), or ``None`` — which adopts the
        thread's ambient span if this tracer owns it, else starts a new
        trace. Returns :data:`NULL_SPAN` when disabled."""
        if not self.enabled:
            return NULL_SPAN
        ctx = self._resolve_parent(parent)
        trace_id = ctx.trace_id if ctx else _hex_id()
        return Span(self, name, trace_id, _hex_id(),
                    ctx.span_id if ctx else "", self.proc,
                    clock.monotonic() if t0 is None else t0, attrs)

    def engine(self, name: str, **attrs):
        """Open an engine span: a child of the thread's ambient span when
        that is an engine span of this tracer, else a root of the engine
        ring."""
        amb = getattr(_ambient, "span", None)
        parent = amb if (amb is not None and amb._tracer is self
                         and amb.trace_id == ENGINE_TRACE) \
            else SpanContext(ENGINE_TRACE, "")
        return self.start(name, parent=parent, attrs=attrs)

    def add(self, name: str, dur: float, parent=None,
            t0: Optional[float] = None, **attrs):
        """Record an already-measured phase as a completed span —
        the instrumentation shape for code that computes a duration
        itself (e.g. device timings). Anchored at ``t0`` or at
        ``now - dur``."""
        if not self.enabled:
            return NULL_SPAN
        if t0 is None:
            t0 = clock.monotonic() - max(dur, 0.0)
        sp = self.start(name, parent=parent, attrs=attrs, t0=t0)
        sp.end(t0 + max(dur, 0.0))
        return sp

    @contextmanager
    def attach(self, parent):
        """Adopt ``parent`` (Span or SpanContext) as this thread's
        ambient parent — the explicit cross-thread handoff."""
        if not self.enabled or parent is None:
            yield
            return
        ctx = self._resolve_parent(parent)
        holder = Span(self, "", ctx.trace_id, ctx.span_id, "",
                      self.proc, 0.0)     # never recorded: pure handle
        prev = (getattr(_ambient, "tracer", None),
                getattr(_ambient, "span", None))
        _ambient.tracer, _ambient.span = self, holder
        try:
            yield
        finally:
            _ambient.tracer, _ambient.span = prev

    # -- the store -----------------------------------------------------
    def _record(self, span: Span) -> None:
        d = span.as_dict()
        with self._lock:
            if span.trace_id == ENGINE_TRACE:
                self._flush_pending()
                self._ring.append(d)
                return
            spans = self._spans.get(span.trace_id)
            if spans is None:
                spans = self._spans[span.trace_id] = []
                while len(self._spans) > self.max_traces:
                    old, _ = self._spans.popitem(last=False)
                    for alias, tid in list(self._aliases.items()):
                        if tid == old:
                            del self._aliases[alias]
            if len(spans) < self.max_spans_per_trace:
                spans.append(d)

    def _flush_pending(self) -> None:
        """Move gc-noted spans into the ring (caller holds ``_lock``)."""
        while self._pending:
            self._ring.append(self._pending.popleft())

    def _note_gc(self, t0: float, dur: float, attrs: dict) -> None:
        if self.enabled:
            self._pending.append({
                "name": "py.gc", "trace": ENGINE_TRACE, "span": _hex_id(),
                "parent": "", "proc": self.proc, "t0": t0, "dur": dur,
                "attrs": attrs})

    def alias(self, name: str, trace_id: str) -> None:
        """Register a secondary lookup key (e.g. the gateway request id
        ``cmpl-42``) for a trace."""
        with self._lock:
            self._aliases[name] = trace_id
            while len(self._aliases) > 4 * self.max_traces:
                self._aliases.popitem(last=False)

    def trace(self, trace_or_alias: str) -> Optional[List[dict]]:
        """All finished spans of one trace (insertion order), by trace
        id or alias; ``None`` if unknown/evicted. ``"engine"``
        (:data:`ENGINE_TRACE`) returns the engine ring."""
        with self._lock:
            if trace_or_alias == ENGINE_TRACE:
                self._flush_pending()
                return list(self._ring)
            tid = self._aliases.get(trace_or_alias, trace_or_alias)
            spans = self._spans.get(tid)
            return list(spans) if spans is not None else None

    def trace_ids(self) -> List[str]:
        with self._lock:
            return list(self._spans)

    def spans(self) -> List[dict]:
        """Every stored span across traces, then the engine ring (export
        convenience)."""
        with self._lock:
            self._flush_pending()
            return [d for spans in self._spans.values()
                    for d in spans] + list(self._ring)

    def rollup(self) -> Dict[str, dict]:
        """Per-span-name aggregate: ``{name: {count, total_s}}`` —
        the per-phase rollup benchmarks attach to their BENCH json."""
        out: Dict[str, dict] = {}
        for d in self.spans():
            agg = out.setdefault(d["name"], {"count": 0, "total_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += d["dur"]
        return out

    # -- cross-process stitching --------------------------------------
    def fold_remote(self, parent: Span, descriptors: Sequence[dict],
                    proc: str = "") -> int:
        """Re-anchor server-side span *descriptors* under a finished
        client-side network span. The processes share no clock, so each
        descriptor carries only (rel_s, dur_s) relative to the server's
        request start; the server window is centered inside the client
        span, splitting the residual RTT evenly between the two
        directions. Returns the number of spans folded."""
        if not self.enabled or not descriptors \
                or isinstance(parent, _NullSpan):
            return 0
        window = max((float(d.get("rel_s", 0.0)) +
                      float(d.get("dur_s", 0.0)) for d in descriptors),
                     default=0.0)
        base = parent.t0 + max((parent.dur - window) / 2.0, 0.0)
        n = 0
        for d in descriptors:
            if not isinstance(d, dict) or "name" not in d:
                continue
            sp = Span(self, str(d["name"]), parent.trace_id, _hex_id(),
                      parent.span_id, proc or str(d.get("proc", "")),
                      base + float(d.get("rel_s", 0.0)),
                      d.get("attrs") or {})
            sp.attrs.setdefault("remote", True)
            sp.end(sp.t0 + float(d.get("dur_s", 0.0)))
            n += 1
        return n


NULL_TRACER = Tracer(enabled=False)


def current_span() -> Optional[Span]:
    """The calling thread's ambient span, or ``None`` outside any —
    what a caller captures before spawning a worker thread and hands
    to :meth:`Tracer.attach` inside it (explicit handoff)."""
    return getattr(_ambient, "span", None)


@contextmanager
def phase(name: str, **attrs):
    """Ambient child span on whatever tracer/span the calling thread
    most recently entered — the zero-plumbing instrumentation used by
    ``state_io`` (serialize/restore/chunk-digest phases). A no-op
    (yields :data:`NULL_SPAN`) when no span is active on this thread."""
    tracer = getattr(_ambient, "tracer", None)
    if tracer is None or not tracer.enabled:
        yield NULL_SPAN
        return
    with tracer.start(name, attrs=attrs) as sp:
        yield sp


# ---------------------------------------------------------------------------
# garbage collections as engine spans
# ---------------------------------------------------------------------------

GC_MIN_S = 1e-3               # shorter collections are not stored
_gc_tracers: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_gc_thread = threading.local()
_gc_lock = threading.Lock()    # watch/unwatch only, never the callback


def _on_gc(stage: str, info: dict) -> None:
    """``gc.callbacks`` hook: each collection opens a host annotation
    (``py.gc``) on the collecting thread; one of ``GC_MIN_S`` or more is
    stored in every watching tracer's engine ring."""
    if stage == "start":
        ann = _annotate("py.gc", {}) if _annotate is not None else None
        if ann is not None:
            ann.__enter__()
        _gc_thread.open = (clock.monotonic(), ann)
        return
    t0, ann = getattr(_gc_thread, "open", (None, None))
    _gc_thread.open = (None, None)
    if ann is not None:
        ann.__exit__(None, None, None)
    if t0 is None:
        return
    dur = clock.monotonic() - t0
    if dur < GC_MIN_S:
        return
    attrs = {"generation": info.get("generation"),
             "collected": info.get("collected"),
             "thread": threading.current_thread().name}
    for tr in list(_gc_tracers):
        tr._note_gc(t0, dur, dict(attrs))


def watch_gc(tracer: Tracer) -> None:
    """Store the process's garbage collections of ``GC_MIN_S`` or more
    as ``py.gc`` engine spans in ``tracer`` (held weakly)."""
    with _gc_lock:
        _gc_tracers.add(tracer)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def unwatch_gc(tracer: Tracer) -> None:
    with _gc_lock:
        _gc_tracers.discard(tracer)
        if not _gc_tracers and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


# ---------------------------------------------------------------------------
# wire propagation (payload envelope)
# ---------------------------------------------------------------------------

def inject_trace(payload: dict, span) -> dict:
    """Copy of ``payload`` carrying the span's trace context under
    :data:`TRACE_KEY`. With a null/absent span, returns the payload
    unchanged — the peer then answers without ``_spans``, exactly like
    a pre-tracing client."""
    ctx = getattr(span, "ctx", None)
    if ctx is None and isinstance(span, SpanContext):
        ctx = span
    if ctx is None:
        return payload
    out = dict(payload)
    out[TRACE_KEY] = [ctx.trace_id, ctx.span_id]
    return out


def extract_trace(payload: dict) -> Optional[SpanContext]:
    """Pop the trace context from an op payload server-side. Tolerant
    of anything malformed (a garbled envelope must never fail an op):
    returns ``None`` unless a well-formed ``[trace_id, span_id]`` pair
    is present."""
    raw = payload.pop(TRACE_KEY, None)
    if (isinstance(raw, (list, tuple)) and len(raw) == 2
            and all(isinstance(x, (str, bytes)) for x in raw)):
        tid, sid = (x.decode() if isinstance(x, bytes) else x
                    for x in raw)
        return SpanContext(tid, sid)
    return None
