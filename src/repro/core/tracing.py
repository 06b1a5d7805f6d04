"""Program spans: what the host was doing, and for how long.

One recorder for the whole program, off unless a caller turns it on::

    from repro.core import tracing

    tracing.enable()
    server.step()
    spans, dropped = tracing.drain()
    tracing.disable()

Spans sit at segment, micro-batch and scheduling-round granularity, never
per record.  Off (the default), ``span()`` returns one shared no-op object
after a single check of a module global: no clock read, no allocation.
On, each closed span is one :class:`SpanRecord` (name, start and end on
``time.monotonic()``, thread, ``n``, ``key``, and the innermost span open
on the same thread when it opened) in a bounded ring; past ``capacity``
the oldest records are dropped and counted, never raised.  Each span also
opens ``jax.profiler.TraceAnnotation("repro:<name>")``, so a profiler
trace shows it on the host plane, on the device trace's clock, beside the
device's ops.  Per-job identifiers go in ``key`` (in memory only), never
in the annotation's name.

Every span name is declared in :data:`SPANS`: a reader tells "not
instrumented" (a name absent from it) from "instrumented, nothing
happened" (declared, never recorded).  Counts come from the spans (how
many, and their ``n``) or from the program's own reports and registry;
the recorder keeps no counters of its own.
Compiles are recorded too: while the recorder is on, a ``jax.monitoring``
listener turns each trace, lowering and backend-compile duration into a
``jax.compile`` span ending when the event fired (that span has no
annotation: the profiler already holds JAX's own compile events).

Recording is thread-safe: prefetch threads and the driver record at once.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, NamedTuple

import jax
from jax.profiler import TraceAnnotation

__all__ = ["SPANS", "SpanRecord", "Drained", "enable", "disable", "enabled",
           "drain", "span"]

#: every span the program opens, by layer (the readers' contract)
SPANS = (
    "ingest.pump",          # SharedIngest.pump; n = records materialized
    "ingest.fetch",         # segment listing, or one segment's GET; n = bytes
    "ingest.decode",        # one segment's lines parsed whole; n = records
    "ingest.publish",       # one segment routed + batch-produced; n = records
    "topic.read",           # one micro-batch read off the topic; n = records
    "server.step",          # JobServer.step; n = records moved
    "server.lane_wait",     # overlapped driver blocked on a job's prefetch
    "server.restore",       # JobServer._restore (fresh or cold)
    "server.park",          # JobServer._park
    "coord.announce",       # batch triggers published; n = triggers
    "coord.prepare",        # _prepare_batch; n = records
    "coord.fold_drain",     # _process_prepared; n = records
    "coord.trigger_poll",   # the batch-trigger poll opening each batch
    "coord.fold",           # wire + fold dispatch; n = rows shipped
    "coord.finalize",       # finalization sweep + sink flush; n = windows
    "coord.device_wait",    # host reads that wait on the device
    "coord.checkpoint",     # save_state; n = carry bytes written
    "jax.compile",          # one JAX trace, lowering or backend compile
)

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_LABELS = {name: f"repro:{name}" for name in SPANS}


class SpanRecord(NamedTuple):
    """One closed span: ``parent`` is the ``id`` of the innermost span open
    on the same thread when this one opened (None at the top)."""

    id: int
    name: str
    start: float            # time.monotonic()
    end: float
    thread: int             # threading.get_ident()
    n: int
    key: Any
    parent: int | None


class Drained(NamedTuple):
    """What :func:`drain` hands back: closed spans, oldest first, and the
    records dropped past ``capacity``."""

    spans: list[SpanRecord]
    dropped: int


_on = False
_lock = threading.Lock()
_records: deque = deque()
_dropped = 0
_ids = itertools.count()
_local = threading.local()
_listening = False


def _stack() -> list["_Span"]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _append(rec: SpanRecord) -> None:
    global _dropped
    with _lock:
        if len(_records) == _records.maxlen:
            _dropped += 1
        _records.append(rec)


class _Span:
    """An open span (``with`` it).  ``n`` may be set while it is open,
    once the count is known."""

    __slots__ = ("name", "n", "key", "id", "parent", "start", "_ann")

    def __init__(self, name: str, n: int, key: Any) -> None:
        if name not in _LABELS:
            raise KeyError(f"undeclared span {name!r}; add it to "
                           f"repro.core.tracing.SPANS")
        self.name, self.n, self.key = name, n, key

    def __enter__(self) -> "_Span":
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._ann = TraceAnnotation(_LABELS[self.name])
        self._ann.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t = time.monotonic()
        self._ann.__exit__(None, None, None)
        _stack().pop()
        if _on:
            _append(SpanRecord(self.id, self.name, self.start, t,
                               threading.get_ident(), int(self.n), self.key,
                               self.parent))


class _Off:
    """The shared span of a recorder that is off: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def __setattr__(self, name: str, value: Any) -> None:
        return None


_OFF = _Off()


def span(name: str | None, *, n: int = 0, key: Any = None):
    """A context manager timing the block as span ``name`` (declared in
    :data:`SPANS`; None times nothing, for a site whose span depends on
    its class); ``n`` counts what the block handled (records, bytes,
    windows: see :data:`SPANS`) and may be set on the span before it
    closes; ``key`` names the segment or ``job_id/batch`` it handled."""
    if not _on or name is None:
        return _OFF
    return _Span(name, n, key)


def _on_compile(event: str, duration: float, **_kw) -> None:
    phase = _COMPILE_EVENTS.get(event)
    if not _on or phase is None:
        return
    t = time.monotonic()
    stack = _stack()
    parent = stack[-1] if stack else None
    start = t - duration if parent is None \
        else max(t - duration, parent.start)     # JAX times on another clock
    _append(SpanRecord(next(_ids), "jax.compile", start, t,
                       threading.get_ident(), 0, phase,
                       None if parent is None else parent.id))


def enable(capacity: int = 1 << 16) -> None:
    """Start recording afresh, keeping at most ``capacity`` span records."""
    global _on, _records, _dropped, _listening
    with _lock:
        _records = deque(maxlen=max(1, int(capacity)))
        _dropped = 0
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _listening = True
        _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until :func:`drain`."""
    global _on
    _on = False


def enabled() -> bool:
    """Whether spans are being recorded."""
    return _on


def drain() -> Drained:
    """Everything recorded since :func:`enable` or the last drain, which
    is then cleared (recording goes on if it was on)."""
    global _dropped
    with _lock:
        out = Drained(list(_records), _dropped)
        _records.clear()
        _dropped = 0
    return out
