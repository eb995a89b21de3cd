"""Program spans for the save and restore paths (docs/write_path.md,
"Tracing").

``span(name, **attrs)`` is a context manager that opens a
``jax.profiler.TraceAnnotation`` of the same name, so every span lands in
any profiler trace on the clock of the device's events, with no call into
the program. It also reads ``time.monotonic`` at both ends
(:attr:`Span.seconds`), so callers that time the same work (the pipeline's
busy seconds, ``Trainer.stall_times``) read these two clock reads and no
second pair.

A span's parent is the span open in the same context when it opens, or the
one passed as ``parent=`` (pipeline workers run an item's stages under the
span that submitted it). Its request id is given (a save's step, a
restore's :func:`new_request`) or taken from the parent, so the spans of
one save or one restore share one id across threads.

Spans are kept in memory only while recording is on: inside a
:func:`record` block, or while a JAX profiler session collects host events
(the condition under which the annotation itself is kept, so a profiled
run carries its spans in memory too). :func:`drain` hands the kept spans
over; the buffer holds the newest :data:`MAX_SPANS` and counts what it
dropped. With recording off a span costs its annotation, its two clock
reads and a context-variable set and reset; nothing is kept.

A ``jax.monitoring`` listener counts each backend compile in
:attr:`Span.compiles` of the innermost span open on the compiling thread
and of each of its ancestors, so a root span counts its whole tree's.

The recorder is one per process, as the profiler and the compile events
are.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
MAX_SPANS = 1 << 16

_Annotation = jax.profiler.TraceAnnotation
_INHERIT = object()
_current: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "cnr_span", default=None)
_ids = itertools.count(1)
_requests = itertools.count(1)


class Span:
    """One span: name, ``t0``/``t1`` (``time.monotonic``), thread name,
    parent, request id and integer attributes (``rows``, ``bytes``, ...)."""

    __slots__ = ("name", "id", "parent", "request", "attrs", "thread", "t0",
                 "t1", "compiles", "_ann", "_token")

    def __init__(self, name: str, parent: Optional["Span"],
                 request: Optional[int], attrs: Dict[str, int]) -> None:
        self.name = name
        self.id = next(_ids)
        self.parent = parent
        self.request = (request if request is not None
                        else parent.request if parent is not None else None)
        self.attrs = attrs
        self.thread = ""
        self.t0 = self.t1 = 0.0
        self.compiles = 0

    def __enter__(self) -> "Span":
        self._ann = _Annotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._token = _current.set(self)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic()
        _current.reset(self._token)
        self._ann.__exit__(*exc)
        self._ann = self._token = None
        if _recorder.on():
            self.thread = threading.current_thread().name
            _recorder.keep(self)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def parent_id(self) -> Optional[int]:
        return self.parent.id if self.parent is not None else None

    def set(self, request: Optional[int] = None, **attrs: int) -> None:
        """Set the request id (when given) and attributes of an open span;
        the attributes also go into the profiler's annotation."""
        if request is not None:
            self.request = request
        if attrs:
            self.attrs.update(attrs)
            if self._ann is not None:
                self._ann.set_metadata(**attrs)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.seconds:.6f} s, "
                f"request={self.request}, attrs={self.attrs})")


def span(name: str, *, parent=_INHERIT, request: Optional[int] = None,
         **attrs: int) -> Span:
    """A span to enter with ``with``. ``parent`` defaults to the span open
    in this context; ``request`` to the parent's."""
    if parent is _INHERIT:
        parent = _current.get()
    return Span(name, parent, request, attrs)


def current() -> Optional[Span]:
    """The innermost span open in this context, or None."""
    return _current.get()


def annotate(**attrs: int) -> None:
    """Set attributes of the innermost open span, where there is one."""
    sp = _current.get()
    if sp is not None:
        sp.set(**attrs)


def new_request() -> int:
    """A fresh request id (restores take one each; saves use their step)."""
    return next(_requests)


class _Recorder:
    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
        self.depth = 0
        self.dropped = 0

    def on(self) -> bool:
        return self.depth > 0 or _Annotation.is_enabled()

    def keep(self, sp: Span) -> None:
        with self.lock:
            if len(self.spans) == self.spans.maxlen:
                self.dropped += 1
            self.spans.append(sp)

    def on_event(self, event: str, duration: float, **kw) -> None:
        if event != BACKEND_COMPILE:
            return
        sp = _current.get()
        if sp is None:
            return
        with self.lock:
            while sp is not None:
                sp.compiles += 1
                sp = sp.parent


_recorder = _Recorder()
jax.monitoring.register_event_duration_secs_listener(_recorder.on_event)


@contextlib.contextmanager
def record() -> Iterator[None]:
    """Keep the spans that close inside this block (blocks may nest and
    overlap across threads; recording stays on until the last one ends)."""
    with _recorder.lock:
        _recorder.depth += 1
    try:
        yield
    finally:
        with _recorder.lock:
            _recorder.depth -= 1


def drain() -> List[Span]:
    """The kept spans in the order they closed, removed from the buffer."""
    with _recorder.lock:
        out = list(_recorder.spans)
        _recorder.spans.clear()
    return out


def dropped() -> int:
    """Spans the bounded buffer has dropped, oldest first, since start."""
    return _recorder.dropped
