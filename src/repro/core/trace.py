"""Spans and counters for the program's own work, on the profiler's clock.

One module holds the program's tracing; ``benchmarks/run.py`` takes its
call timer from here too.

* ``Tracer(name)`` — a context manager that opens one traced unit of work
  (a churn step, a lexmm solve). Opened while no other is open, its span is
  a *root*: when it closes it goes into a bounded in-memory ring that
  ``recent()`` returns, oldest first (nothing is written to disk). Opened
  inside another, its span nests as a child of the innermost open span.
* ``span(name)`` — a child of the innermost open span, for the length of
  the ``with`` block; it yields the ``Span`` (``None``, and records
  nothing, when no ``Tracer`` is open, so library code can call it
  unconditionally).
* ``count(name, n)`` — adds ``n`` to a counter of the innermost open
  span's root (a no-op when no ``Tracer`` is open).

Every recorded span also opens ``jax.profiler.TraceAnnotation("repro." +
name)``, which puts it on the host plane of a profiler trace, on the same
clock as the device planes; it costs about a microsecond when no profiler
runs. While any root is open, JAX's compile events add to its counters:
``compiles`` (programs traced, ``/jax/core/compile/jaxpr_trace_duration``)
and ``compile_ms`` (tracing, lowering and backend compile). Spans time with
``time.perf_counter_ns``. The open spans are tracked per thread and task
(``contextvars``), so two threads trace independently.

``timed_us(fn, *args, repeat=3)`` — one warm-up call, then the mean wall
time of ``repeat`` calls in microseconds (the benchmark CLI's timer).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import time
from typing import Callable, Iterator, List, Optional

#: finished roots kept by ``recent()``
RING_ROOTS = 1024
#: prefix of every span's name on the profiler's host plane
ANNOTATION_PREFIX = "repro."
#: JAX's compile duration events (trace, lower, backend compile); the
#: first is one per traced program
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

_open: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_trace_open", default=())
_ring: collections.deque = collections.deque(maxlen=RING_ROOTS)
_jax_hooks: list = []        # [TraceAnnotation] once the listener is in
_NOTHING = contextlib.nullcontext()


class Span:
    """One timed interval: ``name``, ``start_ns``/``end_ns``
    (``perf_counter_ns``; ``end_ns`` is 0 while open), its ``parent``
    (``None`` on a root), its ``children`` in start order, and — on a
    root — the ``counters`` of everything traced under it."""

    __slots__ = ("name", "start_ns", "end_ns", "parent", "children",
                 "counters")

    def __init__(self, name: str, parent: Optional["Span"] = None):
        self.name = name
        self.parent = parent
        self.children: List[Span] = []
        self.counters: dict = {}
        self.end_ns = 0
        self.start_ns = time.perf_counter_ns()

    @property
    def ms(self) -> float:
        """Duration in milliseconds (0 while open)."""
        return max(self.end_ns - self.start_ns, 0) * 1e-6

    def walk(self) -> Iterator["Span"]:
        """This span and every span under it, parents before children."""
        yield self
        for c in self.children:
            yield from c.walk()

    def total_ms(self, name: str) -> float:
        """Summed milliseconds of the spans called ``name`` under this one
        (itself included). Spans of one name do not nest in this
        program, so nothing is counted twice."""
        return sum(s.ms for s in self.walk() if s.name == name)

    def self_ms(self) -> float:
        """Milliseconds of this span that no child covers."""
        return self.ms - sum(c.ms for c in self.children)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, ms={self.ms:.3f}, "
                f"children={len(self.children)}, counters={self.counters})")


def _jax_annotation():
    """``jax.profiler.TraceAnnotation``, importing JAX and registering the
    compile listener on first use."""
    if not _jax_hooks:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _jax_hooks.append(jax.profiler.TraceAnnotation)
    return _jax_hooks[0]


def _on_compile(event: str, duration: float, **_) -> None:
    if event in COMPILE_EVENTS:
        stack = _open.get()
        if stack:
            c = stack[0].counters
            c["compile_ms"] = c.get("compile_ms", 0.0) + duration * 1e3
            if event == COMPILE_EVENTS[0]:
                c["compiles"] = c.get("compiles", 0) + 1


class _Opened:
    """Context manager of one recorded span: opens its profiler
    annotation and makes it the innermost open span."""

    __slots__ = ("span", "_annotation", "_token")

    def __init__(self, name: str, parent: Optional[Span]):
        self._annotation = _jax_annotation()(ANNOTATION_PREFIX + name)
        self.span = Span(name, parent)
        if parent is not None:
            parent.children.append(self.span)

    def __enter__(self) -> Span:
        self._token = _open.set(_open.get() + (self.span,))
        self._annotation.__enter__()
        return self.span

    def __exit__(self, *exc) -> None:
        self.span.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(None, None, None)
        _open.reset(self._token)


class Tracer:
    """Opens one traced unit of work (see the module docstring).
    ``top`` is its span once entered: a root, or a child when another
    ``Tracer`` was open. NOT shared between threads: open one per unit."""

    def __init__(self, name: str):
        self.name = name
        self.top: Optional[Span] = None
        self._opened: Optional[_Opened] = None

    def __enter__(self) -> "Tracer":
        stack = _open.get()
        self._opened = _Opened(self.name, stack[-1] if stack else None)
        self.top = self._opened.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._opened.__exit__(*exc)
        if self.top.parent is None:
            _ring.append(self.top)

    def stage_ms(self) -> tuple:
        """Durations (ms) of ``top``'s direct children, in start order
        (what ``SolveInfo.stage_ms`` carries)."""
        return tuple(c.ms for c in self.top.children)


def span(name: str):
    """A child of the innermost open span, as a context manager that
    yields it; records nothing (and yields ``None``) when no ``Tracer`` is
    open."""
    stack = _open.get()
    if not stack:
        return _NOTHING
    return _Opened(name, stack[-1])


def count(name: str, n=1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span's root;
    a no-op when no ``Tracer`` is open."""
    stack = _open.get()
    if stack:
        c = stack[0].counters
        c[name] = c.get(name, 0) + n


def recent(name: Optional[str] = None) -> List[Span]:
    """Finished roots still in the ring (the last ``RING_ROOTS``), oldest
    first; only those called ``name`` when it is given."""
    return [s for s in _ring if name is None or s.name == name]


def timed_us(fn: Callable, *args, repeat: int = 3, **kw):
    """Mean wall time of ``fn(*args, **kw)`` over ``repeat`` calls, in us;
    returns ``(us_per_call, last_result)``.

    One un-timed warm-up call runs first so one-off costs (jit compiles,
    lazy imports, matrix caches) don't pollute the steady-state number —
    callers benchmarking *cold* behavior should pass a fresh ``fn`` whose
    setup happens inside the call.
    """
    fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    return (time.perf_counter() - t0) / repeat * 1e6, out
