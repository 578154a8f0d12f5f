"""A traced sub-window and its reduction to numbers.

``Capture`` records a short stretch of a run with ``jax.profiler`` and
marks it with the host span ``psbench.window``; the harness opens
``psbench.<what>`` spans around its own calls into the program inside it.
``reduce_xspace`` turns the ``.xplane.pb`` that the profiler writes into a
``TraceSummary``: the union of the intervals in which a program ran on
each device, the idle share of the window, the device time of each
operation name, and each idle gap named by the host span open over it.

A TPU device plane (``/device:TPU:<n>``) holds an ``XLA Modules`` line,
one event per program run, and an ``XLA Ops`` line, one event per
operation; an operation's name is its HLO text (``%fusion.84 = f32[...]
fusion(...)``), and a loop's event (``%while.57 = ...``) spans the events
of its body.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from pathlib import Path

WINDOW = "psbench.window"
SPAN_PREFIX = "psbench."
BUSY_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
#: operations whose events span other operations' events
CONTAINERS = ("%while", "%conditional", "%call")


@dataclasses.dataclass
class TraceSummary:
    """What a traced window shows, times in seconds."""
    window_s: float
    busy_s: float                      # mean over devices of the busy union
    devices: int
    op_seconds: dict                   # op name -> summed device seconds
    op_counts: dict                    # op name -> number of events
    gaps: list                         # (seconds, host span name), longest first
    spans: dict                        # host span name -> count in the window

    @property
    def idle_share(self) -> float:
        """1 - busy / window."""
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, patterns) -> tuple[float, int]:
        """(summed device seconds, events) of the operations whose name
        contains any of ``patterns``."""
        secs = cnt = 0
        for name, s in self.op_seconds.items():
            if any(p in name for p in patterns):
                secs += s
                cnt += self.op_counts[name]
        return secs, cnt

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (loops left out, as
        their bodies' operations are counted) and the idle time by what the
        host was doing, at most ``top`` of each."""
        short: dict = {}
        for name, secs in self.op_seconds.items():
            head = name.split(" = ", 1)[0]
            if not head.startswith(CONTAINERS):
                short[head] = short.get(head, 0.0) + secs
        ops = sorted(short.items(), key=lambda kv: -kv[1])[:top]
        idle: dict = {}
        for secs, span in self.gaps:
            idle[span] = idle.get(span, 0.0) + secs
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, reach = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    gaps, reach = [], lo
    for a, b in sorted(intervals):
        if a > reach and reach < hi:
            gaps.append((reach, min(a, hi)))
        reach = max(reach, b)
    if reach < hi:
        gaps.append((reach, hi))
    return gaps


def name_gap(gap, spans) -> str:
    """The host span that overlaps ``gap`` most, or ``(no span)``."""
    best, name = 0.0, "(no span)"
    for a, b, n in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def _events(line):
    for e in line.events:
        start = e.start_ns * 1e-9
        yield e.name, start, start + e.duration_ns * 1e-9


def reduce_xspace(data) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a ``TraceSummary``. The
    window is the ``psbench.window`` host span; busy time is the union of
    each TPU plane's ``XLA Modules`` events, operation time comes from its
    ``XLA Ops`` events."""
    window, spans = None, []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, a, b in _events(line):
                    if name == WINDOW:
                        window = (a, b)
                    elif name.startswith(SPAN_PREFIX):
                        spans.append((a, b, name[len(SPAN_PREFIX):]))
        elif _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            if BUSY_LINE not in lines:
                raise ValueError(f"{plane.name} has no {BUSY_LINE!r} line")
            devices.append((list(_events(lines[BUSY_LINE])),
                            list(_events(lines[OP_LINE]))
                            if OP_LINE in lines else []))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} host span")
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    lo, hi = window
    busy, op_s, op_n, gaps = [], {}, {}, []
    for modules, ops in devices:
        inside = [(a, b) for _, a, b in modules if b > lo and a < hi]
        busy.append(union_length(inside, lo, hi))
        for name, a, b in ops:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_s[name] = op_s.get(name, 0.0) + d
                op_n[name] = op_n.get(name, 0) + 1
        gaps += [(b - a, name_gap((a, b), spans))
                 for a, b in idle_gaps(inside, lo, hi)]
    counts: dict = {}
    for a, b, n in spans:
        if a >= lo and b <= hi:
            counts[n] = counts.get(n, 0) + 1
    n_dev = len(devices)
    return TraceSummary(
        window_s=hi - lo, busy_s=sum(busy) / n_dev, devices=n_dev,
        op_seconds={k: v / n_dev for k, v in op_s.items()},
        op_counts=op_n, gaps=sorted(gaps, reverse=True), spans=counts)


def _is_device(name: str) -> bool:
    head, _, tail = name.partition("/device:TPU:")
    return head == "" and tail.isdigit()


class Capture:
    """Profile the code inside ``with``; ``summary()`` afterwards reduces
    what was written under ``directory``."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the harness's own spans suffice
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.directory),
                                 profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> TraceSummary:
        """The reduction of the newest trace file written."""
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise ValueError(f"no .xplane.pb under {self.directory}")
        newest = max(files, key=os.path.getmtime)
        return reduce_xspace(ProfileData.from_file(newest))


def span(what: str):
    """A host span ``psbench.<what>`` in the profiler's trace (free when no
    trace is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + what)
