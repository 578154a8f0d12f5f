"""The program's own spans of a churn run's window steps.

``ChurnSimulator.step`` opens a ``churn.step`` root span in
``repro.core.trace`` and the finished roots stay in that module's ring
(``recent()``). The loop calls ``step()`` once for each entry of
``run.records`` and ``run.traced_records`` and nothing after them, so the
window's steps are the last ``len(records) + len(traced_records)``
``churn.step`` roots, in the order of their ``start_s``.

Each function returns ``None`` where the program keeps no such spans (a
checkout without ``recent()``) or the ring holds fewer roots than the
window has steps.
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np


def steps(run, traced: bool = False) -> Optional[list]:
    """The window's ``churn.step`` roots: the untraced steps, which the
    host-clock metrics read, or every step when ``traced``."""
    try:
        from repro.core import trace
    except ImportError:
        return None
    recent = getattr(trace, "recent", None)
    if recent is None:
        return None
    kinds = sorted([(r["start_s"], False) for r in run.records]
                   + [(r["start_s"], True) for r in run.traced_records])
    roots = recent("churn.step")
    if not kinds or len(roots) < len(kinds):
        return None
    roots = roots[len(roots) - len(kinds):]
    return [s for s, (_, in_trace) in zip(roots, kinds)
            if traced or not in_trace]


def child_ms(step, name: str) -> float:
    """Milliseconds of ``step``'s direct children called ``name``."""
    return sum(c.ms for c in step.children if c.name == name)


def mean_per_step(run, value: Callable) -> Optional[float]:
    """Mean of ``value(step)`` over the window's untraced steps."""
    found = steps(run)
    if not found:
        return None
    return float(np.mean([value(s) for s in found]))
