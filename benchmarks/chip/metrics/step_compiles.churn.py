"""Programs traced inside the window's steps, traced steps included: the
``compiles`` counters of their ``churn.step`` spans. It reads what
``window_compiles.churn`` reads, from inside the program."""
from psbench.program_spans import steps


def read(run):
    found = steps(run, traced=True)
    if found is None:
        return None
    return sum(s.counters.get("compiles", 0) for s in found)
