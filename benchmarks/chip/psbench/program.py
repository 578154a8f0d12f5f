"""The program's entries under test, and nothing else of it: the churn
simulator and its event type (``AllocationProblem`` is the simulator's
input type)."""
from __future__ import annotations


def churn_simulator(deployment, guarantees: dict, telemetry: bool):
    """``ChurnSimulator`` over the whole deployment, all tenants present,
    under the configuration's guarantees."""
    from repro.core.types import AllocationProblem
    from repro.sched.churn import ChurnSimulator

    d = deployment
    problem = AllocationProblem(d.demands, d.capacities, d.weights,
                                d.eligibility)
    return ChurnSimulator(problem, mechanism=guarantees["mechanism"],
                          tol=guarantees["tol"],
                          max_rounds=guarantees["max_rounds"],
                          layout=guarantees["layout"], telemetry=telemetry)


def churn_event(e):
    """The simulator's event for a ``psbench.events.Event``."""
    from repro.sched.churn import ChurnEvent

    return ChurnEvent(e.due, e.kind, user=e.user, server=e.server,
                      scale=e.scale)
