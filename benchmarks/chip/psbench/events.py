"""The seeded event generator, kept with the benchmark so that
no change to the program can change the load it is measured under.

``churn_stream`` is the program's ``poisson_churn_events`` rule, copied and
made open-loop: events carry continuous due times, tenant departures and
returning arrivals come in geometric bursts, and every single-server
degrade is followed by a restore.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Event:
    """One state change due at ``due`` seconds after the window opens."""
    due: float
    kind: str
    user: int = -1
    server: int = -1
    scale: float = 1.0


def churn_stream(num_users: int, num_servers: int, seconds: float, *,
                 rate_hz: float, burst_mean: float, degrade_share: float,
                 degrade_scale: tuple, restore_after_s: float,
                 timeline: np.random.Generator,
                 pick: np.random.Generator) -> list[Event]:
    """Events due in ``[0, seconds)`` at a mean of ``rate_hz`` events per
    second, sorted by due time.

    Tenant bursts arrive as a Poisson process; a burst is a departure of
    present tenants or a return of departed ones (a coin flip, falling
    back to the kind that is possible), all due at one instant, of
    geometric size with mean ``burst_mean``. Server degrades make up
    ``degrade_share`` of the events (a degrade and its restore count as
    two): each scales one healthy server by U(``degrade_scale``) and is
    restored after an exponential delay of mean ``restore_after_s``. At
    least one tenant stays present. Every slot starts present, so a
    return only re-activates a slot the solver has seen.

    ``timeline`` draws when, what kind and how large; ``pick`` draws which
    tenants and servers. Holding ``timeline`` fixed gives every seed of
    ``pick`` the same amount of work at the same times."""
    tenant_rate = rate_hz * (1.0 - degrade_share) / burst_mean
    degrade_rate = rate_hz * degrade_share / 2.0
    present = np.ones(num_users, dtype=bool)
    degraded = np.zeros(num_servers, dtype=bool)
    pending: list[Event] = []           # restores not yet due
    events: list[Event] = []
    t = 0.0
    total = tenant_rate + degrade_rate
    while True:
        t += timeline.exponential(1.0 / total)
        # restores fall due in time order with everything else
        for ev in sorted((p for p in pending if p.due <= t),
                         key=lambda p: p.due):
            if ev.due < seconds:
                events.append(ev)
            degraded[ev.server] = False
        pending = [p for p in pending if p.due > t]
        if t >= seconds:
            break
        if timeline.random() < tenant_rate / total:
            size = int(timeline.geometric(1.0 / burst_mean))
            leave = timeline.random() < 0.5
            if leave and present.sum() <= 1:
                leave = False
            if not leave and present.all():
                leave = True
            pool = np.flatnonzero(present if leave else ~present)
            if leave:
                size = min(size, pool.size - 1)
            size = min(size, pool.size)
            for u in pick.choice(pool, size, replace=False):
                present[u] = not leave
                events.append(Event(t, "departure" if leave else "arrival",
                                    user=int(u)))
        else:
            scale = float(timeline.uniform(*degrade_scale))
            after = timeline.exponential(restore_after_s)
            healthy = np.flatnonzero(~degraded)
            if healthy.size == 0:
                continue
            s = int(pick.choice(healthy))
            degraded[s] = True
            events.append(Event(t, "degrade", server=s, scale=scale))
            pending.append(Event(t + after, "restore", server=s))
    return events
