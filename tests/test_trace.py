"""Spans and counters of ``repro.core.trace``, and the ones the churn step
records: nesting, counters on the root, the bounded ring, the profiler's
host plane, and on a small ``ChurnSimulator`` the span of every part of a
step with the bytes it moved and the programs it compiled."""
import glob
import os

import numpy as np
import pytest

from repro.core import trace
from repro.core.instances import fig2_instance, sparse_cell_instance
from repro.sched.churn import ChurnEvent, ChurnSimulator

# an instance size no other test compiles, so the first step here traces
# its programs in this process whatever ran before
USERS, SERVERS = 232, 48


def _names(root):
    return [s.name for s in root.walk()]


def test_spans_nest_with_parent_links():
    with trace.Tracer("outer") as tr:
        with trace.span("a") as a:
            with trace.span("a.1") as a1:
                pass
        with trace.span("b") as b:
            pass
    root = tr.top
    assert root.parent is None
    assert [c.name for c in root.children] == ["a", "b"]
    assert a.parent is root and b.parent is root and a1.parent is a
    assert _names(root) == ["outer", "a", "a.1", "b"]


def test_children_lie_inside_their_parents():
    with trace.Tracer("outer") as tr:
        for i in range(3):
            with trace.span(f"s{i}"):
                with trace.span("inner"):
                    sum(range(1000))
    for s in tr.top.walk():
        assert s.end_ns >= s.start_ns > 0
        for c in s.children:
            assert s.start_ns <= c.start_ns <= c.end_ns <= s.end_ns
        starts = [c.start_ns for c in s.children]
        assert starts == sorted(starts)
        assert s.self_ms() >= 0.0
    assert tr.top.total_ms("inner") <= tr.top.ms
    assert tr.stage_ms() == tuple(c.ms for c in tr.top.children)


def test_counters_are_kept_on_the_root():
    with trace.Tracer("outer") as tr:
        trace.count("bytes", 10)
        with trace.span("a") as a:
            trace.count("bytes", 5)
            with trace.span("a.1"):
                trace.count("calls")
    assert tr.top.counters == {"bytes": 15, "calls": 1}
    assert a.counters == {}


def test_the_ring_stays_bounded():
    for i in range(trace.RING_ROOTS + 10):
        with trace.Tracer("ring.test") as tr:
            pass
    ring = trace.recent()
    assert len(ring) == trace.RING_ROOTS
    assert ring[-1] is tr.top
    assert len(trace.recent("ring.test")) == trace.RING_ROOTS


def test_a_span_outside_any_tracer_records_nothing():
    before = trace.recent()
    with trace.span("loose") as s:
        trace.count("loose", 1)
    assert s is None
    after = trace.recent()
    assert len(after) == len(before)
    assert all(a is b for a, b in zip(after, before))


def test_a_tracer_opened_inside_another_nests():
    with trace.Tracer("outer") as outer:
        with trace.span("mid"):
            with trace.Tracer("inner") as inner:
                with trace.span("leaf"):
                    trace.count("n", 2)
    assert inner.top.parent is outer.top.children[0]
    assert inner.top.parent.parent is outer.top
    assert outer.top.counters == {"n": 2} and inner.top.counters == {}
    assert trace.recent()[-1] is outer.top
    assert all(r is not inner.top for r in trace.recent())
    assert inner.stage_ms() == (inner.top.children[0].ms,)


def test_lexmm_router_spans_nest_under_the_churn_solve():
    sim = ChurnSimulator(fig2_instance(), mechanism="tsf",
                         placement="lexmm", telemetry=False)
    rec = sim.step([], 0.0)
    (solve,) = [c for c in rec.trace.children if c.name == "churn.solve"]
    (router,) = solve.children
    assert router.name in ("lexmm.solve", "lexmm.resolve")
    stages = [c.name for c in router.children]
    assert stages and all(n.startswith("lexmm.stage") for n in stages)
    assert len(stages) == rec.rounds
    assert rec.trace.counters["lp_calls"] == rec.lp_calls > 0
    assert sim._router_stats.stage_ms == tuple(c.ms for c in router.children)


@pytest.fixture(scope="module")
def churn():
    """A bucketed simulator with user 0 absent at the start, its
    ``churn.init`` root, a first step, the same step again, and a step
    whose arrival of user 0 rebuilds the layout."""
    prob, _ = sparse_cell_instance(num_users=USERS, num_servers=SERVERS)
    active = np.ones(USERS, dtype=bool)
    active[0] = False
    sim = ChurnSimulator(prob, layout="bucketed", initial_active=active)
    init = trace.recent("churn.init")[-1]
    init_layout_bytes = sim._idx_j.nbytes + sim._mask_j.nbytes
    first = sim.step([], 0.0)
    again = sim.step([], 1.0)
    rebuilt = sim.step([ChurnEvent(2.0, "arrival", user=0),
                        ChurnEvent(2.0, "departure", user=5)], 2.0)
    return dict(sim=sim, prob=prob, init=init, first=first, again=again,
                rebuilt=rebuilt, init_layout_bytes=init_layout_bytes)


def test_a_step_holds_the_spans_of_each_part(churn):
    rec = churn["first"]
    assert rec.trace.name == "churn.step" and rec.trace.parent is None
    assert [c.name for c in rec.trace.children] == [
        "churn.apply", "churn.solve", "churn.telemetry", "churn.certify"]
    names = _names(rec.trace)
    for name in ("churn.solve.upload", "churn.solve.wait",
                 "churn.solve.download", "churn.telemetry.gamma",
                 "vds.prep", "vds.call"):
        assert name in names
    assert names.count("vds.prep") == 2
    assert "churn.rebuild" not in names
    apply = churn["rebuilt"].trace.children[0]
    assert [c.name for c in apply.children] == ["churn.rebuild"]
    assert churn["rebuilt"].layout_rebuilds == 1
    assert trace.recent("churn.step")[-1] is churn["rebuilt"].trace


def test_solve_ms_is_the_solve_span(churn):
    for key in ("first", "again", "rebuilt"):
        rec = churn[key]
        (solve,) = [c for c in rec.trace.children if c.name == "churn.solve"]
        assert rec.solve_ms == solve.ms > 0
        parts = sum(c.ms for c in solve.children)
        assert parts <= solve.ms


def test_transfer_counters_equal_the_bytes_moved(churn):
    sim, prob = churn["sim"], churn["prob"]
    n, k, r = USERS, SERVERS, prob.num_resources
    f32 = 4
    init = churn["init"].counters["h2d_bytes"]
    assert init == (f32 * (n * r + k * r + n + n * k)
                    + churn["init_layout_bytes"])
    n_pad = n + (-n % min(256, n))
    k_pad = k + (-k % min(128, k))
    rounds_bytes = np.dtype(np.int32).itemsize
    for key in ("first", "again"):
        c = churn[key].trace.counters
        # activity (bool), degrade scales and the warm start up; the vds
        # kernel's x/phi and gamma up, padded to its blocks
        assert c["h2d_bytes"] == n + f32 * k + f32 * n * k + f32 * (
            n_pad + n_pad * k_pad)
        # the allocation, rounds and residual down; the kernel's minima
        # and argmins down
        assert c["d2h_bytes"] == f32 * n * k + rounds_bytes + f32 + 2 * (
            f32 * k_pad)
    rebuilt = churn["rebuilt"].trace.counters["h2d_bytes"]
    assert rebuilt == churn["again"].trace.counters["h2d_bytes"] + (
        sim._idx_j.nbytes + sim._mask_j.nbytes)


def test_compiles_show_on_the_first_step_only(churn):
    first = churn["first"].trace.counters
    assert first["compiles"] > 0 and first["compile_ms"] > 0
    assert churn["again"].trace.counters.get("compiles", 0) == 0


def test_a_profiler_trace_holds_the_step_on_the_host_plane(churn, tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        churn["sim"].step([], 3.0)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    names = set()
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"repro.churn.step", "repro.churn.solve",
            "repro.vds.call"} <= names
