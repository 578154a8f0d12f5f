"""The chip benchmark's trace reduction, on a small recorded trace laid
out as a TPU run writes it: a host plane with the harness's spans and a
device plane whose "XLA Ops" line holds one event per operation."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "benchmarks"
                       / "chip"))

from psbench import tracing  # noqa: E402

MS = 1_000_000_000          # picoseconds per millisecond


def _plane(pid, name, lines, names):
    """An XPlane in text form; ``lines`` maps a line's name to its events
    (metadata id, start ms, duration ms)."""
    text = [f'planes {{\n  id: {pid}\n  name: "{name}"']
    for lid, (line, events) in enumerate(lines.items(), 1):
        evs = "\n".join(
            f"    events {{ metadata_id: {m} offset_ps: {a * MS} "
            f"duration_ps: {d * MS} }}" for m, a, d in events)
        text.append(f'  lines {{\n    id: {lid}\n    name: "{line}"\n'
                    f'    timestamp_ns: 5000\n{evs}\n  }}')
    text += [f'  event_metadata {{ key: {i} value {{ id: {i} '
             f'name: "{n}" }} }}' for i, n in names.items()]
    return "\n".join(text) + "\n}\n"


def recorded(device_plane="/device:TPU:0"):
    """A 100 ms window: the host waits 0-10, steps 10-60 and 70-100. The
    device runs programs 0-5, 20-35, 50-55 and 120-125 (after the window);
    inside them a loop 20-30 holding a fusion, the vds kernel 25-35, a
    fusion 50-55 and copies 0-5 and 120-125."""
    host = _plane(1, "/host:CPU", {"python": [
        (1, 0, 100), (2, 0, 10), (3, 10, 50), (3, 70, 30)]},
        {1: "psbench.window", 2: "psbench.wait", 3: "psbench.step"})
    dev = _plane(2, device_plane, {
        "XLA Modules": [(5, 0, 5), (6, 20, 15), (5, 50, 5), (5, 120, 5)],
        "XLA Ops": [(4, 20, 10), (1, 20, 10), (2, 25, 10), (1, 50, 5),
                    (3, 0, 5), (3, 120, 5)]},
        {1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
         2: "%custom-call.3 = f32[1,128]{1,0} custom-call(), "
            "custom_call_target=vds_argmin",
         3: "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)",
         4: "%while.57 = (f32[8]{0}) while((f32[8]{0}) %t)",
         5: "jit_resolve(123)", 6: "jit_vds_argmin(456)"})
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(host + dev)


def test_busy_union_and_idle_share():
    s = tracing.reduce_xspace(recorded())
    assert s.window_s == pytest.approx(0.100)
    # 0-5 (copy), 20-35 (fusion and kernel overlap), 50-55
    assert s.busy_s == pytest.approx(0.025)
    assert s.idle_share == pytest.approx(0.75)
    assert s.devices == 1


def test_kernel_time_by_name():
    s = tracing.reduce_xspace(recorded())
    secs, count = s.kernel(("vds_argmin", "_vds_kernel"))
    assert secs == pytest.approx(0.010) and count == 1
    assert s.kernel(("%fusion",)) == (pytest.approx(0.015), 2)
    # the event after the window does not count
    copy = "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)"
    assert s.op_counts[copy] == 1
    assert s.op_seconds[copy] == pytest.approx(0.005)


def test_gap_attribution_to_host_spans():
    s = tracing.reduce_xspace(recorded())
    assert [(pytest.approx(a), n) for a, n in s.gaps] == [
        (0.045, "step"), (0.015, "step"), (0.015, "step")]
    by_span = dict(s.breakdown()["idle_gaps"])
    # idle 5-20 (wait 5-10, step 10-20 -> step), 35-50 (step),
    # 55-100 (step 55-60, none 60-70, step 70-100 -> step)
    assert by_span == {"step": pytest.approx(0.075)}
    ops = s.breakdown()["device_ops"]
    # short names, the loop left out (its body's operations count)
    assert [n for n, _ in ops] == ["%fusion.1", "%custom-call.3", "%copy.2"]
    assert ops[0][1] == pytest.approx(0.015)
    assert s.spans == {"wait": 1, "step": 2}


def test_interval_helpers():
    assert tracing.union_length([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert tracing.union_length([(-5, 1), (9, 20)], 0, 10) == 2
    assert tracing.idle_gaps([(1, 2), (1.5, 4)], 0, 5) == [(0, 1), (4, 5)]
    assert tracing.idle_gaps([], 0, 1) == [(0, 1)]
    assert tracing.name_gap((0, 1), []) == "(no span)"
    assert tracing.name_gap((0, 10), [(0, 2, "a"), (2, 10, "b")]) == "b"


def test_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tracing.reduce_xspace(recorded(device_plane="/device:CPU:0"))
    from jax.profiler import ProfileData

    only_dev = _plane(2, "/device:TPU:0", {"XLA Modules": [(1, 0, 1)]},
                      {1: "jit_f(1)"})
    with pytest.raises(ValueError, match="psbench.window"):
        tracing.reduce_xspace(ProfileData.from_text_proto(only_dev))
