"""One run of one cell: find its pieces by name, check the device, build
the deployment, drive the traffic mix's loop, read the metrics, decide
``correct`` against the plain reference, and print the result line.

The loops (``loops/<name>.py``) fill a ``Run``; the metric readers
(``metrics/<name>.py``) read it. ``Run`` is the one contract between them.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from psbench import control, device, registry


@dataclasses.dataclass
class Context:
    """What a loop is given."""
    cell: dict
    config: dict
    mix: dict
    deployment: object                  # psbench.deployment.Deployment
    seed: int
    seconds: float
    traced: bool
    trace_dir: Path
    t_start: float                      # perf_counter at process start
    clock: device.CompileClock
    log: Callable[[str], None]
    phases: dict = dataclasses.field(default_factory=dict)

    def phase(self, name: str) -> None:
        """Record the set-up seconds since the last phase (or since the
        process started) under ``name``."""
        now = time.perf_counter() - self.t_start
        self.phases[name] = round(now - sum(self.phases.values()), 3)

    def rng(self, stream: int) -> np.random.Generator:
        """Independent seeded generators: 0 builds the deployment, 1 the
        traffic, 2 the choice of what the check samples."""
        return np.random.default_rng([stream, self.seed])


@dataclasses.dataclass
class Sample:
    """One certified answer of the timed path, kept for the check: the
    allocation and the state it answers."""
    x: object                          # (N, K) host or device array
    demands: np.ndarray
    capacities: np.ndarray             # effective (degrades applied)
    weights: np.ndarray
    eligibility: Optional[np.ndarray] = None  # (N, K) after departures
    min_vds: Optional[float] = None    # the program's telemetry, if any


@dataclasses.dataclass
class Run:
    """What a loop measured. ``records`` are the untraced steps or
    batches of the window; ``traced_records`` those inside the trace."""
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    latencies_s: np.ndarray = None     # per event due in the window
    completed: int = 0                 # certified answers in the window
    records: list = dataclasses.field(default_factory=list)
    traced_records: list = dataclasses.field(default_factory=list)
    window_compiles: int = 0
    trace: object = None               # tracing.TraceSummary
    samples: list = dataclasses.field(default_factory=list)
    missing: int = 0                   # answers that never came
    peaks: dict = None
    shape: tuple = None                # (tenants, servers, resources)
    x_start: object = None             # the allocation as the window opened
    notes: dict = dataclasses.field(default_factory=dict)


def check(run: Run, limits: dict) -> tuple[bool, dict]:
    """Compare every sample with the reference on the numbers the cell's
    limits name. Returns (correct, {check: {"value", "limit"}}) with each
    number at its worst over the samples; every limit must hold, at least
    one sample must have been compared, and no answer may be missing."""
    worst = {k: 0.0 for k in limits}
    for s in run.samples:
        for k, v in control.sound(s, names=limits).items():
            worst[k] = max(worst[k], v)
    out = {k: {"value": v, "limit": limits[k]} for k, v in worst.items()}
    out["samples"] = {"value": len(run.samples), "limit": 1}
    out["missing"] = {"value": run.missing, "limit": 0}
    ok = (all(v <= limits[k] for k, v in worst.items())
          and len(run.samples) >= 1 and run.missing == 0)
    return ok, out


def drive(root: Path, workload: str, seed: int, seconds: float,
          traced: bool, *, t_start: float, devices, log,
          config_override: Optional[dict] = None,
          phases: Optional[dict] = None) -> Run:
    """Build the cell's deployment from ``seed`` and run its loop.
    ``phases`` holds the set-up seconds already spent, by phase."""
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, workload)
    cfg, builder = registry.config(bench, cell["config"])
    if config_override:
        cfg = {**cfg, **config_override}
    mix, loop = registry.traffic(cell["traffic"])
    deployment = builder.build(cfg, np.random.default_rng([0, seed]))
    n, k, r = deployment.shape
    log(f"deployment {cell['config']} tenants={n} servers={k} "
        f"resources={r} eligible_pairs={int(deployment.eligibility.sum())}")
    trace_dir = root / ".psbench_traces" / f"{workload}-{seed}"
    with device.CompileClock() as clock:
        ctx = Context(cell, cfg, mix, deployment, seed, seconds, traced,
                      trace_dir, t_start, clock, log, dict(phases or {}))
        run = loop.run(ctx)
    run.peaks = device.PEAKS.get(devices[0].device_kind)
    run.shape = deployment.shape
    return run


def execute(root: Path, workload: str, seed: int, seconds: float,
            traced: bool, *, t_start: float, devices=None,
            config_override: Optional[dict] = None,
            out=sys.stdout, err=sys.stderr) -> int:
    """The whole run; returns the exit code. ``devices`` replaces the TPU
    lookup (tests hand the CPU devices in); ``config_override`` replaces
    top-level keys of the configuration (tests shrink the fleet)."""
    def log(line):
        print(line, file=err, flush=True)

    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, workload)
    wanted = registry.metrics(bench, workload, traced)
    readers = {m["name"]: registry.reader(m["name"]) for m in wanted}
    limits = registry.limits(workload)

    cache = device.configure_compile_cache(root)
    if devices is None:
        devices = device.require_tpu(cell["chips"])
    phases = {"start": round(time.perf_counter() - t_start, 3)}
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} {device.versions()} cache={cache} "
        f"cell={workload} seed={seed} seconds={seconds} trace={int(traced)}")
    run = drive(root, workload, seed, seconds, traced, t_start=t_start,
                devices=devices, log=log, config_override=config_override,
                phases=phases)
    mem = device.memory_peak_bytes(devices)
    correct, checks = check(run, limits)

    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev_out = {"platform": dev.platform, "kind": dev.device_kind,
               "count": len(devices), "memory_peak_bytes": mem}
    line = {"correct": bool(correct), "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics, "device": dev_out}
    if traced and run.trace is not None:
        dev_out["busy_s"] = run.trace.busy_s
        dev_out["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = checks
    log(f"run attempted={run.attempted} failed={run.failed} "
        f"setup_s={run.setup_s!r} window_s={run.window_s!r} "
        f"window_compiles={run.window_compiles} notes={run.notes}")
    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return 0


def main(argv=None, t_start: Optional[float] = None) -> int:
    """Command line: ``--workload --seed --seconds --trace``."""
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    root = Path(__file__).resolve().parents[3]
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"run_cell: the system under test is not in this checkout "
              f"({src / 'repro'} is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        return execute(root, a.workload, a.seed, a.seconds, bool(a.trace),
                       t_start=t_start)
    except (device.DeviceError, registry.UnknownName) as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
