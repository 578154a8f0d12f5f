"""Readings that set the limits of the correctness check, at the cell's
own size and load: for each seed, one short window of the cell, then the
check's numbers and verdict (``harness.check`` with the cell's limits) for

* ``program``: the program's own answers (the largest over its samples);
* ``control``: the plain reference in the program's place, in bfloat16,
  from an empty allocation (``psbench.control.answer``), on the first
  ``--control`` samples;
* ``unchanged``: the allocation as the window opened, held against each
  sample's state (a step that returns its state unchanged);
* ``altered``: each sample's answer with one server's column scaled 1.05.

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds 1,2,3 --seconds 10 [--control 2]

One process for all seeds (the programs compile once). Prints one JSON
line per seed and a last line with, for each kind, the largest (program)
or smallest (the others) reading of each number and how many seeds came
out correct. Needs a TPU, like a run.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
KINDS = ("program", "control", "unchanged", "altered")


def verdicts(run, limits, tol, n_control):
    """{kind: (correct, {number: value})} for one run."""
    from psbench import control, harness

    samples = {
        "program": run.samples,
        "control": [control.answer(s, tol) for s in run.samples[:n_control]],
        "unchanged": [control.unchanged(s, run.x_start) for s in run.samples],
        "altered": [control.altered(s) for s in run.samples],
    }
    out = {}
    for kind, ss in samples.items():
        ok, checks = harness.check(dataclasses.replace(run, samples=ss),
                                   limits)
        out[kind] = (ok, {k: checks[k]["value"] for k in limits})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", type=int, default=2)
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from psbench import device, harness, registry

    bench = registry.load_benchmark(ROOT)
    cell = registry.cell(bench, a.workload)
    cfg, _ = registry.config(bench, cell["config"])
    tol = cfg["guarantees"]["tol"]
    limits = registry.limits(a.workload)
    device.configure_compile_cache(ROOT)
    try:
        devices = device.require_tpu(cell["chips"])
    except device.DeviceError as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    log = lambda line: print(line, file=sys.stderr, flush=True)  # noqa
    worst = {k: {} for k in KINDS}
    correct = {k: 0 for k in KINDS}
    for seed in [int(s) for s in a.seeds.split(",")]:
        t = time.perf_counter()
        run = harness.drive(ROOT, a.workload, seed, a.seconds, False,
                            t_start=t, devices=devices, log=log)
        per = {"seed": seed, "failed": run.failed,
               "attempted": run.attempted, "missing": run.missing,
               "samples": len(run.samples)}
        for kind, (ok, nums) in verdicts(run, limits, tol,
                                         a.control).items():
            per[kind] = {"correct": ok, **nums}
            correct[kind] += ok
            pick = max if kind == "program" else min
            for k, v in nums.items():
                worst[kind][k] = pick(worst[kind].get(
                    k, -math.inf if kind == "program" else math.inf), v)
        per["seconds"] = round(time.perf_counter() - t, 3)
        print(json.dumps(per), flush=True)
    print(json.dumps({"limits": limits, "worst": worst,
                      "seeds_correct": correct}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
